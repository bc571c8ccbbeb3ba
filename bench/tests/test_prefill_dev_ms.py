"""The ``prefill_dev_ms`` reader against hand counts, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

It reads the device time of the module the program names ``jit_prefill``
per pack of the window's admissions, and nothing from a program whose
prefill module has another name.
"""

from __future__ import annotations

import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402,F401  (puts bench/ on the import path)
from lib import harness, trace as TR  # noqa: E402

READ = harness.load_reader("prefill_dev_ms")


def _ctx(prefill_module: str):
    # window [0, 10]: a step [1, 2], two prefills [3, 5] and [6, 6.5], and a
    # third [9.5, 11] clipped to [9.5, 10]; three admissions of 600, 300 and
    # 100 tokens at a pack of 256: 2 + 1 + 0 packs
    mods = [[("jit__step_fn(1)", 1.0, 2.0), (f"{prefill_module}(2)", 3.0, 5.0),
             (f"{prefill_module}(3)", 6.0, 6.5),
             (f"{prefill_module}(2)", 9.5, 11.0)]]
    ops = [[("%fusion.1 = f32[8] fusion(...)", 1.0, 2.0)]]
    tr = TR.Trace(ops, mods, [("bench.traced", 0.0, 10.0)], (0.0, 10.0))
    loop = types.SimpleNamespace(log={7: {"prompt_len": 600},
                                      8: {"prompt_len": 300},
                                      9: {"prompt_len": 100}})
    return {"trace": tr, "loop": loop, "uids": range(7, 10),
            "cfg": {"lpsa": {"pack": 256}}, "ticks": (0, 1)}


def test_prefill_time_per_pack():
    assert READ(_ctx("jit_prefill")) == pytest.approx(1e3 * 3.0 / 3)


def test_an_unnamed_prefill_reads_nothing():
    assert READ(_ctx("jit__lambda")) is None


def test_no_packs_reads_nothing():
    ctx = _ctx("jit_prefill")
    ctx["uids"] = range(9, 10)
    assert READ(ctx) is None
