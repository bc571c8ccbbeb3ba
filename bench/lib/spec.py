"""What a cell is: its entry in BENCHMARK.json and the files it names.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Each lives in a file of its own under ``bench/``, found by name:

  bench/configs/<config>.json   sizes of the model as run, with its source
  bench/models/<model>.py       the family's program config, weights, reference
  bench/traffic/<traffic>.json  the traffic mix: loop, clients, length laws
  bench/limits/<workload>.json  the limits ``correct`` is decided by
  bench/metrics/<metric>.py     one reader per per-layer metric

so a later change adds a configuration, a model family, a mix, a metric or
a cell by adding files and entries, and edits none of these modules.
"""

from __future__ import annotations

import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def add_program_path() -> None:
    """Put the program under test (``src/``) on the import path, or exit:
    a checkout that holds only the benchmark has nothing to run."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: the program is not in this checkout "
                         f"({src}/repro is missing)")
    if src not in sys.path:
        sys.path.insert(0, src)


def config_file(name: str) -> dict:
    return load_json(os.path.join(BENCH, "configs", f"{name}.json"))


def traffic_file(name: str) -> dict:
    return load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def limits_file(workload: str) -> dict:
    return load_json(os.path.join(BENCH, "limits", f"{workload}.json"))


def resolve_cell(bench: dict, name: str) -> dict:
    """The cell `name` with its configuration, traffic, limits and the
    metrics it reports, each read from its own file."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {', '.join(sorted(cells))})")
    w = cells[name]

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {
        "name": name,
        "chips": w["chips"],
        "config": config_file(w["config"]),
        "traffic": traffic_file(w["traffic"]),
        "limits": limits_file(name),
        "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
    }


def model_module(cfg_file: dict):
    """The module of the configuration's model family, by the name the file
    gives (``"model"``): ``bench/models/<model>.py`` holds its program
    configuration, weight draw and plain reference."""
    import importlib.util
    name = cfg_file["model"]
    path = os.path.join(BENCH, "models", f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"bench: no model module {path}")
    sp = importlib.util.spec_from_file_location(f"bench_model_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def max_len(cfg_file: dict) -> int:
    """The served context: the configuration's own."""
    return cfg_file["max_position_embeddings"]


def seed_key(seed: int):
    """A JAX key for any whole-number seed, also past 32 bits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    hi, lo = np.random.default_rng(seed).integers(0, 2 ** 32, 2,
                                                  dtype=np.uint64)
    return jax.random.wrap_key_data(jnp.asarray([hi, lo], jnp.uint32),
                                    impl="threefry2x32")


def prefill_lengths(mix: dict, pack: int) -> list[int]:
    """Every packed-prefill length the mix can produce: the multiples of the
    pack up to its longest prompt (the engine prefills the longest
    pack-aligned prefix of a prompt and feeds the rest token by token)."""
    return [n * pack for n in range(1, mix["prompt_tokens"]["max"] // pack + 1)]


def check_lengths(mix: dict, context: int) -> None:
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    if p["max"] + o["min"] > context:
        raise SystemExit(f"bench: the longest prompt leaves no room for the "
                         f"shortest output in a context of {context}")
    if not (0 < p["min"] <= p["max"] and 0 < o["min"] <= o["max"]
            and math.isfinite(p["sigma"]) and math.isfinite(o["sigma"])):
        raise SystemExit("bench: malformed length law in the traffic file")
