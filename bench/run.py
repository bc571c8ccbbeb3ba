"""Run one cell of the chip benchmark once and print its result line.

    python bench/run.py --workload bitnet-1.3b.chat.c16 --seed 7 \
        --seconds 45 --trace 0

The cell is an entry of ``workloads`` in BENCHMARK.json; everything it
names is read from files under ``bench/`` (see ``lib/spec.py``).  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace of the window.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with a trace
``breakdown``), then ``checks``, each number compared beside its limit.

Without a TPU, with a device kind missing from ``lib/peaks.json``, with
fewer chips than the cell asks for, or without the program (``src/``) in
the checkout, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import harness, spec  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.resolve_cell(spec.load_benchmark(), args.workload)
    spec.add_program_path()
    harness.chip_or_exit(cell["chips"])
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
