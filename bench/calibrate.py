"""Readings that a cell's limits are set from: the program and its control.

    python bench/calibrate.py --workload bitnet-1.3b.chat.c16 \
        --seconds 40 --seeds 11 12 13

For each seed, in one process: one run of the cell as the command makes
it, then on the same sample of requests the control (the plain reference
one precision below the configuration's, ``check.CONTROL``, in the
program's place: at each position the token it puts first, and its
first-layer K and V) and the fault of a token altered where it is
produced (one served token of each sampled request replaced), each judged
by the cell's own limits as the program is.  One JSON line per seed with
every reading and verdict.  The benchmark's own runs never run the
control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import check, harness, spec  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve_cell(spec.load_benchmark(), args.workload)
    spec.add_program_path()
    harness.chip_or_exit(cell["chips"])
    low = check.CONTROL[cell["config"]["dtype"]]
    for seed in args.seeds:
        r = harness.run_cell(cell, seed, args.seconds, False,
                             time.perf_counter(), control=low)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": r["correct"], "checks": r["checks"],
            "program": r["readings"], "control": r["control"],
            "fault_altered_token": r["fault_altered_token"],
            "metrics": r["metrics"], "device": r["device"]}), flush=True)


if __name__ == "__main__":
    main()
