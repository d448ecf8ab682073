#!/usr/bin/env python3
"""The control of `correct`: the system given weights rounded to fewer bits
than the configuration states, compared with the reference as every run is.

    python benchmark/control.py --workload chat-steady --seeds 11 12 13 --precision fp8

It has to come out as NOT correct, on every seed, at the cell's own size on the
chip. One process, one cluster, one deployment (or fit) a seed; the window is
short because no timing is taken. `--precision none` reads sound runs the same
way. The benchmark's own runs never run this; its small twin is
benchmark/tests/test_control.py. The readings it gave on the chip, and the
limits set from them, are in PERF.md section 2.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import importlib

    import ray_tpu

    from benchmark import common
    from benchmark.run import preflight

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", choices=("fp8", "int8", "none"), default="fp8")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = common.load_cell(args.workload)
    preflight(cell["chips"])
    common.apply_env(cell["config_file"])
    driver = importlib.import_module("benchmark.drivers." + cell["config_file"]["driver"])
    precision = None if args.precision == "none" else args.precision
    rows = []
    ray_tpu.init()
    try:
        for seed in args.seeds:
            out = driver.measure(cell, seed, args.seconds, False, common.clock(),
                                 lower_precision=precision)
            row = {"workload": args.workload, "seed": seed, "precision": args.precision,
                   "checks": out["checks"], "correct": all(c["ok"] for c in out["checks"])}
            common.note(phase="control", **row)
            rows.append(row)
    finally:
        ray_tpu.shutdown()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    if precision and any(r["correct"] for r in rows):
        print("THE CONTROL PASSED AS CORRECT: the comparison cannot tell a lower precision")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
