#!/usr/bin/env python3
"""Find the knee of an open-loop serve cell once, by a sweep on the chip.

    python benchmark/sweep.py --workload chat-steady --rates 1.0 1.5 2.0 2.5 --seconds 30

One process, one deployment: the cell is brought up and warmed once, then each
rate is offered for --seconds with the cell's own lengths, drained, and the
next follows. For each rate it records latency, tokens per second and the
backlog. The knee is the highest rate at which the queue did not grow through
the window: the requests still in flight when the window closed are no more
than twice the lanes, and the last third's median latency is under twice the
lowest rate's median (comparing a window's own thirds misfires at a low load,
where they differ by chance alone: my first sweep, PR 26, flagged 1.6/s and
passed 2.0/s). The traffic file's rate is four fifths of it, written there
by hand as a number. Like run.py, this never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import statistics
import sys
import time

T_PROCESS_START = time.clock_gettime(time.CLOCK_MONOTONIC)  # benchmark.common.clock()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from benchmark import common, traffic
    from benchmark.drivers import serve as driver
    from benchmark.run import preflight

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2147483701)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import ray_tpu
    from ray_tpu import serve

    cell = common.load_cell(args.workload)
    common.require(cell["traffic_file"]["kind"] == "serve_open", "only an open loop has a knee")
    preflight(cell["chips"])
    common.apply_env(cell["config_file"])
    lanes = cell["config_file"]["serve"]["n_slots"]
    rows = []
    ray_tpu.init()
    try:
        handle, cfg, info = driver.bring_up(cell, args.seed)
        for n, rate in enumerate(args.rates):
            tf = copy.deepcopy(cell["traffic_file"])
            tf["arrivals"]["rate_per_s"] = rate
            plan_ = traffic.plan(tf, args.seed + n, args.seconds, cfg.vocab_size)
            window = traffic.run_window(handle, plan_, args.seconds)
            s = traffic.summarize(window)
            recs, t_end = window["records"], window["t0"] + args.seconds
            third = args.seconds / 3.0
            lat = lambda lo, hi: [  # noqa: E731
                (r["t_done"] - r["t_due"]) * 1e3 for r in recs
                if r["ok"] and lo <= r["t_due"] - window["t0"] < hi]
            first, last = lat(0, third), lat(2 * third, args.seconds)
            row = {"rate_per_s": rate, "offered": len(recs), "failed": s["failed"],
                   "latency_p50_ms": s["latency_p50_ms"], "latency_p90_ms": s["latency_p90_ms"],
                   "tok_s": s["tok_s"], "completed_in_window": s["completed_in_window"],
                   "in_flight_at_close": sum(1 for r in recs if r["t_done"] is None or r["t_done"] > t_end),
                   "first_third_p50_ms": statistics.median(first) if first else None,
                   "last_third_p50_ms": statistics.median(last) if last else None,
                   "drain_s": s["drain_s"], "generator_late_ms_max": s["generator_late_ms_max"]}
            rows.append(row)
            row["sustained"] = bool(
                row["in_flight_at_close"] <= 2 * lanes and last
                and row["last_third_p50_ms"] < 2.0 * rows[0]["latency_p50_ms"])
            common.note(phase="sweep", **row)
        device = driver.call(handle, "bench_device")
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
    sustained = [r["rate_per_s"] for r in rows if r["sustained"]]
    record = {"workload": args.workload, "seconds_per_rate": args.seconds, "seed": args.seed,
              "device": {k: device[k] for k in ("platform", "kind", "count")},
              "rows": rows, "knee_per_s": max(sustained, default=None),
              "four_fifths_per_s": 0.8 * max(sustained) if sustained else None,
              "setup": {k: info[k] for k in ("deploy_s", "warm_s")}}
    out = args.out or os.path.join(common.BENCH_DIR, "out", f"sweep.{args.workload}.json")
    with open(out, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({k: record[k] for k in ("workload", "knee_per_s", "four_fifths_per_s")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
