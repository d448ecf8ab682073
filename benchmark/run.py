#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. This process never initialises a JAX backend: it
starts a local ray_tpu cluster, brings the cell up through the normal entry
points in a worker granted `TPU: 1`, warms up, measures for --seconds, shuts
everything down and prints one JSON object as its last line. Without a chip it
exits non-zero and prints no result; it never falls back to the CPU.
"""
from __future__ import annotations

import time

# benchmark.common.clock(), before anything is imported; the wall clock only dates the logs
T_PROCESS_START = time.clock_gettime(time.CLOCK_MONOTONIC)
T_WALL_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WATCHDOG_S = 1150  # the first run of a cell in a checkout may take 1200 s


def preflight(chips: int) -> None:
    """Refuse to start where the run could not be a chip run."""
    from benchmark.common import require
    from ray_tpu._private.accelerator_detect import detect_tpu_chips

    require(not os.environ.get("RAY_TPU_WORKER_JAX_PLATFORMS"),
            "RAY_TPU_WORKER_JAX_PLATFORMS pins every worker's platform (the test suites' "
            "setting); unset it to run on the chip")
    platforms = os.environ.get("JAX_PLATFORMS")
    require(not platforms or "tpu" in platforms.split(","),
            f"JAX_PLATFORMS={platforms!r} holds JAX off the tpu backend")
    found = detect_tpu_chips()
    require(found >= chips, f"this cell needs {chips} chip(s); the host exposes {found} "
                            "(/dev/accel*, /dev/vfio/<n>)")


def dump_logs() -> None:
    """Keep the cluster's logs of a failed run where `.gitignore` lists them."""
    import shutil

    from benchmark import common

    log_dir = os.path.join(os.path.realpath("/tmp/ray_tpu/session_latest"), "logs")
    if os.path.isdir(log_dir) and os.path.getmtime(log_dir) >= T_WALL_START:
        shutil.copytree(log_dir, os.path.join(common.RUN_DIR, "logs"), dirs_exist_ok=True)
        for name in sorted(os.listdir(log_dir)):
            if name.startswith("worker-") or name.startswith("raylet-"):
                with open(os.path.join(log_dir, name), errors="replace") as f:
                    tail = f.readlines()[-25:]
                print(f"----- tail of {name}\n" + "".join(tail), flush=True)


def sweep_dead_rings(grace_s: float = 5.0) -> None:
    """The program's flight-recorder rings (`/dev/shm/ray_tpu_ring_<pid>_*`)
    outlive their processes on purpose and are reaped by the NEXT cluster's
    start; a run is the last cluster on its machine, so it reaps its own: a
    ring whose process is gone, by the program's own rule."""
    import re

    deadline = time.time() + grace_s
    while True:
        left = 0
        for name in os.listdir("/dev/shm"):
            m = re.match(r"ray_tpu_ring_(\d+)_", name)
            if not m:
                continue
            try:
                os.kill(int(m.group(1)), 0)
                left += 1  # its process is still going down
            except ProcessLookupError:
                try:
                    os.unlink(os.path.join("/dev/shm", name))
                except OSError:
                    pass
            except PermissionError:
                pass  # another user's
        if not left or time.time() > deadline:
            return
        time.sleep(0.2)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    def _watchdog():
        print(f"benchmark/run.py: no end after {WATCHDOG_S}s, giving up", flush=True)
        os._exit(1)

    timer = threading.Timer(WATCHDOG_S, _watchdog)
    timer.daemon = True
    timer.start()

    from benchmark import common
    from benchmark.common import note

    try:
        cell = common.load_cell(args.workload)
        preflight(cell["chips"])
        common.apply_env(cell["config_file"])
        os.makedirs(common.RUN_DIR, exist_ok=True)
        driver = importlib.import_module("benchmark.drivers." + cell["config_file"]["driver"])
        note(phase="start", workload=cell["name"], config=cell["config"], traffic=cell["traffic"],
             seed=args.seed, seconds=args.seconds, trace=args.trace)
        out = driver.run(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS_START)

        device = out["device"]
        checks = list(out["checks"])
        checks.append({"name": "device", "value": f"{device['platform']} x{device['count']}",
                       "limit": f"tpu x{cell['chips']}",
                       "ok": device["platform"] == "tpu" and device["count"] == cell["chips"]})
        peaks = common.peaks_for(device["kind"])  # an unknown device is an error
        for c in checks:  # every number compared, beside its limit
            note(phase="check", **c)
        correct = all(c["ok"] for c in checks)

        result_device = {"platform": device["platform"], "kind": device["kind"],
                         "count": device["count"],
                         "memory_peak_bytes": device["memory_peak_bytes"]}
        result = {"correct": correct, "attempted": out["attempted"], "failed": out["failed"]}
        if not args.trace:
            missing = [m["name"] for m in cell["end_to_end"] if out["e2e"].get(m["name"]) is None]
            common.require(not missing, f"no value for end-to-end metric(s) {missing}")
            result["metrics"] = {m["name"]: {"value": out["e2e"][m["name"]], "unit": m["unit"]}
                                 for m in cell["end_to_end"]}
        else:
            reduced = out["facts"]["reduced"]
            common.require(reduced and reduced["busy_s"] > 0,
                           "the traced window holds no operation on the device")
            common.require(reduced["busy_s"] <= reduced["window_s"],
                           f"device busy time {reduced['busy_s']} s passes the traced window "
                           f"{reduced['window_s']} s: the window's mark and the trace disagree")
            ctx = {"cell": cell, "config": cell["config_file"], "facts": out["facts"],
                   "e2e": out["e2e"], "peaks": peaks}
            metrics = {}
            for m in cell["per_layer"]:
                got = common.load_module("layer_metrics", m["name"]).read(ctx)
                if got is None:
                    continue  # nothing to read in this cell: left out of the line
                got = got if isinstance(got, dict) else {"value": got}
                note(phase="layer_metric", name=m["name"], layer=m["layer"], **got)
                metrics[m["name"]] = {"value": got["value"], "unit": m["unit"]}
            result["metrics"] = metrics
            result_device["busy_s"] = reduced["busy_s"]
            result_device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            keep = {k: reduced[k] for k in reduced if k != "ops"}
            with open(os.path.join(common.RUN_DIR, f"reduced.{cell['name']}.json"), "w") as f:
                json.dump({"workload": cell["name"], "seed": args.seed, "reduced": keep,
                           "ops_top": sorted(((k, v["total_s"], v["count"])
                                              for k, v in reduced["ops"].items()),
                                             key=lambda t: -t[1])[:60]}, f, indent=1)
        result["device"] = result_device
        # every number compared beside its limit: last in the line, and last on standard error
        result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"], "ok": c["ok"]}
                            for c in checks}
    except BaseException as e:  # the one boundary: say why, exit non-zero, no result line
        traceback.print_exc(file=sys.stdout)
        try:
            dump_logs()
        except Exception:
            pass
        note(phase="failed", error=f"{type(e).__name__}: {e}")
        return 1
    finally:
        sweep_dead_rings()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']}) {'ok' if c['ok'] else 'NOT OK'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
