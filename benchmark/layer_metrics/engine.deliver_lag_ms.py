"""How long after a dispatch's last operation left the device the host has
handed its tokens on: the median, over the window's dispatches, of the end of
`engine.resolve(seq)` minus the end of that dispatch's macro-step execution,
both on the trace's clock. Printed beside it: the part of it spent until the
blocking fetch returned (the rest is delivery work), and how the pairing of
dispatches with executions came out."""
import statistics

from benchmark import program_spans


def read(ctx):
    view = program_spans.run_serve_view(ctx["facts"])
    if not view or not view["deliver_lag_s"]:
        return None
    fetch = view["fetch_lag_s"]
    return {"value": 1e3 * statistics.median(view["deliver_lag_s"]),
            "samples": len(view["deliver_lag_s"]),
            "max_ms": 1e3 * max(view["deliver_lag_s"]),
            "until_fetch_returned_ms": 1e3 * statistics.median(fetch) if fetch else None,
            "until_fetch_returned_max_ms": 1e3 * max(fetch) if fetch else None,
            "executions": view["executions"], "paired": view["paired"],
            "unpaired_executions": view["unpaired_executions"],
            "unpaired_dispatches": view["unpaired_dispatches"],
            "check_at_most_one_unpaired_at_each_end":
                view["unpaired_executions"] <= 1 and view["unpaired_dispatches"] <= 1}
