"""From the start of the plan that admits a request to the device start of its
own admitting phase, in ms, a MEAN over the requests that finish inside the
traced stretch, each with its WHOLE life: `plan_us` (the admitting plan's start
to its dispatch enqueued) + `ahead_us` (from there to the return of the fetch
of the dispatch that was in flight then; 0 where the device was idle) +
`lead_steps` x a decode step + `lead_rows` x an admitted row (what its
admitting dispatch runs before its own phase). `engine.dispatch_lead_ms` times
the same station from outside, as a MEDIAN over the stretch's admitting
dispatches; under short plans the lead has two modes (the device idle, or one
dispatch in flight), which this reader tells apart on the host's clock:
printed are the share of requests with `ahead_us` = 0 and each mode's mean.

This file also holds what the five readers of a request's own account share
(`engine.request_stall_ms`, `engine.request_tail_ms`,
`engine.request_unexplained_ms`, `engine.short_plan_pct` load it by name).
The account is the program's: one `engine.request` span a request, written by
the engine where the request finishes (`ray_tpu.observability.REQUEST_SPAN`
says what every stat is), with five host stations that tile submit to finish
on `perf_counter` and the plan's counts summed over EVERY dispatch the request
rode. A reader here takes the spans with `reason` `length` or `stop` that
start inside the traced window, multiplies the counts by what a decode step
and an admitted token row cost the device (`decode_chunk`'s time over `steps`,
`admit_prefill`'s over `admit_rows`, both of the executions counted by
`engine.dispatch_lead_ms.run_stretch`, unedited) and takes means over the
requests. A program that writes no such span (the parent of PR 54) gives an
empty list, and every reader returns None."""
import statistics

from benchmark import common, program_spans

account = common.load_module("layer_metrics", "engine.dispatch_lead_ms")

REQUEST = "engine.request"
FINISHED = ("length", "stop")
STATIONS = ("unseen_us", "lane_wait_us", "plan_us", "flight_us", "deliver_us")


def finished(trace):
    """Stats of the `engine.request` spans of requests that ran to their end
    and finished inside the traced window."""
    window = trace.get("window")
    if not window:
        return []
    lo, hi = window
    return [st for name, start, _, st in trace["spans"]
            if name == REQUEST and lo <= start <= hi and st.get("reason") in FINISHED]


def units(acc):
    """(a decode step, an admitted token row) in ms on the device, of the
    stretch's counted executions; None where the stretch ran none."""
    s = acc["sums"]
    step = 1e3 * acc["decode_s"] / s["steps"] if s.get("steps") else None
    row = 1e3 * acc["admit_s"] / s["admit_rows"] if s.get("admit_rows") else None
    return step, row


def parts(st, step_ms, row_ms):
    """One request's account in ms: what the plan's counts explain of its
    `flight_us` at the stretch's device costs, and what they leave."""
    ms = lambda key: 1e-3 * int(st[key])  # noqa: E731
    n = lambda key: int(st[key])  # noqa: E731
    step, row = step_ms or 0.0, row_ms or 0.0
    lead_device = n("lead_steps") * step + n("lead_rows") * row
    tail_device = n("tail_steps") * step + n("tail_rows") * row
    out = {"plan_ms": ms("plan_us"), "ahead_ms": ms("ahead_us"), "lead_device_ms": lead_device,
           "own_ms": n("own_rows") * row, "decode_ms": n("decode_steps") * step,
           "stall_ms": n("stall_rows") * row, "tail_device_ms": tail_device,
           "deliver_ms": ms("deliver_us"), "flight_ms": ms("flight_us")}
    out["lead_ms"] = out["plan_ms"] + out["ahead_ms"] + lead_device
    out["tail_ms"] = tail_device + out["deliver_ms"]
    out["unexplained_ms"] = out["flight_ms"] - (out["ahead_ms"] + lead_device + out["own_ms"]
                                                + out["decode_ms"] + out["stall_ms"] + tail_device)
    return out


def reduce(spans, acc):
    """The means every reader here takes its value from; None where the trace
    holds no finished request or the stretch no counted execution."""
    if not spans or not acc:
        return None
    step_ms, row_ms = units(acc)
    if step_ms is None:
        return None
    per = [parts(st, step_ms, row_ms) for st in spans]
    mean = lambda rows, key: statistics.mean(r[key] for r in rows)  # noqa: E731
    count = lambda key: statistics.mean(int(st[key]) for st in spans)  # noqa: E731
    return {"requests": len(spans), "decode_step_ms": step_ms, "admitted_row_ms": row_ms,
            "executions": acc["executions"], "spans": spans, "per_request": per,
            "mean_ms": {key: mean(per, key) for key in per[0]},
            "mean_count": {key: count(key) for key in (
                "dispatches", "lead_steps", "lead_phases", "lead_rows", "own_rows", "decode_steps",
                "stall_phases", "stall_rows", "tail_steps", "tail_phases", "tail_rows", "late",
                "tokens")}}


def reading(facts):
    """`reduce` of this run's trace, worked out once for all its readers."""
    trace = program_spans.run_trace(facts)
    if trace is None:
        return None
    if "request_account" not in trace:
        trace["request_account"] = reduce(finished(trace), account.run_stretch(facts))
    return trace["request_account"]


def read(ctx):
    got = reading(ctx["facts"])
    if not got:
        return None
    idle = [p for p, st in zip(got["per_request"], got["spans"]) if int(st["ahead_us"]) == 0]
    behind = [p for p, st in zip(got["per_request"], got["spans"]) if int(st["ahead_us"]) > 0]
    lead = lambda rows: statistics.mean(p["lead_ms"] for p in rows) if rows else None  # noqa: E731
    m, c = got["mean_ms"], got["mean_count"]
    return {"value": m["lead_ms"], "requests": got["requests"],
            "plan_ms": m["plan_ms"], "ahead_ms": m["ahead_ms"], "device_ms": m["lead_device_ms"],
            "lead_steps": c["lead_steps"], "lead_phases": c["lead_phases"], "lead_rows": c["lead_rows"],
            "device_idle_at_enqueue_pct": 100.0 * len(idle) / got["requests"],
            "lead_ms_device_idle": lead(idle), "lead_ms_behind_a_dispatch": lead(behind),
            "decode_step_ms": got["decode_step_ms"], "admitted_row_ms": got["admitted_row_ms"],
            "executions": got["executions"]}
