"""What the plan's counts do NOT explain of a request's time in flight, in ms, a
mean over the requests finishing inside the traced stretch: `flight_us` (its
admitting dispatch enqueued to the return of the fetch of its last dispatch)
minus `ahead_us`, the lead, its own admitting phase (`own_rows` x a row), its
answer (`decode_steps` x a step), others' admissions (`stall_rows` x a row)
and the tail on the device (`engine.request_lead_ms` holds the shared
reading). What is left is the seams between a request's dispatches (the device
idle between two short plans), the fixed cost of a dispatch, a host that came
late, and how far the stretch's mean step and row are from the request's own.
Printed: the same a seam (`dispatches` - 1), the share of requests with a late
resolve, and the WHOLE account: the five host stations, which tile the
engine's `finish` - `submit` exactly, their sum, the mean client latency of the
same request ids (`records`), the serve plane's part before and after the
engine on the one monotonic clock (`submit_us` - `t_due`, `t_done` -
`done_us`), and the residual in %: what two processes' readings of one clock
leave, where `engine.dispatch_lead_ms`' account assumed one dispatch an answer."""
import statistics

from benchmark import common

account = common.load_module("layer_metrics", "engine.request_lead_ms")


def whole_account(got, records):
    """The host stations of the finished requests beside the client's latency
    of the same rids; {} where the run's records hold none of them."""
    by_rid = {str(r["rid"]): r for r in records or [] if r["ok"] and r.get("t_done") is not None}
    mine = [(st, by_rid[str(st["rid"])]) for st in got["spans"] if str(st["rid"]) in by_rid]
    if not mine:
        return {}
    mean_us = lambda key: statistics.mean(int(st[key]) for st, _ in mine)  # noqa: E731
    stations = {key[:-3] + "_ms": 1e-3 * mean_us(key) for key in account.STATIONS}
    engine_ms = sum(stations.values())
    before = 1e3 * statistics.mean(1e-6 * int(st["submit_us"]) - r["t_due"] for st, r in mine)
    after = 1e3 * statistics.mean(r["t_done"] - 1e-6 * int(st["done_us"]) for st, r in mine)
    latency = 1e3 * statistics.mean(r["t_done"] - r["t_due"] for _, r in mine)
    total = engine_ms + before + after
    return {"stations": stations, "engine_finish_minus_submit_ms": engine_ms,
            "serve_plane_before_ms": before, "serve_plane_after_ms": after,
            "account_sum_ms": total, "mean_client_latency_ms": latency,
            "account_residual_pct": 100.0 * (latency - total) / latency,
            "account_requests": len(mine)}


def read(ctx):
    got = account.reading(ctx["facts"])
    if not got:
        return None
    m, c = got["mean_ms"], got["mean_count"]
    seams = c["dispatches"] - 1
    late = sum(int(st["late"]) > 0 for st in got["spans"])
    flight = {key: m[key] for key in ("ahead_ms", "lead_device_ms", "own_ms", "decode_ms",
                                      "stall_ms", "tail_device_ms", "unexplained_ms")}
    return {"value": m["unexplained_ms"], "requests": got["requests"],
            "unexplained_ms_a_seam": m["unexplained_ms"] / seams if seams > 0 else None,
            "unexplained_pct_of_flight": 100.0 * m["unexplained_ms"] / m["flight_ms"]
            if m["flight_ms"] else None,
            "dispatches_a_request": c["dispatches"], "late_requests_pct": 100.0 * late / got["requests"],
            "flight_ms": m["flight_ms"], "flight_split": flight,
            "decode_step_ms": got["decode_step_ms"], "admitted_row_ms": got["admitted_row_ms"],
            "executions": got["executions"],
            **whole_account(got, ctx["facts"].get("records"))}
