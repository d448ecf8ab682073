"""Submit to the start of the first `engine.plan` that found the request
waiting, a mean over admissions in ms: `plan_wait_us` / `admissions` from the
`engine.resolve` spans of the executions whole in the traced stretch
(`engine.dispatch_lead_ms` holds the shared reading). A request that arrives
inside a dispatch waits for the next plan whatever lanes are free: plan
granularity (ROADMAP S2(a)). With `engine.lane_wait_ms` it is
`engine.queue_ms`'s mean twin, up to the admitting plan's own duration."""
from benchmark import common

account = common.load_module("layer_metrics", "engine.dispatch_lead_ms")


def read(ctx):
    got = account.sums_with(ctx["facts"], "plan_wait_us")
    if not got or not got[1]["admissions"]:
        return None
    acc, s = got
    return {"value": account.stations(acc)["plan_wait_ms"], "plan_wait_us": s["plan_wait_us"],
            "admissions": s["admissions"], "executions": acc["executions"]}
