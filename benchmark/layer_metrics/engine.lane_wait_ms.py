"""From the first plan that found a request waiting to the start of the plan
that admits it, a mean over admissions in ms: `lane_wait_us` / `admissions`
from the `engine.resolve` spans of the executions whole in the traced stretch
(`engine.dispatch_lead_ms` holds the shared reading). 0 for a request that
the first plan to see it admits; the rest waited because no lane or no block
was free: capacity, where `engine.plan_wait_ms` is plan granularity. Printed
beside it: how many of the admissions were admitted by their first plan."""
from benchmark import common

account = common.load_module("layer_metrics", "engine.dispatch_lead_ms")


def read(ctx):
    got = account.sums_with(ctx["facts"], "lane_wait_us", "admitted_first_plan")
    if not got or not got[1]["admissions"]:
        return None
    acc, s = got
    return {"value": account.stations(acc)["lane_wait_ms"], "lane_wait_us": s["lane_wait_us"],
            "admissions": s["admissions"], "admitted_first_plan": s["admitted_first_plan"],
            "admitted_first_plan_pct": 100.0 * s["admitted_first_plan"] / s["admissions"],
            "executions": acc["executions"]}
