"""Share of the dispatched lane-steps that stayed empty with NOBODY waiting
when their phase was planned: `vacant_lane_steps` over `lane_steps` + vacant +
blocked + spent (which is `n_slots` x `steps`), from the `engine.resolve`
spans of the executions whole in the traced stretch (`engine.dispatch_lead_ms`
holds the shared reading). What a shorter plan, or a plan made later, would
fill: the next request came inside a dispatch. Printed beside it: the
stretch's own occupancy, its blocked and spent shares (the four are 100), and
the window's `engine.lane_occupancy_pct` from the engine's counters."""
from benchmark import common

account = common.load_module("layer_metrics", "engine.dispatch_lead_ms")
LANES = ("lane_steps", "vacant_lane_steps", "blocked_lane_steps", "spent_lane_steps")


def shares(ctx):
    """The four shares of the stretch's lane-steps in %, and their bases."""
    got = account.sums_with(ctx["facts"], *LANES)
    if not got or not sum(got[1][k] for k in LANES):
        return None
    acc, s = got
    total = sum(s[k] for k in LANES)
    engine = ctx["facts"].get("engine") or {}
    lanes = ctx["facts"].get("lanes")
    return {"occupancy_pct": 100.0 * s["lane_steps"] / total,
            "vacant_pct": 100.0 * s["vacant_lane_steps"] / total,
            "blocked_pct": 100.0 * s["blocked_lane_steps"] / total,
            "spent_pct": 100.0 * s["spent_lane_steps"] / total,
            **{k: s[k] for k in LANES}, "steps": s["steps"], "executions": acc["executions"],
            "check_all_lane_steps_accounted": total == lanes * s["steps"] if lanes else None,
            "window_lane_occupancy_pct": (100.0 * engine["useful_slot_steps"] / engine["slot_steps"]
                                          if engine.get("slot_steps") else None)}


def read(ctx):
    got = shares(ctx)
    return {"value": got["vacant_pct"], **got} if got else None
