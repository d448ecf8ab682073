"""What a request that finishes has sat through of OTHERS' admissions, in ms:
`stall_lane_phases` (over the admitting phases, the lanes live through one
and admitted before it) x an admitting phase's device time / `finishing`,
from the `engine.resolve` spans and the `admit_prefill` scope of the
executions whole in the traced stretch (`engine.dispatch_lead_ms` holds the
shared reading). `programs.prefill_share_pct` says what admissions cost the
DEVICE; this is what they cost a REQUEST, and what an admission token budget
(ROADMAP S2(b)) would shorten."""
from benchmark import common

account = common.load_module("layer_metrics", "engine.dispatch_lead_ms")


def read(ctx):
    got = account.sums_with(ctx["facts"], "stall_lane_phases", "admit_phases")
    if not got or not got[1]["finishing"] or not got[1]["admit_phases"]:
        return None
    acc, s = got
    st = account.stations(acc)
    return {"value": st["admit_stall_ms"], "stall_lane_phases": s["stall_lane_phases"],
            "finishing": s["finishing"], "admit_phases": s["admit_phases"],
            "admit_phase_ms": st["admit_phase_ms"], "admit_prefill_s": acc["admit_s"],
            "stalls_a_finishing": s["stall_lane_phases"] / s["finishing"],
            "executions": acc["executions"]}
