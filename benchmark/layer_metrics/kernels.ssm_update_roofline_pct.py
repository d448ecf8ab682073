"""The decode-side state update's share of its roofline: the least time the
chip could take to read and write the conv tail and SSM state once for each
LIVE lane-step of the paired dispatches (`state_lanes` of the `engine.dispatch`
spans x the bytes a lane's state takes, over the memory peak) over the device
time under `ssm_update` in the paired executions. The program moves every
lane's row, live or not, so this is a lower bound on the work and cannot read
over 100."""
from benchmark import hybrid_spans, model_math_granite_hybrid as mm


def read(ctx):
    view = hybrid_spans.hybrid_view(ctx["facts"])
    if not view:
        return None
    update_s, lane_steps = view["paired"][hybrid_spans.UPDATE], view["paired_state_lanes"]
    if not update_s or not lane_steps:
        return None
    nbytes = mm.update_bytes_per_lane_step(ctx["config"]) * lane_steps
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return {"value": 100.0 * least_s / update_s, "bound": "memory", "least_s": least_s,
            "ssm_update_s": update_s, "state_lanes": lane_steps, "steps": view["paired_steps"],
            "bytes_per_lane_step": mm.update_bytes_per_lane_step(ctx["config"]),
            "engine_state_bytes": ctx["facts"].get("state_bytes"),
            "paired_executions": view["paired_executions"]}
