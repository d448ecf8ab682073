"""The decode-side delta-rule update's share of its roofline: the least time
the chip could take to read and write the float32 matrix state and the conv
tail once for each LIVE lane-step of the counted executions (`state_lanes` of
the `engine.resolve` spans x the bytes a lane-step must move,
`model_math_qwen3_next`, over the memory peak) over the device time under
`decode_chunk/.../gdn_update` in the same executions. The same work whatever
implements it; a lane that is not live is not counted, so this cannot read
over 100."""
from benchmark import model_math_qwen3_next as mm, qwen3_next_spans as S


def read(ctx):
    view = S.qwen3_next_view(ctx["facts"])
    if not view:
        return None
    update_s, lane_steps = view["counted"][f"{S.DECODE}/{S.UPDATE}"], view["counted_state_lanes"]
    if not update_s or not lane_steps:
        return None
    per = mm.update_bytes_per_lane_step(ctx["config"])
    least_s = per * lane_steps / ctx["peaks"]["hbm_bytes_per_s"]
    return {"value": 100.0 * least_s / update_s, "bound": "memory", "least_s": least_s,
            "gdn_update_s": update_s, "state_lanes": lane_steps, "steps": view["counted_steps"],
            "bytes_per_lane_step": per, "engine_state_bytes": ctx["facts"].get("state_bytes"),
            "counted_executions": view["counted_executions"]}
