"""The decode-side Mamba-1 state update's share of its roofline: the least
time the chip could take to read and write the float32 state and the conv
tail once for each LIVE lane-step of the counted executions, with the rows
that go in and out of it, and the layers' A and conv weights once a step
(`state_lanes` and `steps` of the `engine.resolve` spans x
`model_math_phi4flash`, over the memory peak) over the device time under
`decode_chunk/.../s6_update` (the kernel `s6_update`, the conv step, the tail's
select) in the same executions. The same work whatever implements it; a lane
that is not live is not counted, so this cannot read over 100."""
from benchmark import model_math_phi4flash as mm, phi4flash_spans as S


def read(ctx):
    view = S.phi4flash_view(ctx["facts"])
    if not view:
        return None
    update_s, lane_steps = view["counted"][f"{S.DECODE}/{S.UPDATE}"], view["counted_state_lanes"]
    if not update_s or not lane_steps:
        return None
    per = mm.s6_update_bytes_per_lane_step(ctx["config"])
    nbytes = per * lane_steps + mm.s6_update_bytes_per_step(ctx["config"]) * view["counted_steps"]
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return {"value": 100.0 * least_s / update_s, "bound": "memory", "least_s": least_s,
            "s6_update_s": update_s, "state_lanes": lane_steps, "steps": view["counted_steps"],
            "bytes_per_lane_step": per, "engine_state_bytes": ctx["facts"].get("state_bytes"),
            "counted_executions": view["counted_executions"]}
