"""The decode step's retention update against its roofline: the least time
the chip could take to read and write the float32 state (S and z of every KV
head: 8 x (8,256 x 128 + 8,256) numbers a layer at the published sizes) once
for each LIVE lane-step of the counted executions, with the rows that go in
and out of it (`state_lanes` of the `engine.resolve` spans x
`model_math_brumby`, over the memory peak) over the device time under
`decode_chunk/.../retention_update` (the kernel `retention_update` and the two
expansions that feed it) in the same executions. The same work whatever
implements it and however the program pads the state; a lane that is not live
is not counted, so this cannot read over 100."""
from benchmark import brumby_spans as S, model_math_brumby as mm


def read(ctx):
    view = S.brumby_view(ctx["facts"])
    if not view:
        return None
    update_s, lane_steps = view["counted"][f"{S.DECODE}/{S.UPDATE}"], view["counted_state_lanes"]
    if not update_s or not lane_steps:
        return None
    per = mm.retention_update_bytes_per_lane_step(ctx["config"])
    roof = mm.roofline(mm.retention_update_flops_per_lane_step(ctx["config"]) * lane_steps,
                       per * lane_steps, ctx["peaks"])
    return {"value": 100.0 * roof["least_s"] / update_s, "bound": roof["bound"],
            "least_s": roof["least_s"], "retention_update_s": update_s, "state_lanes": lane_steps,
            "steps": view["counted_steps"], "bytes_per_lane_step": per,
            "us_a_lane_step_and_layer": 1e6 * update_s / lane_steps / mm.shapes(ctx["config"])["L"],
            "engine_state_bytes": ctx["facts"].get("state_bytes"),
            "counted_executions": view["counted_executions"]}
