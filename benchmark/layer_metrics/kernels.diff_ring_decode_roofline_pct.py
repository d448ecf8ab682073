"""The decode steps' window attentions over the lanes' rings against their
roofline: the least time the chip could take to read, in each of the eight
window layers, the window's 512 positions of keys and values for every live
lane-step whose context has passed the window (`past_window_lane_steps` of the
`engine.resolve` spans; a lane-step that has not is counted as one position: a
floor) and the layer's Wqkv and out_proj once a step
(`model_math_phi4flash.diff_ring_decode_bytes`, over the memory peak), over the
device time under `decode_chunk/.../diff_window` in the counted executions
(`phi4flash_spans.view`). Memory-bound. The program reads every lane's whole
ring, live or not, past the window or not, and multiplies over all 1,280
columns: the roofline counts the model's bytes, so the share shows both."""
from benchmark import model_math_phi4flash as mm, phi4flash_spans as S


def read(ctx):
    view = S.phi4flash_view(ctx["facts"])
    if not view:
        return None
    ring_s = view["counted"][f"{S.DECODE}/{S.WINDOW}"]
    lane_steps, steps = view["counted_lane_steps"], view["counted_steps"]
    if not ring_s or not lane_steps or not steps:
        return None
    past = view["counted_past_window_lane_steps"]
    least_s = (mm.diff_ring_decode_bytes(ctx["config"], lane_steps, past, steps)
               / ctx["peaks"]["hbm_bytes_per_s"])
    return {"value": 100.0 * least_s / ring_s, "bound": "memory", "least_s": least_s,
            "diff_window_s": ring_s, "lane_steps": lane_steps, "past_window_lane_steps": past,
            "steps": steps, "counted_executions": view["counted_executions"]}
