"""The decode steps' cross attentions, seven readers of ONE pool layer,
against their roofline: the least time the chip could take to read the keys
and values of every attended position once a reading layer (`ctx_tokens` of
the `engine.resolve` spans: positions summed over the dispatch's decode steps
and live lanes, x 5,120 B x 7) and each layer's Wq and out_proj once a step
(`model_math_phi4flash.cross_attn_decode_bytes`, over the memory peak), over
the device time under `decode_chunk/.../cross_attn` in the counted executions
(`phi4flash_spans.view`). Memory-bound. A chunk of 128 positions is gathered
whole whatever the lanes hold of it and multiplied over all 1,280 columns: the
roofline counts the model's bytes, so the share shows both."""
from benchmark import model_math_phi4flash as mm, phi4flash_spans as S


def read(ctx):
    view = S.phi4flash_view(ctx["facts"])
    if not view:
        return None
    cross_s = view["counted"][f"{S.DECODE}/{S.CROSS}"]
    tokens, steps = view["counted_ctx_tokens"], view["counted_steps"]
    if not cross_s or not tokens or not steps:
        return None
    least_s = mm.cross_attn_decode_bytes(ctx["config"], tokens, steps) / ctx["peaks"]["hbm_bytes_per_s"]
    return {"value": 100.0 * least_s / cross_s, "bound": "memory", "least_s": least_s,
            "cross_attn_s": cross_s, "ctx_tokens": tokens, "steps": steps,
            "ctx_tokens_a_lane_step": tokens / view["counted_lane_steps"]
            if view["counted_lane_steps"] else None,
            "diff_full_s": view["counted"][f"{S.DECODE}/{S.FULL}"],
            "counted_executions": view["counted_executions"]}
