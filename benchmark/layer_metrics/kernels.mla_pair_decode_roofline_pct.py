"""The decode steps' latent attentions, TWO a layer, against their roofline:
the least time the chip could take to read the cached row of every attended
position in both planes of every layer (`ctx_tokens` of the `engine.resolve`
spans: positions summed over the dispatch's decode steps and live lanes, x
(latent + rope) numbers x 2 x layers) and W_kv_b's halves once a sublayer and
step (`model_math_longcat_flash.mla_pair_decode_bytes`, over the memory peak),
over the device time under `decode_chunk/.../mla_ctx` (the pool's write, the
loop over chunks of the pool) and `decode_chunk/.../mla_absorb` (W_uk into the
query, W_uv onto the attended latent) in the counted executions
(`longcat_flash_spans.view`). Memory-bound. The pool's rows are 640 columns for
the model's 576 and a chunk of 128 positions is gathered whole whatever the
lanes hold of it: the roofline counts the model's bytes, so the share shows
both. Printed beside it: the same with the other projections (`mla_proj`:
W_qa, W_qb, W_kv_a, Wo, their bytes once a sublayer and step) on both sides,
the attention halves of a decode step whole."""
from benchmark import longcat_flash_spans as S, model_math_longcat_flash as mm


def read(ctx):
    view = S.longcat_flash_view(ctx["facts"])
    if not view:
        return None
    p = view["counted"]
    ctx_s, absorb_s, proj_s = (p[f"{S.DECODE}/{scope}"] for scope in (S.CTX, S.ABSORB, S.PROJ))
    tokens, steps = view["counted_ctx_tokens"], view["counted_steps"]
    if not ctx_s or not tokens or not steps:
        return None
    cfg = ctx["config"]
    peak = ctx["peaks"]["hbm_bytes_per_s"]
    least_s = mm.mla_pair_decode_bytes(cfg, tokens, steps) / peak
    other_s = mm.attn_other_bytes(cfg, steps) / peak
    return {"value": 100.0 * least_s / (ctx_s + absorb_s), "bound": "memory", "least_s": least_s,
            "mla_ctx_s": ctx_s, "mla_absorb_s": absorb_s, "mla_proj_s": proj_s,
            "attention_halves_pct": 100.0 * (least_s + other_s) / (ctx_s + absorb_s + proj_s),
            "ctx_tokens": tokens, "steps": steps,
            "ctx_tokens_a_lane_step": tokens / view["counted_lane_steps"]
            if view["counted_lane_steps"] else None,
            "counted_executions": view["counted_executions"]}
