"""The decode steps' latent attention against its roofline: the least time the
chip could take to read the cached row of every attended position
(`ctx_tokens` of the `engine.resolve` spans: positions summed over the
dispatch's decode steps and live lanes, x (latent + rope) numbers x layers)
and W_kv_b once a layer and step (`model_math_sarvam_mla.mla_decode_bytes`,
over the memory peak), over the device time under `decode_chunk/.../mla_ctx`
(the pool's write, the loop over chunks of the pool) and
`decode_chunk/.../mla_absorb` (W_uk into the query, W_uv onto the attended
latent) in the counted executions (`sarvam_mla_spans.view`). Memory-bound. The pool's rows are 640
columns for the model's 576 and a chunk of 128 positions is gathered whole
whatever the lanes hold of it: the roofline counts the model's bytes, so the
share shows both. Printed beside it: the same with the other projections
(`mla_proj`: Wq, W_kv_a, Wo, their bytes once a layer and step) on both sides,
the attention half of a decode step whole."""
from benchmark import model_math_sarvam_mla as mm, sarvam_mla_spans as S


def read(ctx):
    view = S.mla_view(ctx["facts"])
    if not view:
        return None
    p = view["counted"]
    ctx_s, absorb_s, proj_s = (p[f"{S.DECODE}/{scope}"] for scope in (S.CTX, S.ABSORB, S.PROJ))
    tokens, steps = view["counted_ctx_tokens"], view["counted_steps"]
    if not ctx_s or not tokens or not steps:
        return None
    cfg = ctx["config"]
    s = mm.shapes(cfg)
    peak = ctx["peaks"]["hbm_bytes_per_s"]
    least_s = mm.mla_decode_bytes(cfg, tokens, steps) / peak
    other_s = (steps * s["L"] * (mm.attn_matmul_params(cfg) - mm.kv_b_params(cfg))
               * mm.BYTES[cfg["torch_dtype"]]) / peak
    return {"value": 100.0 * least_s / (ctx_s + absorb_s), "bound": "memory", "least_s": least_s,
            "mla_ctx_s": ctx_s, "mla_absorb_s": absorb_s, "mla_proj_s": proj_s,
            "attention_half_pct": 100.0 * (least_s + other_s) / (ctx_s + absorb_s + proj_s),
            "ctx_tokens": tokens, "steps": steps,
            "ctx_tokens_a_lane_step": tokens / view["counted_lane_steps"]
            if view["counted_lane_steps"] else None,
            "counted_executions": view["counted_executions"]}
