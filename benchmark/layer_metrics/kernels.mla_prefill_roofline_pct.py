"""The admission attention's share of its roofline: the least time the chip
could take for the scores and values of the counted executions' causal (query,
key) pairs (`prompt_pairs` of their `engine.resolve` spans x 2 x heads x (nope +
rope + v) operations a pair and layer, `model_math_sarvam_mla`, over the bf16
peak) over the device time under `admit_prefill/.../mla_ctx` in the same
executions (the expansion of keys and values from the latent, the flash
kernel, the pool's write). The count is the model's 192 / 128 whatever shapes
the kernel is fed, and of real tokens only: padding to the bucket and to the
admission's width, and whole key blocks above the diagonal that a kernel
still touches, are work the program does and the roofline does not count.
Compute-bound."""
from benchmark import model_math_sarvam_mla as mm, sarvam_mla_spans as S


def read(ctx):
    view = S.mla_view(ctx["facts"])
    if not view:
        return None
    ctx_s = view["counted"][f"{S.ADMIT}/{S.CTX}"]
    pairs = view["counted_prompt_pairs"]
    if not ctx_s or not pairs:
        return None
    least_s = mm.mla_prefill_flops(ctx["config"], pairs) / ctx["peaks"]["flops_per_s_bf16"]
    return {"value": 100.0 * least_s / ctx_s, "bound": "compute", "least_s": least_s,
            "mla_ctx_s": ctx_s, "prompt_pairs": pairs, "prompt_tokens": view["counted_prompt_tokens"],
            "counted_executions": view["counted_executions"]}
