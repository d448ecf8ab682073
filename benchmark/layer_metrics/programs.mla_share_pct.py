"""Latent attention's share of the macro-step: device time of the operations
under `mla_proj` (Wq, W_kv_a, norms and RoPE, Wo; `mla_absorb` inside it,
W_kv_b's halves in a decode step) and
`mla_ctx` (the pool's write and read, scores, softmax, values; the admission's
flash kernel), in both halves, over the device time of the window's macro-step
executions. Printed beside it: seconds under each scope and in all of each
half, and the expert layer's share (`moe_route` + `moe_experts` +
`moe_shared`) of the same executions."""
from benchmark import sarvam_mla_spans as S


def read(ctx):
    view = S.mla_view(ctx["facts"])
    if not view or not view["macro_step_s"]:
        return None
    w = view["window"]
    share = lambda scopes: sum(w[f"{half}/{scope}"] for half in (S.ADMIT, S.DECODE)  # noqa: E731
                               for scope in scopes)
    if not share(S.MLA):
        return None
    macro = view["macro_step_s"]
    return {"value": 100.0 * share(S.MLA) / macro, "macro_step_s": macro,
            "executions": view["executions"], "moe_share_pct": 100.0 * share(S.MOE) / macro,
            **{k.replace("/", "_") + "_s": v for k, v in w.items()}}
