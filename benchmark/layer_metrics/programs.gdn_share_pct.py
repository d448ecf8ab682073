"""The gated-delta-rule linear-attention layers' share of the macro-step:
device time of the operations under `gdn_proj` (the two input projections,
the admission's conv, norms, gates, the output projection), `gdn_scan` (the
admission's chunked rule) and `gdn_update` (the decode step's conv tail and
one-position rule), in both halves, over the device time of the window's
macro-step executions. Printed beside it: seconds under each scope and in all
of each half, the attention mixers' share (`attn_full`) and the expert layers'
(`moe_route` + `moe_experts` + `moe_shared`) of the same executions, and what
is left for the head, the norms and the writes between scopes."""
from benchmark import qwen3_next_spans as S


def read(ctx):
    view = S.qwen3_next_view(ctx["facts"])
    if not view or not view["macro_step_s"]:
        return None
    w = view["window"]
    share = lambda scopes: sum(w[f"{half}/{scope}"] for half in (S.ADMIT, S.DECODE)  # noqa: E731
                               for scope in scopes)
    if not share(S.GDN):
        return None
    macro = view["macro_step_s"]
    pct = {"value": 100.0 * share(S.GDN) / macro, "attn_full_pct": 100.0 * share((S.ATTN,)) / macro,
           "moe_share_pct": 100.0 * share(S.MOE) / macro}
    return {**pct, "rest_pct": 100.0 - sum(pct.values()), "macro_step_s": macro,
            "executions": view["executions"],
            **{k.replace("/", "_") + "_s": v for k, v in w.items()}}
