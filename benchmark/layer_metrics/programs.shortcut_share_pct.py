"""The shortcut branch's share of a decode step: device time of the operations
under `moe_route`, `moe_experts` and `moe_zero` inside `decode_chunk` over all
of `decode_chunk`, in the window's macro-step executions
(`longcat_flash_spans.view`). On one chip the branch runs in line with the
rest of the layer; in the deployment it is what overlaps the second
attention and dense FFN, so its share of a step is what the shortcut could
hide. Printed beside it: the shares of the two attentions (`mla_*`) and of the
dense FFNs (`ffn_dense`) of the same steps, the admissions' share of the
macro-steps, and from the device's counts of the counted executions the share
of a live row's choices that fell on real experts (`real_choices / (real_choices
+ zero_choices)`) and real experts a token."""
from benchmark import longcat_flash_spans as S, model_math_longcat_flash as mm


def read(ctx):
    view = S.longcat_flash_view(ctx["facts"])
    if not view or not view["macro_step_s"]:
        return None
    w = view["window"]
    decode_s = w[f"{S.DECODE}/{S.ALL}"]
    under = lambda scopes: sum(w[f"{S.DECODE}/{scope}"] for scope in scopes)  # noqa: E731
    if not decode_s or not under(S.SHORTCUT):
        return None
    real, zero = view["counted_real_choices"], view["counted_zero_choices"]
    return {"value": 100.0 * under(S.SHORTCUT) / decode_s, "decode_chunk_s": decode_s,
            "mla_share_pct": 100.0 * under(S.MLA) / decode_s,
            "ffn_dense_share_pct": 100.0 * under((S.DENSE,)) / decode_s,
            "admit_share_of_macro_steps_pct":
                100.0 * w[f"{S.ADMIT}/{S.ALL}"] / view["macro_step_s"],
            "real_choice_share": real / (real + zero) if real + zero else None,
            "real_experts_a_token": mm.real_experts_per_token(ctx["config"], real, zero)
            if real + zero else None,
            "executions": view["executions"], "counted_executions": view["counted_executions"],
            **{k.replace("/", "_") + "_s": v for k, v in w.items()}}
