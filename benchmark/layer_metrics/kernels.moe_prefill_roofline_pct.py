"""The admission-side expert products' share of their roofline: the least
time the chip could take for the routed experts' products of the REAL prompt
tokens of the paired dispatches (`prompt_tokens` of the `engine.dispatch`
spans x top_k pairs a token and expert layer, `model_math_afmoe`'s operations
and bytes) over the device time under `admit_prefill/.../moe_experts` in the
paired executions. Padding to the bucket and to the admission's width is work
the program does and the roofline does not count."""
from benchmark import afmoe_spans, model_math_afmoe as mm


def read(ctx):
    view = afmoe_spans.afmoe_view(ctx["facts"])
    if not view:
        return None
    experts_s = view["paired"][f"{afmoe_spans.ADMIT}/{afmoe_spans.EXPERTS}"]
    tokens = view["paired_prompt_tokens"]
    if not experts_s or not tokens:
        return None
    roof = mm.roofline(mm.expert_prefill_flops(ctx["config"], tokens),
                       mm.expert_prefill_bytes(ctx["config"], tokens), ctx["peaks"])
    return {"value": 100.0 * roof["least_s"] / experts_s, "bound": roof["bound"],
            "least_s": roof["least_s"], "moe_experts_s": experts_s, "prompt_tokens": tokens,
            "paired_executions": view["paired_executions"]}
