"""The admission-side products of the HELD experts against their roofline:
the least time the chip could take for the held (row, expert) pairs of the
counted executions' REAL prompt tokens (`prompt_tokens` of their
`engine.resolve` spans x top_k x held / router's width pairs a token and
expert layer, three products a pair: `model_math_sarvam_mla`, over the bf16
peak) over the device time under `admit_prefill/.../moe_experts` in the same
executions (the compiler-named ragged kernels take their half by
`program_spans.halves`). The device counts held pairs in decode steps only, so
the admissions' are what even routing over the router's width gives: this
chip's quarter draws a few percent more or fewer by seed. Padding to the
bucket and to the admission's width is work the program does and the roofline
does not count. Compute-bound, and only just: at 4,096 rows a piece an expert
sees ~260 rows, beside the chip's ridge of 240 operations a byte, so reading
the held experts' matrices takes about as long as the products; the bytes are
not counted, which can only make the share read low."""
from benchmark import model_math_sarvam_mla as mm, sarvam_mla_spans as S


def read(ctx):
    view = S.mla_view(ctx["facts"])
    if not view:
        return None
    experts_s = view["counted"][f"{S.ADMIT}/{S.EXPERTS}"]
    tokens = view["counted_prompt_tokens"]
    if not experts_s or not tokens:
        return None
    least_s = mm.held_prefill_flops(ctx["config"], tokens) / ctx["peaks"]["flops_per_s_bf16"]
    return {"value": 100.0 * least_s / experts_s, "bound": "compute", "least_s": least_s,
            "moe_experts_s": experts_s, "prompt_tokens": tokens,
            "held_pairs_a_token": mm.held_pairs_per_token(ctx["config"]),
            "counted_executions": view["counted_executions"]}
