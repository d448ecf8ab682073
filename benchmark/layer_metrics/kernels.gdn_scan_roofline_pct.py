"""The admission's chunked delta rule against its roofline: the least time
the chip could take for the rule's own products over the REAL prompt tokens of
the counted executions (`prompt_tokens` of the `engine.resolve` spans x the
chunked rule's operations a token at the file's chunk size,
`model_math_qwen3_next.scan_flops_per_token`, over the compute peak) over the
device time under `admit_prefill/.../gdn_scan` in the same executions. Padding
to the bucket and the inverse of a chunk's triangular matrix are work the
program does and the roofline does not count."""
from benchmark import model_math_qwen3_next as mm, qwen3_next_spans as S


def read(ctx):
    view = S.qwen3_next_view(ctx["facts"])
    if not view:
        return None
    scan_s, tokens = view["counted"][f"{S.ADMIT}/{S.SCAN}"], view["counted_prompt_tokens"]
    if not scan_s or not tokens:
        return None
    flops = mm.scan_flops_per_token(ctx["config"]) * tokens
    least_s = flops / ctx["peaks"]["flops_per_s_bf16"]
    return {"value": 100.0 * least_s / scan_s, "bound": "compute", "least_s": least_s,
            "gdn_scan_s": scan_s, "prompt_tokens": tokens, "flops": flops,
            "counted_executions": view["counted_executions"]}
