"""The admission scan's share of its roofline: the least time the chip could
take for the chunked scan of the REAL prompt tokens of the paired dispatches
(`prompt_tokens` of the `engine.dispatch` spans x the scan's operations and
bytes a token, `model_math_granite_hybrid`) over the device time under
`ssm_scan` in the paired executions. Padding to the bucket is work the
program does and the roofline does not count."""
from benchmark import hybrid_spans, model_math_granite_hybrid as mm


def read(ctx):
    view = hybrid_spans.hybrid_view(ctx["facts"])
    if not view:
        return None
    scan_s, tokens = view["paired"][hybrid_spans.SCAN], view["paired_prompt_tokens"]
    if not scan_s or not tokens:
        return None
    roof = mm.roofline(mm.scan_flops_per_token(ctx["config"]) * tokens,
                       mm.scan_bytes_per_token(ctx["config"]) * tokens, ctx["peaks"])
    return {"value": 100.0 * roof["least_s"] / scan_s, "bound": roof["bound"],
            "least_s": roof["least_s"], "ssm_scan_s": scan_s, "prompt_tokens": tokens,
            "paired_executions": view["paired_executions"]}
