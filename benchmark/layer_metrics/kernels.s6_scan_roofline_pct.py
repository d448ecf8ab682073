"""The admission's conv and selective scan against their roofline: the least
time the chip could take for the recurrence over the REAL prompt tokens of the
counted executions (`prompt_tokens` of the `engine.resolve` spans x the
operations and bytes a token, `model_math_phi4flash`: the larger of operations
over the compute peak and bytes over the memory peak) over the device time
under `admit_prefill/.../s6_scan` in the same executions. Padding to the
bucket is work the program does and the roofline does not count; the
recurrence runs on the vector unit, so the compute peak (the matrix unit's) is
a bound it cannot reach."""
from benchmark import model_math_phi4flash as mm, phi4flash_spans as S


def read(ctx):
    view = S.phi4flash_view(ctx["facts"])
    if not view:
        return None
    scan_s, tokens = view["counted"][f"{S.ADMIT}/{S.SCAN}"], view["counted_prompt_tokens"]
    if not scan_s or not tokens:
        return None
    roof = mm.roofline(mm.s6_scan_flops_per_token(ctx["config"]) * tokens,
                       mm.s6_scan_bytes_per_token(ctx["config"]) * tokens, ctx["peaks"])
    return {"value": 100.0 * roof["least_s"] / scan_s, "bound": roof["bound"],
            "least_s": roof["least_s"], "s6_scan_s": scan_s, "prompt_tokens": tokens,
            "self_rows": view["counted_self_rows"],
            "us_a_real_token_and_layer": 1e6 * scan_s / tokens / mm.shapes(ctx["config"])["Lm"],
            "counted_executions": view["counted_executions"]}
