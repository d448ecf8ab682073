"""The admission's chunked power retention against its roofline: the least
time the chip could take for the chunked form, at the configuration's chunk
size, over the REAL prompt tokens of the counted executions (`prompt_tokens`
and `admissions` of the `engine.resolve` spans: the operations and bytes of
that many prompts of the mean length, which never overcounts,
`model_math_brumby`: the larger of operations over the compute peak and bytes
over the memory peak) over the device time under
`admit_prefill/.../retention_scan` in the same executions. Padding to the
bucket, and the columns the program's layout of the symmetric square adds to
its 8,256, are work the program does and the roofline does not count."""
from benchmark import brumby_spans as S, model_math_brumby as mm


def read(ctx):
    view = S.brumby_view(ctx["facts"])
    if not view:
        return None
    scan_s, tokens = view["counted"][f"{S.ADMIT}/{S.SCAN}"], view["counted_prompt_tokens"]
    if not scan_s or not tokens:
        return None
    n = view["counted_admissions"]
    roof = mm.roofline(mm.retention_scan_flops(ctx["config"], tokens, n),
                       mm.retention_scan_bytes(ctx["config"], tokens, n), ctx["peaks"])
    return {"value": 100.0 * roof["least_s"] / scan_s, "bound": roof["bound"],
            "least_s": roof["least_s"], "retention_scan_s": scan_s, "prompt_tokens": tokens,
            "admissions": n, "admit_rows": view["counted_admit_rows"],
            "us_a_real_token_and_layer": 1e6 * scan_s / tokens / mm.shapes(ctx["config"])["L"],
            "counted_executions": view["counted_executions"]}
