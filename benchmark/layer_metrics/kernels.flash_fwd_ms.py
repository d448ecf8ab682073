"""Median device time of one call of the flash-attention forward kernel (the
Pallas call named `flash_fwd`; under remat every layer runs it twice a step,
and both are counted). Printed beside it: the calls, and a check of the three
kernel readings against the time `kernels.flash_roofline_pct` sums over every
`tpu_custom_call`: 2 x forward + dQ + dK/dV, times layers and traced steps."""
from benchmark import common, program_spans


def read(ctx):
    facts = ctx["facts"]
    out = program_spans.kernel_reading(facts, "flash_fwd")
    if out is None:
        return None
    dq = program_spans.kernel_reading(facts, "flash_bwd_dq")
    dkdv = program_spans.kernel_reading(facts, "flash_bwd_dkdv")
    roofline = common.load_module("layer_metrics", "kernels.flash_roofline_pct").read(ctx)
    if roofline and dq and dkdv:
        calls = ctx["config"]["num_hidden_layers"] * facts["traced_steps"]
        out["from_medians_s"] = 1e-3 * (2 * out["value"] + dq["value"] + dkdv["value"]) * calls
        out["roofline_kernel_s"] = roofline["kernel_s"]
        out["check_within_5pct_of_roofline_kernel_s"] = (
            abs(out["from_medians_s"] - roofline["kernel_s"]) <= 0.05 * roofline["kernel_s"])
    return out
