"""The flash-attention kernels' share of their roofline in the LFM2-MoE train
step: `kernels.flash_roofline_pct` with the ATTENTION layers counted
(`model_math_lfm2_moe.attention_layers`: three of this cell's twelve layers,
heads of 64) where that reader's arithmetic takes every layer for one: the
least time the chip could take for the attention the traced steps require
(forward's two matrix products and backward's five, causal half; or its
bytes, whichever bounds) over the summed device time of the Pallas calls
(forward, its recomputation under remat, dK/dV and dQ) in the device trace,
taken by the kernels' own names (`flash_fwd`, `flash_bwd_dkdv`,
`flash_bwd_dq`: ops/flash_attention.py): this step's trace labels the
compiler's ragged-product kernels `tpu_custom_call` as well, a thousand of
them in four steps (my chip run, PR 57: with them counted in, the first
traced run of this reader read 9.0 where the flash kernels alone read 19.6)."""
from benchmark import model_math_lfm2_moe as mm

# how a flash kernel's call reads in the device trace's op line (trace_reduce.op_label):
# `flash_fwd.6 tpu_custom_call`
PREFIX, KERNEL = "flash_", "tpu_custom_call"


def read(ctx):
    facts = ctx["facts"]
    reduced = facts.get("reduced") or {}
    steps = facts.get("traced_steps")
    if not steps or not reduced.get("ops"):
        return None
    hits = {k: v for k, v in reduced["ops"].items() if k.startswith(PREFIX) and k.endswith(KERNEL)}
    kernel_s = sum(v["total_s"] for v in hits.values()) / max(1, reduced["devices"])
    if kernel_s <= 0:
        return None
    job = facts["job"]
    flops = mm.flash_step_flops(ctx["config"], job["batch"], job["seq_len"]) * steps
    nbytes = mm.flash_step_bytes(ctx["config"], job["batch"], job["seq_len"]) * steps
    roof = mm.roofline(flops, nbytes, ctx["peaks"])
    return {"value": 100.0 * roof["least_s"] / kernel_s, "bound": roof["bound"],
            "kernel_s": kernel_s, "least_s": roof["least_s"], "traced_steps": steps,
            "attention_layers": mm.attention_layers(ctx["config"]),
            "kernels": {k: v["total_s"] for k, v in hits.items()}}
