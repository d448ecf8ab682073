"""The flash-attention kernels' share of their roofline: the least time the
chip could take for the attention the traced steps require (forward's two
matrix products and backward's five, causal half; or its bytes, whichever
bounds) over the summed device time of the Pallas calls (forward, its
recomputation under remat, dK/dV and dQ) in the device trace."""
from benchmark import model_math

# how a Pallas call reads in the device trace's op line (trace_reduce.op_label);
# the train step holds no other Pallas kernel than flash attention's
KERNEL = "tpu_custom_call"


def read(ctx):
    facts = ctx["facts"]
    reduced = facts.get("reduced") or {}
    steps = facts.get("traced_steps")
    if not steps or not reduced.get("ops"):
        return None
    hits = {k: v for k, v in reduced["ops"].items() if k.endswith(KERNEL)}
    kernel_s = sum(v["total_s"] for v in hits.values()) / max(1, reduced["devices"])
    if kernel_s <= 0:
        return None
    job = facts["job"]
    flops = model_math.flash_step_flops(ctx["config"], job["batch"], job["seq_len"]) * steps
    nbytes = model_math.flash_step_bytes(ctx["config"], job["batch"], job["seq_len"]) * steps
    roof = model_math.roofline(flops, nbytes, ctx["peaks"])
    return {"value": 100.0 * roof["least_s"] / kernel_s, "bound": roof["bound"],
            "kernel_s": kernel_s, "least_s": roof["least_s"], "traced_steps": steps,
            "kernels": {k: v["total_s"] for k, v in hits.items()}}
