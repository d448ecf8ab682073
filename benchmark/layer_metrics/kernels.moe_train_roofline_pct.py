"""The expert layer's ragged products in a train step against their roofline:
the least time the chip could take for the nine products a layer and step
(three forward, six backward) over the pairs the traced steps really held
(`held_pairs`, the step's own counter): the larger of their operations over
the bf16 peak and their least bytes over the memory bandwidth
(`model_math_lfm2_moe`; the forward's three made again under remat are NOT
counted as required), over the summed device time of the compiler-named
`ragged-dot` kernels in the traced window. Says which of the two bounds."""
from benchmark import lfm2_moe_spans as S, model_math_lfm2_moe as mm


def read(ctx):
    facts = ctx["facts"]
    view = S.train_view(facts)
    steps, pairs = facts.get("traced_steps"), facts.get("held_pairs_traced")
    if not view or not view["ragged_s"] or not steps or not pairs:
        return None
    devices = max(1, facts["reduced"].get("devices", 1))
    kernel_s = view["ragged_s"] / devices
    cfg = ctx["config"]
    flops = mm.ragged_flops(cfg, pairs)
    nbytes = mm.ragged_bytes(cfg, pairs, mm.expert_layers(cfg) * steps)
    roof = mm.roofline(flops, nbytes, ctx["peaks"])
    return {"value": 100.0 * roof["least_s"] / kernel_s, "bound": roof["bound"],
            "kernel_s": kernel_s, "least_s": roof["least_s"], "compute_s": roof["compute_s"],
            "memory_s": roof["memory_s"], "held_pairs": pairs, "traced_steps": steps,
            "ragged_calls": view["ragged_calls"], "ragged_metadata_s": view["ragged_metadata_s"]}
