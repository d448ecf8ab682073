"""Model FLOP/s utilization of the LFM2-MoE train step: the operations the
steps REQUIRE (`model_math_lfm2_moe.train_flops`: 6 a matrix weight outside
the experts and in the head a token, causal attention once, 18 x 2048 x 1792 a
HELD PAIR by the step's own `held_pairs` counter, recomputation not at all)
over the chip's peak, over the steps the profiler did not touch. It reads the
same work whatever implements the layers: a form that multiplies rows no
expert was given reads lower, not higher."""
from benchmark import model_math_lfm2_moe as mm


def read(ctx):
    facts = ctx["facts"]
    if not facts.get("untraced_s") or facts.get("held_pairs_untraced") is None:
        return None
    job = facts["job"]
    tokens = facts["untraced_steps"] * job["batch"] * job["seq_len"]
    flops = mm.train_flops(ctx["config"], tokens, job["seq_len"], facts["held_pairs_untraced"])
    return {"value": 100.0 * flops / facts["untraced_s"] / ctx["peaks"]["flops_per_s_bf16"],
            "train_tok_s": tokens / facts["untraced_s"], "flops_per_token": flops / tokens,
            "held_pairs_per_token_and_layer": facts["held_pairs_untraced"] / tokens
            / mm.expert_layers(ctx["config"]), "untraced_steps": facts["untraced_steps"]}
