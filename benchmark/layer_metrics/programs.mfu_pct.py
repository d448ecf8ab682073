"""Model FLOP/s utilization: tokens per second times the operations the
forward and backward passes require per token (causal attention counted once,
recomputation not at all) over the chip's peak. A per-layer metric comes from
a traced run, where starting and stopping the profiler sits inside the window:
the rate is taken over the steps the profiler did not touch."""
from benchmark import model_math


def read(ctx):
    rate = ctx["facts"].get("train_tok_s_untraced") or ctx["e2e"].get("train_tok_s")
    if rate is None:
        return None
    per_token = model_math.train_flops_per_token(ctx["config"], ctx["facts"]["job"]["seq_len"])
    return {"value": 100.0 * rate * per_token / ctx["peaks"]["flops_per_s_bf16"],
            "train_tok_s": rate, "flops_per_token": per_token}
