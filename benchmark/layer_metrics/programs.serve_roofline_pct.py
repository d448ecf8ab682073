"""The least time the chip could have taken for the traced window's tokens,
over the time the device was busy in it. The least time is the larger of the
bytes a decode must read (every weight once a step, for as few steps as the
lanes allow: tokens out / lanes) over the memory peak, and the operations the
tokens require (2 a weight for every prompt and output token) over the compute
peak. It is a lower bound on the work, so it cannot read over 100."""
from benchmark import model_math


def read(ctx):
    reduced = ctx["facts"].get("reduced") or {}
    counters = reduced.get("counters")
    if not counters or not reduced.get("busy_s"):
        return None
    cfg, lanes = ctx["config"], ctx["facts"]["lanes"]
    tokens = counters["prefill_tokens"] + counters["tokens_out"]
    nbytes = model_math.decode_read_bytes(cfg) * counters["tokens_out"] / lanes
    flops = model_math.forward_flops_per_token(cfg) * tokens
    roof = model_math.roofline(flops, nbytes, ctx["peaks"])
    return {"value": 100.0 * roof["least_s"] / reduced["busy_s"], "bound": roof["bound"],
            "least_s": roof["least_s"], "busy_s": reduced["busy_s"],
            "prompt_tokens": counters["prefill_tokens"], "output_tokens": counters["tokens_out"]}
