"""The expert layers' share of a train step: device time of the operations
under `moe_route` (scores, choice, weights) and `moe_experts` (sort, gathers,
the ragged products and their backward, the weighted sum back), forward,
rematerialised and backward together, over the traced window's device busy
time. Printed beside it: seconds under every scope of the step and how far
they, with the rest, are from the busy time."""
from benchmark import lfm2_moe_spans as S


def read(ctx):
    return S.share(ctx["facts"], S.MOE)
