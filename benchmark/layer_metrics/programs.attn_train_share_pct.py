"""The attention layers' share of a train step: device time under `attn` (an
attention layer's projections, head norms, RoPE and the flash kernels),
forward, rematerialised and backward together, over the traced window's
device busy time."""
from benchmark import lfm2_moe_spans as S


def read(ctx):
    return S.share(ctx["facts"], (S.ATTN,))
