"""The gated short convolutions' share of a train step: device time under
`short_conv` (a conv layer's in-projection, its two gates, the three taps and
the out-projection), forward, rematerialised and backward together, over the
traced window's device busy time."""
from benchmark import lfm2_moe_spans as S


def read(ctx):
    return S.share(ctx["facts"], (S.CONV,))
