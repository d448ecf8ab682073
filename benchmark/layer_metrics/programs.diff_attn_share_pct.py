"""Differential attention's share of the macro-step: device time under
`diff_window` (eight window layers: the rings), `diff_full` (the one layer that
writes the pool) and `cross_attn` (the seven that read it), projections, cache
traffic, the pairs' difference and sub-norm together, in both halves, over the
device time of the window's macro-step executions (`phi4flash_spans.view`)."""
from benchmark import phi4flash_spans as S


def read(ctx):
    return S.share_reading(ctx["facts"], S.DIFF_ATTN)
