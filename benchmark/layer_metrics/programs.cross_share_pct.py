"""The cross-decoder's own mixers' share of the macro-step: device time under
`cross_attn` (the seven attentions that project a query only and read the full
layer's pool) and `gmu` (the seven gated memory units), in both halves, over
the device time of the window's macro-step executions
(`phi4flash_spans.view`). An admission runs them over one row a prompt, so
nearly all of it is decode. Printed beside it: the device's `self_rows` and
`cross_rows` of the counted executions beside their admissions."""
from benchmark import phi4flash_spans as S


def read(ctx):
    return S.share_reading(ctx["facts"], S.CROSS_DECODER)
