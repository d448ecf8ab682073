"""The Mamba-1 layers' share of the macro-step: device time of the operations
under `s6_proj` (the mixers' projections and gate), `s6_scan` (the admission's
conv and selective scan) and `s6_update` (the decode step's conv tail and
one-position recurrence), in both halves, over the device time of the window's
macro-step executions (`phi4flash_spans.view`). Printed beside it: seconds
under every scope of both halves and the admissions' share."""
from benchmark import phi4flash_spans as S


def read(ctx):
    return S.share_reading(ctx["facts"], S.S6)
