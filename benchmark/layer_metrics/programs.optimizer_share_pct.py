"""The optimizer's share of a train step: device time under `optimizer`
(train/step.py: the gradient's norm and clip, AdamW's moments, the update),
over the traced window's device busy time. Bound by bytes: it reads and writes
every parameter and both moments once a step whatever the batch."""
from benchmark import lfm2_moe_spans as S


def read(ctx):
    return S.share(ctx["facts"], (S.OPTIMIZER,))
