"""Median device time of one call of the flash-attention backward kernel that
computes dQ (the Pallas call named `flash_bwd_dq`): one call a layer a
step. Printed beside it: the calls counted in the traced steps."""
from benchmark import program_spans


def read(ctx):
    return program_spans.kernel_reading(ctx["facts"], "flash_bwd_dq")
