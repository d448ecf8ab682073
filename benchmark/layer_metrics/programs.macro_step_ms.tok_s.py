"""`programs.macro_step_ms` for a cell whose judged figure is `tok_s`: the
accepted reader's value (the median device time of one execution of the paged
macro-step, from the trace's module line), read by that reader's own code. In
a closed loop with every lane full a macro-step is what an answer waits for
before the host sees it, and what the next admission waits for."""
from benchmark import common


def read(ctx):
    return common.load_module("layer_metrics", "programs.macro_step_ms").read(ctx)
