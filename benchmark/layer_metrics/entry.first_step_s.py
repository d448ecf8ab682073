"""Seconds from the `JaxTrainer.fit()` call to the end of the last compiling
train step (the second: it sees donated buffers). Part of `setup_s`."""


def read(ctx):
    return ctx["facts"].get("first_step_s")
