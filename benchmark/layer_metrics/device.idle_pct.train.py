"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    reduced = ctx["facts"].get("reduced") or {}
    if not reduced.get("window_s"):
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
