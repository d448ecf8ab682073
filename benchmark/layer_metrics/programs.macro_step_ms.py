"""Median device time of one execution of the jitted paged macro-step (up to 8
phases of admissions and decode chunks in one dispatch), from the device
trace's module line. The program gives that jitted partial no name, so the
trace calls it `jit__unknown`: the reader takes the module that holds most of
the traced window's device time, which in a serve cell is the macro-step, and
says which it took."""


def read(ctx):
    modules = (ctx["facts"].get("reduced") or {}).get("modules") or {}
    if not modules:
        return None
    name, m = max(modules.items(), key=lambda kv: kv[1]["total_s"])
    return {"value": m["median_s"] * 1e3, "module": name, "executions": m["count"],
            "total_s": m["total_s"]}
