"""Decode steps a finished answer waits on the device for its dispatch to end:
the plan's own counts, carried by each `engine.dispatch` span of the window
(`finish_wait_steps` summed over `finishing` summed). A request's tokens reach
the host only when its whole dispatch resolves, so every step the dispatch
runs after a request's last is latency added to it. Printed beside it: the
same in milliseconds at `programs.decode_step_ms`."""
from benchmark import program_spans


def read(ctx):
    view = program_spans.run_serve_view(ctx["facts"])
    if not view or not view["finishing"]:
        return None
    steps = view["finish_wait_steps"] / view["finishing"]
    step_ms = program_spans.decode_step_ms(view)
    return {"value": steps, "dispatches": view["dispatches"], "finishing": view["finishing"],
            "finish_wait_steps": view["finish_wait_steps"],
            "wait_ms_at_decode_step_ms": steps * step_ms if step_ms else None}
