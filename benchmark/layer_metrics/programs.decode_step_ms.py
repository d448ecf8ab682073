"""Device time of one decode step of the macro-step: the time of the
operations under the `decode_chunk` scope over the decode steps dispatched
(`steps` of the `engine.dispatch` spans), both over the window's executions
that could be paired with their dispatch and that the trace holds whole (its
last one is cut by the profiler's stop: `program_spans.whole_in_window`)."""
from benchmark import program_spans


def read(ctx):
    view = program_spans.run_serve_view(ctx["facts"])
    value = program_spans.decode_step_ms(view) if view else None
    if value is None:
        return None
    return {"value": value, "decode_chunk_s": view["paired_decode_s"],
            "steps": view["paired_steps"], "paired_executions": view["paired"]}
