"""The admission prefill's share of the macro-step: device time of the
operations under the `admit_prefill` scope over the device time of the
window's macro-step executions. Printed beside it: seconds under each scope
and under neither (operations outside both, and the device's turn-around
between operations inside an execution), and the prompt tokens admitted."""
from benchmark import program_spans


def read(ctx):
    view = program_spans.run_serve_view(ctx["facts"])
    if not view or not view["macro_step_s"]:
        return None
    macro = view["macro_step_s"]
    return {"value": 100.0 * view["admit_s"] / macro, "macro_step_s": macro,
            "admit_prefill_s": view["admit_s"], "decode_chunk_s": view["decode_s"],
            "neither_s": view["neither_s"], "unscoped_ops_s": view["unscoped_ops_s"],
            "executions": view["executions"], "prompt_tokens": view["paired_prompt_tokens"],
            "check_neither_under_5pct": view["neither_s"] <= 0.05 * macro}
