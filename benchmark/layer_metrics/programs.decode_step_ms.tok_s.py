"""`programs.decode_step_ms` for a cell whose judged figure is `tok_s` and
whose macro-steps are long beside its traced stretch: device time of the
operations under the `decode_chunk` scope over the decode steps planned
(`steps`), both over the executions that lie whole in the window, found by
their `engine.resolve` spans (`sarvam_mla_spans.view`: the accepted reader
pairs by `engine.dispatch` and finds one execution or none in 2.5 s of
macro-steps of 0.6-1.0 s)."""
from benchmark import sarvam_mla_spans as S


def read(ctx):
    view = S.mla_view(ctx["facts"])
    if not view or not view["counted_steps"]:
        return None
    decode_s = view["counted"][f"{S.DECODE}/{S.ALL}"]
    return {"value": 1e3 * decode_s / view["counted_steps"], "decode_chunk_s": decode_s,
            "steps": view["counted_steps"], "counted_executions": view["counted_executions"]}
