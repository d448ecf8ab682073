"""The state-space layers' share of the macro-step: device time of the
operations under `ssm_scan` (admission's chunked scan, conv included),
`ssm_update` (a decode step's conv and state update) and `ssm_proj` (the
mixers' projections and gated norm) over the device time of the window's
macro-step executions. Printed beside it: seconds under each scope and under
`attn_mix`, the four attention layers' mixers."""
from benchmark import hybrid_spans


def read(ctx):
    view = hybrid_spans.hybrid_view(ctx["facts"])
    if not view or not view["macro_step_s"]:
        return None
    w = view["window"]
    ssm = w[hybrid_spans.SCAN] + w[hybrid_spans.UPDATE] + w[hybrid_spans.PROJ]
    if not ssm:
        return None
    return {"value": 100.0 * ssm / view["macro_step_s"], "macro_step_s": view["macro_step_s"],
            "ssm_scan_s": w[hybrid_spans.SCAN], "ssm_update_s": w[hybrid_spans.UPDATE],
            "ssm_proj_s": w[hybrid_spans.PROJ], "attn_mix_s": w[hybrid_spans.ATTN],
            "executions": view["executions"]}
