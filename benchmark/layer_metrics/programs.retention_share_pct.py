"""Power retention's share of the macro-step: device time of the operations
under `retention_proj` (the mixers' projections, head norms, rotation and
W_o), `retention_scan` (the admission's chunked form) and `retention_update`
(the decode step's one-position recurrence), in both halves, over the device
time of the window's macro-step executions (`brumby_spans.view`). What is
left is the SwiGLU, the head and sampling. Printed beside it: seconds under
every scope of both halves and the admissions' share."""
from benchmark import brumby_spans as S


def read(ctx):
    v = S.brumby_view(ctx["facts"])
    if not v or not v["macro_step_s"]:
        return None
    w = v["window"]
    under = sum(w[f"{half}/{scope}"] for half in (S.ADMIT, S.DECODE) for scope in S.SCOPES)
    if not under:
        return None
    return {"value": 100.0 * under / v["macro_step_s"], "macro_step_s": v["macro_step_s"],
            "admit_share_of_macro_steps_pct": 100.0 * w[f"{S.ADMIT}/{S.ALL}"] / v["macro_step_s"],
            "executions": v["executions"], "counted_executions": v["counted_executions"],
            "admissions": v["counted_admissions"],
            **{k.replace("/", "_") + "_s": s for k, s in w.items()}}
