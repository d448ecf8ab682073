"""The expert layers' share of the macro-step: device time of the operations
under `moe_route` (scores, choice, weights), `moe_experts` (the routed
experts' products) and `moe_shared` (the shared expert), in both halves, over
the device time of the window's macro-step executions. Printed beside it:
seconds under each scope and in all of each half (the expert products'
kernels counted where they run: `program_spans.halves`), and from those the
whole decode step and the admission's share, as `programs.decode_step_ms` and
`programs.prefill_share_pct` read them since PR 35."""
from benchmark import afmoe_spans


def read(ctx):
    view = afmoe_spans.afmoe_view(ctx["facts"])
    if not view or not view["macro_step_s"]:
        return None
    w = view["window"]
    moe = sum(w[f"{half}/{scope}"] for half in (afmoe_spans.ADMIT, afmoe_spans.DECODE)
              for scope in afmoe_spans.MOE)
    if not moe:
        return None
    paired = view["paired"]
    return {"value": 100.0 * moe / view["macro_step_s"], "macro_step_s": view["macro_step_s"],
            "executions": view["executions"],
            "decode_step_ms": 1e3 * paired[f"{afmoe_spans.DECODE}/{afmoe_spans.ALL}"]
            / view["paired_steps"] if view["paired_steps"] else None,
            "prefill_share_pct": 100.0 * w[f"{afmoe_spans.ADMIT}/{afmoe_spans.ALL}"]
            / view["macro_step_s"],
            **{k.replace("/", "_") + "_s": v for k, v in w.items()}}
