"""Attention's share of the decode steps: device time under `attn_window`
(the sliding-window layers: projections, the ring's write and read, softmax,
output gate) and `attn_full` (the full layers, through the block pool) inside
`decode_chunk`, over all device time of `decode_chunk` (the expert products'
kernels included, which carry no scope of their own: `afmoe_spans.scoped`), in
the window's macro-step executions. Printed beside it: each scope's seconds,
and the share of the paired dispatches' live lane-steps whose context passes
the window (`past_window_lane_steps` / `lane_steps` of the `engine.dispatch`
spans)."""
from benchmark import afmoe_spans


def read(ctx):
    view = afmoe_spans.afmoe_view(ctx["facts"])
    if not view:
        return None
    w = view["window"]
    decode_s = w[f"{afmoe_spans.DECODE}/{afmoe_spans.ALL}"]
    window_s = w[f"{afmoe_spans.DECODE}/{afmoe_spans.WINDOW}"]
    full_s = w[f"{afmoe_spans.DECODE}/{afmoe_spans.FULL}"]
    if not decode_s or not window_s + full_s:
        return None
    lane_steps = view["paired_lane_steps"]
    return {"value": 100.0 * (window_s + full_s) / decode_s,
            "attn_window_s": window_s, "attn_full_s": full_s, "decode_s": decode_s,
            "past_window_lane_steps": view["paired_past_window_lane_steps"],
            "lane_steps": lane_steps,
            "past_window_share": view["paired_past_window_lane_steps"] / lane_steps
            if lane_steps else None}
