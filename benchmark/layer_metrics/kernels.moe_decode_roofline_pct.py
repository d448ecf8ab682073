"""The decode-side expert products' share of their roofline: the least time
the chip could take to read each HIT expert's matrices once and move each
routed row in and out (`experts_hit` and `expert_rows` of the `engine.resolve`
spans, the device's own counts over the dispatch's decode steps and expert
layers, x `model_math_afmoe`'s bytes, over the memory peak) over the device
time under `decode_chunk/.../moe_experts` in the same executions. The counts
are of work that must be done with the routing as it fell, so this cannot read
over 100; a layer that streams every expert's matrices reads about the share
of experts hit. Printed beside it: experts hit a step and layer, against what
uniform routing would give for the live rows."""
from benchmark import afmoe_spans, model_math_afmoe as mm


def read(ctx):
    view = afmoe_spans.afmoe_view(ctx["facts"])
    if not view:
        return None
    experts_s = view["counted"][f"{afmoe_spans.DECODE}/{afmoe_spans.EXPERTS}"]
    hit, rows, steps = (view["counted_experts_hit"], view["counted_expert_rows"],
                        view["counted_steps"])
    if not experts_s or not hit:
        return None
    cfg = ctx["config"]
    s = mm.shapes(cfg)
    least_s = mm.expert_decode_bytes(cfg, hit, rows) / ctx["peaks"]["hbm_bytes_per_s"]
    layer_steps = max(1, steps * s["Lm"])
    live_rows = rows / s["k"] / layer_steps
    return {"value": 100.0 * least_s / experts_s, "bound": "memory", "least_s": least_s,
            "moe_experts_s": experts_s, "experts_hit": hit, "expert_rows": rows,
            "expert_rows_max": view["counted_expert_rows_max"], "steps": steps,
            "experts_hit_a_layer_step": hit / layer_steps, "live_rows_a_step": live_rows,
            "uniform_experts_hit": mm.expected_experts_hit(cfg, round(live_rows)),
            "counted_executions": view["counted_executions"]}
