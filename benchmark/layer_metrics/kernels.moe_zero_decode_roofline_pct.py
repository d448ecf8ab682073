"""The decode steps' shortcut branch (the router over real and identity
experts, the HELD real experts' products, the identity experts' term) against
its roofline: the least time the chip could take to read each hit held
expert's matrices once, move each held (row, expert) pair's row in and out
(`experts_hit`, `expert_rows` of the `engine.resolve` spans, the device's own
counts over the dispatch's decode steps and expert layers), read the router
once a layer and step and move a live row in and out of the identity term a
layer (`model_math_longcat_flash.moe_zero_decode_bytes`, over the memory peak),
over the device time under `decode_chunk/.../moe_route` + `moe_experts` +
`moe_zero` in the same executions (the compiler-named ragged kernels take
their half by `program_spans.halves`). The counts are of work that must be
done with the routing as it fell, so this cannot read over 100. Printed
beside it: seconds under each of the three scopes, held experts hit a step
and layer against what uniform routing over the router's 768 outputs would
give for the live rows, and real experts a token."""
from benchmark import longcat_flash_spans as S, model_math_longcat_flash as mm


def read(ctx):
    view = S.longcat_flash_view(ctx["facts"])
    if not view:
        return None
    p = view["counted"]
    route_s, experts_s, zero_s = (p[f"{S.DECODE}/{scope}"] for scope in S.SHORTCUT)
    hit, rows, steps, lane_steps = (view["counted_experts_hit"], view["counted_expert_rows"],
                                    view["counted_steps"], view["counted_lane_steps"])
    if not experts_s or not zero_s or not steps:
        return None
    cfg = ctx["config"]
    s = mm.shapes(cfg)
    least_s = (mm.moe_zero_decode_bytes(cfg, hit, rows, steps, lane_steps)
               / ctx["peaks"]["hbm_bytes_per_s"])
    layer_steps = steps * s["L"]
    live_rows = lane_steps / steps
    return {"value": 100.0 * least_s / (route_s + experts_s + zero_s), "bound": "memory",
            "least_s": least_s, "moe_route_s": route_s, "moe_experts_s": experts_s,
            "moe_zero_s": zero_s, "experts_hit": hit, "expert_rows": rows,
            "expert_rows_max": view["counted_expert_rows_max"], "steps": steps,
            "held_hit_a_layer_step": hit / layer_steps, "live_rows_a_step": live_rows,
            "uniform_held_hit": mm.expected_held_hit(cfg, round(live_rows)),
            "real_experts_a_token": mm.real_experts_per_token(
                cfg, view["counted_real_choices"], view["counted_zero_choices"]),
            "counted_executions": view["counted_executions"]}
