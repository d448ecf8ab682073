"""The decode-side products of the HELD experts (128 of a 512-wide router,
512 wide) against their roofline: `kernels.moe_held_roofline_pct`'s arithmetic
with this model's shapes (that reader takes sarvam's `model_math` by import).
The least time the chip could take to read each hit held expert's matrices
once and move each held (row, expert) pair's row in and out (`experts_hit` and
`expert_rows` of the `engine.resolve` spans x `model_math_qwen3_next`'s bytes,
over the memory peak) over the device time under
`decode_chunk/.../moe_experts` in the same executions. The counts are of work
that must be done with the routing as it fell, so this cannot read over 100.
Printed beside it: held experts hit a step and layer, against what uniform
routing over the router's width would give for the live rows."""
from benchmark import model_math_qwen3_next as mm, qwen3_next_spans as S


def read(ctx):
    view = S.qwen3_next_view(ctx["facts"])
    if not view:
        return None
    experts_s = view["counted"][f"{S.DECODE}/{S.EXPERTS}"]
    hit, rows, steps = (view["counted_experts_hit"], view["counted_expert_rows"],
                        view["counted_steps"])
    if not experts_s or not hit:
        return None
    cfg = ctx["config"]
    s = mm.shapes(cfg)
    least_s = mm.expert_decode_bytes(cfg, hit, rows) / ctx["peaks"]["hbm_bytes_per_s"]
    layer_steps = max(1, steps * s["L"])
    # a live row has k pairs over the router's width, k E / Er of them held
    live_rows = rows / (s["k"] * s["E"] / s["Er"]) / layer_steps
    return {"value": 100.0 * least_s / experts_s, "bound": "memory", "least_s": least_s,
            "moe_experts_s": experts_s, "experts_hit": hit, "expert_rows": rows,
            "expert_rows_max": view["counted_expert_rows_max"], "steps": steps,
            "held_hit_a_layer_step": hit / layer_steps, "live_rows_a_step": live_rows,
            "uniform_held_hit": mm.expected_held_hit(cfg, round(live_rows)),
            "counted_executions": view["counted_executions"]}
