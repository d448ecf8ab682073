"""The decode steps' dense FFNs, two a layer, against their roofline: the
least time the chip could take to read their weights once a sublayer and step
(`steps` of the `engine.resolve` spans x `model_math_longcat_flash.
ffn_dense_decode_bytes`, over the memory peak; a step's rows are a thousandth
of them) over the device time under `decode_chunk/.../ffn_dense` in the
counted executions (`longcat_flash_spans.view`). Memory-bound: 32 rows make 2.4
GFLOP a product where the weights are 151 MB. Printed beside it: the bytes a
second the products reached."""
from benchmark import longcat_flash_spans as S, model_math_longcat_flash as mm


def read(ctx):
    view = S.longcat_flash_view(ctx["facts"])
    if not view:
        return None
    dense_s, steps = view["counted"][f"{S.DECODE}/{S.DENSE}"], view["counted_steps"]
    if not dense_s or not steps:
        return None
    least_bytes = mm.ffn_dense_decode_bytes(ctx["config"], steps)
    least_s = least_bytes / ctx["peaks"]["hbm_bytes_per_s"]
    return {"value": 100.0 * least_s / dense_s, "bound": "memory", "least_s": least_s,
            "ffn_dense_s": dense_s, "steps": steps, "bytes_per_s": least_bytes / dense_s,
            "ffn_dense_ms_a_step": 1e3 * dense_s / steps,
            "counted_executions": view["counted_executions"]}
