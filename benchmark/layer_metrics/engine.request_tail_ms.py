"""From the moment a request's last token exists on the device to the engine's
`finish`, in ms, a mean over the requests finishing inside the traced stretch:
(`tail_steps` x a decode step + `tail_rows` x an admitted token row), what its
last dispatch still runs after that token, + `deliver_us`, the host delivering
the requests before it in the same resolve (`engine.request_lead_ms` holds the
shared reading). `engine.finish_wait_steps` is the first term in steps, over
the stretch's dispatches; the admitting phases after a last token, which no
reader saw, are `tail_rows`. The return of the fetch itself (the end of
`flight_us`) lies between the two terms and is in `engine.request_unexplained_ms`."""
from benchmark import common

account = common.load_module("layer_metrics", "engine.request_lead_ms")


def read(ctx):
    got = account.reading(ctx["facts"])
    if not got:
        return None
    m, c = got["mean_ms"], got["mean_count"]
    return {"value": m["tail_ms"], "requests": got["requests"],
            "device_ms": m["tail_device_ms"], "deliver_ms": m["deliver_ms"],
            "tail_steps": c["tail_steps"], "tail_phases": c["tail_phases"],
            "tail_rows": c["tail_rows"], "decode_step_ms": got["decode_step_ms"],
            "admitted_row_ms": got["admitted_row_ms"], "executions": got["executions"]}
