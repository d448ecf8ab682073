"""What a request that finishes has sat through of OTHERS' admissions over its
WHOLE life, in ms: the mean over the requests finishing inside the traced
stretch of `stall_rows` x an admitted token row's device time
(`engine.request_lead_ms` holds the shared reading). `stall_rows` is the plan's
own count on the request's `engine.request` span: the token rows, padding
included, of every admitting phase the request rode and was not admitted by,
in ANY dispatch of its life. `engine.admit_stall_ms` counts the stretch's
dispatches and divides by the stretch's `finishing`, so it moves with which
requests happen to finish in the stretch; this one follows each request from
its admission. Printed: stalled phases and dispatches a request."""
from benchmark import common

account = common.load_module("layer_metrics", "engine.request_lead_ms")


def read(ctx):
    got = account.reading(ctx["facts"])
    if not got:
        return None
    c = got["mean_count"]
    return {"value": got["mean_ms"]["stall_ms"], "requests": got["requests"],
            "stall_phases_a_request": c["stall_phases"], "stall_rows_a_request": c["stall_rows"],
            "dispatches_a_request": c["dispatches"], "admitted_row_ms": got["admitted_row_ms"],
            "executions": got["executions"]}
