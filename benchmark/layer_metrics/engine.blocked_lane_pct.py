"""Share of the dispatched lane-steps that stayed empty with a request WAITING
that the block pool refused: `blocked_lane_steps` over `lane_steps` + vacant +
blocked + spent, from the `engine.resolve` spans of the executions whole in
the traced stretch. `engine.vacant_lane_pct` holds the arithmetic and prints
the same four shares; more blocks (or releasing them sooner) would fill these
lanes, a finer plan would not."""
from benchmark import common

lanes = common.load_module("layer_metrics", "engine.vacant_lane_pct")


def read(ctx):
    got = lanes.shares(ctx)
    return {"value": got["blocked_pct"], **got} if got else None
