"""Share of the lane-steps the engine dispatched in the window that carried a
live request: the engine's own counters, differenced over the window."""


def read(ctx):
    engine = ctx["facts"].get("engine")
    if not engine or not engine.get("slot_steps"):
        return None
    return 100.0 * engine["useful_slot_steps"] / engine["slot_steps"]
