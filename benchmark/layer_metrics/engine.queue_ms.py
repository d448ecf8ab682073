"""How long a request waits in the engine for a lane: the median of `admit` -
`submit` from the replica's lifelines."""
import statistics


def read(ctx):
    waits = []
    for events in (ctx["facts"].get("timelines") or {}).values():
        ev = {e["kind"]: e["t"] for e in events}
        if "submit" in ev and "admit" in ev:
            waits.append((ev["admit"] - ev["submit"]) * 1e3)
    return {"value": statistics.median(waits), "samples": len(waits)} if waits else None
