"""What the serve plane (handle, router, replica wrapper, transport) adds to a
request: the median over requests of the client's latency, from due to result,
minus the engine's own `finish` - `submit` for the same request id, read from
the replica's lifeline after the window. Each side is a difference on one
process's own clock (the client's monotonic, the lifeline's `time.time()`).
In an open loop it holds the generator's lateness too."""
import statistics


def read(ctx):
    timelines = ctx["facts"].get("timelines") or {}
    over = []
    for r in ctx["facts"].get("records") or []:
        ev = {e["kind"]: e["t"] for e in timelines.get(r["rid"], [])}
        if r["ok"] and "submit" in ev and "finish" in ev:
            over.append(((r["t_done"] - r["t_due"]) - (ev["finish"] - ev["submit"])) * 1e3)
    return {"value": statistics.median(over), "samples": len(over)} if over else None
