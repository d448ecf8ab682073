"""The share of the traced stretch's dispatches whose plan a vacant lane closed
(`short` = 1 on the `engine.resolve` span, which repeats its dispatch's plan
counts: PR 47's vacancy rule), in %, with the mean vacancy quantum `q` of
those plans in decode steps and the share of resolves the host came late for
(`late`: the result was ready before the host asked). A short plan is a
dispatch of at most a quantum of steps: it lets an arrival in sooner and pays
a dispatch's fixed cost more often, so the share is high where lanes stand
vacant while admissions are cheap (`chat-steady`, `chat-burst`) and 0 where
the gate of `_quantum` is shut. Counted are the resolves that start inside
the window and carry all three stats; a program whose resolves lack `late`
(the parent of PR 54) gives None."""
from benchmark import program_spans


def read(ctx):
    trace = program_spans.run_trace(ctx["facts"])
    if trace is None or not trace.get("window"):
        return None
    lo, hi = trace["window"]
    mine = [st for name, start, _, st in trace["spans"]
            if name == program_spans.RESOLVE and lo <= start <= hi
            and all(key in st for key in ("short", "q", "late"))]
    if not mine:
        return None
    short = [st for st in mine if int(st["short"])]
    return {"value": 100.0 * len(short) / len(mine), "dispatches": len(mine),
            "short_plans": len(short),
            "mean_q_steps": sum(int(st["q"]) for st in short) / len(short) if short else None,
            "late_resolves_pct": 100.0 * sum(int(st["late"]) for st in mine) / len(mine)}
