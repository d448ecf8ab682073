"""The `brumby` model's own marks in a run's device trace: device time under
the `jax.named_scope`s that `ray_tpu/models/brumby.py` puts inside the
macro-step's `admit_prefill` and `decode_chunk`, kept apart by the half they
lie in:

  retention_proj    the mixers' projections: q, k, v and the gate, the head
                    norms and the rotation, W_o
  retention_scan    the admission's chunked power retention (the expansion of
                    a chunk's keys and queries among it)
  retention_update  the decode step's one-position recurrence (the Pallas
                    kernel of that name and the two expansions that feed it)

and the counts that go with them, from the `engine.resolve` span of each
counted execution (`sarvam_mla_spans.pair_resolves` says why the resolve and
not the dispatch): the plan's `steps`, `lane_steps`, `state_lanes`,
`prompt_tokens`, `admit_rows`, `admissions`, which the span repeats from its
`engine.dispatch`.

`brumby_view(facts)` works this out once a run; the readers of this model's
metrics are a few lines each on top of it. A program without these scopes
(another model's, or a tree that has not this one) gives zeros, a trace
without the spans None, and every reader then returns None.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import program_spans
from benchmark.program_spans import ADMIT, DECODE
from benchmark.sarvam_mla_spans import pair_resolves

PROJ, SCAN, UPDATE = "retention_proj", "retention_scan", "retention_update"
SCOPES = (PROJ, SCAN, UPDATE)
ALL = "all"  # every operation of a half, whatever its scope
PLAN_COUNTS = ("steps", "lane_steps", "state_lanes", "prompt_tokens", "admit_rows", "admissions")
KEYS = tuple((half, scope) for half in (ADMIT, DECODE) for scope in SCOPES + (ALL,))

ScopedOp = Tuple[float, float, str, str]  # start_s, duration_s, half, scope ("" = none)


def scope_of(text: str) -> str:
    """The innermost of SCOPES in a name stack, "" where there is none."""
    at, best = -1, ""
    for scope in SCOPES:
        i = text.rfind(scope)
        if i > at:
            at, best = i, scope
    return best


def scoped(raw: Sequence[program_spans.NamedOp]) -> List[ScopedOp]:
    """(start_s, duration_s, HLO name, name stack) of every device operation
    -> ScopedOps, sorted. The kernel, whose event may carry no stack, is known
    by its name: the state update is the decode step's."""
    raw = sorted(raw)
    out = []
    for (start, dur, name, text), half in zip(raw, program_spans.halves(raw)):
        scope = scope_of(text)
        if not scope and UPDATE in name:
            half, scope = half or DECODE, UPDATE
        out.append((start, dur, half, scope))
    return out


def view(trace: Dict[str, Any], ops: Sequence[ScopedOp]) -> Optional[Dict[str, Any]]:
    """Seconds under each (half, scope) in the window's macro-step executions
    (`window`), and in those that lie WHOLE in the window and whose resolve
    span, with the plan's counts on it, the trace holds (`counted`), with the
    counts those carry: `phi4flash_spans.view`'s arithmetic over this model's
    scopes and counts."""
    window, spans = trace["window"], trace["spans"]
    executions = sorted((s, d) for name, s, d in trace["modules"]
                        if program_spans.MACRO_STEP.match(name))
    if not window or not spans or not executions:
        return None
    lo, hi = window
    in_window = [ex for ex in executions if lo <= ex[0] + ex[1] / 2 <= hi]
    counted = [(st, ex) for st, ex in pair_resolves(spans, executions)
               if "steps" in st and lo <= ex[0] and ex in in_window and ex != executions[-1]]
    per = {ex: dict.fromkeys(KEYS, 0.0) for ex in executions}
    i = 0
    for s, d, half, scope in ops:  # both sorted: an operation goes to the execution that holds its middle
        mid = s + d / 2
        while i < len(executions) and sum(executions[i]) < mid:
            i += 1
        if i < len(executions) and executions[i][0] <= mid and half:
            per[executions[i]][(half, ALL)] += d
            if scope:
                per[executions[i]][(half, scope)] += d
    total = lambda execs: {f"{h}/{s}": sum(per[ex][(h, s)] for ex in execs) for h, s in KEYS}  # noqa: E731
    out = {"macro_step_s": sum(d for _, d in in_window), "executions": len(in_window),
           "window": total(in_window),
           "counted_executions": len(counted), "counted": total([ex for _, ex in counted])}
    for key in PLAN_COUNTS:
        out["counted_" + key] = sum(int(st.get(key, 0)) for st, _ in counted)
    return out


def brumby_view(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`view` of this run's trace, worked out once for all its readers; None
    for an untraced run or a trace without the macro-step's marks."""
    trace = program_spans.run_trace(facts)
    if trace is None:
        return None
    if "brumby_view" not in trace:
        trace["brumby_view"] = view(trace, scoped(trace["named_ops"]))
    return trace["brumby_view"]
