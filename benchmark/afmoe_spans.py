"""The AFMoE model's own marks in a run's device trace: device time under the
`jax.named_scope`s that `ray_tpu/models/afmoe.py` puts inside the macro-step's
`admit_prefill` and `decode_chunk`, kept apart by the half they lie in:

  moe_route    the router: scores, the choice, the weights
  moe_experts  the routed experts' products (sort, three ragged products, combine)
  moe_shared   the shared expert
  attn_window  a sliding-window layer's attention (projections, ring, softmax, gate)
  attn_full    a full layer's attention (projections, pool, softmax, gate)

and the counters that go with them: from the `engine.dispatch` span of each
paired execution the plan's `steps`, `lane_steps`, `prompt_tokens` and
`past_window_lane_steps`; from its `engine.resolve` span the device's own
`expert_rows`, `experts_hit` and `expert_rows_max` (summed over the dispatch's
decode steps and expert layers).

What `program_spans` already reads (the window mark, the engine's spans, the
macro-step's executions and their pairing with `engine.dispatch`, every
operation's name stack and half) is taken from there. The readers
`programs.moe_share_pct`, `kernels.moe_decode_roofline_pct`,
`kernels.moe_prefill_roofline_pct` and `programs.attn_share_pct` are a few
lines each on top of `afmoe_view`. A program without these scopes gives zeros,
and every reader then returns None.

The ragged products themselves carry NO scope in a trace: the TPU compiler
turns each into a kernel of its own making and names it itself (`tf_op`
`ragged-dot-none`, as it does the `ragged-dot-metadata` before it), so the
name stack the program gave the product is gone (my chip run, PR 33: 1.5 of
2.5 traced seconds lay under neither half). `program_spans.halves` gives such
an operation the half of the last operation before it that had one, and
`scoped` the scope `moe_experts`: these are the program's only ragged products.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import program_spans
from benchmark.program_spans import ADMIT, COMPILER_NAMED, DECODE

ROUTE, EXPERTS, SHARED, WINDOW, FULL = (
    "moe_route", "moe_experts", "moe_shared", "attn_window", "attn_full")
SCOPES = (ROUTE, EXPERTS, SHARED, WINDOW, FULL)
MOE = (ROUTE, EXPERTS, SHARED)
ALL = "all"  # every operation of a half, whatever its scope
DEVICE_COUNTERS = ("expert_rows", "experts_hit", "expert_rows_max")
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
    -> ScopedOps, sorted. A kernel the compiler named itself has the half
    `program_spans.halves` gives it and the scope `moe_experts` (a fusion
    before one may carry a neighbouring scope's name: it reads 0.1 s of the
    admission's kernels under no scope, my chip run, PR 33)."""
    raw = sorted(raw)
    return [(start, dur, half,
             EXPERTS if COMPILER_NAMED in name and not program_spans.scope_of(text)
             else scope_of(text))
            for (start, dur, name, text), half in zip(raw, program_spans.halves(raw))]


def by_execution(ops: Sequence[ScopedOp], executions: Sequence[Tuple[float, float]]):
    """{execution: {(half, scope): seconds}}: each operation goes to the
    execution that holds its middle (both lists sorted)."""
    out = {ex: dict.fromkeys(KEYS, 0.0) for ex in executions}
    i = 0
    for s, d, half, scope in ops:
        mid = s + d / 2
        while i < len(executions) and sum(executions[i]) < mid:
            i += 1
        if i < len(executions) and executions[i][0] <= mid and half:
            out[executions[i]][(half, ALL)] += d
            if scope:
                out[executions[i]][(half, scope)] += d
    return out


def view(trace: Dict[str, Any], ops: Sequence[ScopedOp]) -> Optional[Dict[str, Any]]:
    """Seconds under each (half, scope) in the window's macro-step executions,
    in those paired with their dispatch, and in those whose resolve span the
    trace holds too (`counted`: the device's counters are known for them),
    with the counts each carries."""
    window, spans = trace["window"], trace["spans"]
    executions = sorted((s, d) for name, s, d in trace["modules"]
                        if program_spans.MACRO_STEP.match(name))
    if not window or not spans or not executions:
        return None
    lo, hi = window
    inside = lambda s, d: lo <= s + d / 2 <= hi  # noqa: E731
    pairs, _, _ = program_spans.pair_dispatches(
        [s for s in spans if s[0] == program_spans.DISPATCH], executions)
    pairs = program_spans.whole_in_window(pairs, executions, window)
    resolves = {int(st["seq"]): st for n, _, _, st in spans
                if n == program_spans.RESOLVE and "seq" in st and "experts_hit" in st}
    counted = [(dsp, ex, resolves[int(dsp[3]["seq"])]) for dsp, ex in pairs
               if int(dsp[3].get("seq", -1)) in resolves]
    in_window = [ex for ex in executions if inside(*ex)]
    per = by_execution(ops, executions)
    total = lambda execs: {f"{h}/{s}": sum(per[ex][(h, s)] for ex in execs) for h, s in KEYS}  # noqa: E731
    plan = lambda key, rows: sum(int(dsp[3].get(key, 0)) for dsp in rows)  # noqa: E731
    out = {"macro_step_s": sum(d for _, d in in_window), "executions": len(in_window),
           "window": total(in_window),
           "paired_executions": len(pairs), "paired": total([ex for _, ex in pairs]),
           "counted_executions": len(counted), "counted": total([ex for _, ex, _ in counted])}
    for key in ("steps", "lane_steps", "prompt_tokens", "past_window_lane_steps"):
        out["paired_" + key] = plan(key, [dsp for dsp, _ in pairs])
    out["counted_steps"] = plan("steps", [dsp for dsp, _, _ in counted])
    for key in DEVICE_COUNTERS:
        out["counted_" + key] = sum(int(st.get(key, 0)) for _, _, st in counted)
    return out


def afmoe_view(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`view` of this run's trace, worked out once for all its readers; None
    for an untraced run or a trace without the macro-step's marks."""
    trace = program_spans.run_trace(facts)
    if trace is None:
        return None
    if "afmoe_view" not in trace:
        trace["afmoe_view"] = view(trace, scoped(trace["named_ops"]))
    return trace["afmoe_view"]
