"""The latent-attention model's own marks in a run's device trace: device time
under the `jax.named_scope`s that `ray_tpu/models/sarvam_mla.py` and the
shared expert layer (`ray_tpu/models/afmoe.py`) put inside the macro-step's
`admit_prefill` and `decode_chunk`, kept apart by the half they lie in:

  mla_proj     Wq, W_kv_a, the norms and RoPE, Wo
  mla_absorb   inside mla_proj, a decode step only: W_kv_b's halves, W_uk
               absorbed into the query and W_uv applied to the attended latent
  mla_ctx      the pool's write and read, scores, softmax, values: the
               admission's expansion of keys and values from c and its flash
               kernel, the decode step's loop over chunks of the latent pool
  moe_route, moe_experts, moe_shared   as `afmoe_spans` has them

and the counts that go with them, all from the `engine.resolve` span of each
counted execution: the plan's `steps`, `lane_steps`, `prompt_tokens`,
`ctx_tokens` (positions the decode steps attend, summed over steps and live
lanes) and `prompt_pairs` (causal (query, key) pairs of the admissions), which
the span repeats from its `engine.dispatch`, and the device's own
`expert_rows`, `experts_hit` and `expert_rows_max`, of HELD experts.

Why the resolve and not the dispatch (`program_spans.pair_dispatches`): the
engine dispatches a macro-step ahead, so the first whole execution of a
traced stretch was dispatched before the trace began, and its resolve lies
inside it. This cell's macro-steps take 0.6-1.0 s and its traced stretch is
2.5 s: pairing by dispatch found one execution in one traced run and none in
the next (my chip runs, PR 39); by resolve every execution that lies whole in
the window is counted, one in each of the traced runs of the cell's usual
cycle. Where the closed loop runs its other cycle (one run in thirty:
macro-steps of 1.25 s, two to a stretch, neither whole) nothing can be
counted, whatever pairs: the second's counts lie on spans outside the trace.
The readers that need counts then return None; `programs.mla_share_pct` and
`programs.macro_step_ms.tok_s` need none.

What `program_spans` already reads is taken from there, and an operation goes
to the execution that holds its middle, a kernel the compiler named itself
takes the half of the operation before it and the scope `moe_experts`, as in
`afmoe_spans`. The flash kernel of the admission is a custom call whose HLO
name the program gave (`flash_fwd`); where its name stack is lost it counts
under `admit_prefill/mla_ctx` all the same. A program without these scopes
gives zeros, and every reader then returns None.
"""
from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import program_spans
from benchmark.program_spans import ADMIT, COMPILER_NAMED, DECODE

PROJ, ABSORB, CTX, ROUTE, EXPERTS, SHARED = ("mla_proj", "mla_absorb", "mla_ctx", "moe_route",
                                             "moe_experts", "moe_shared")
SCOPES = (PROJ, ABSORB, CTX, ROUTE, EXPERTS, SHARED)
MLA, MOE = (PROJ, ABSORB, CTX), (ROUTE, EXPERTS, SHARED)
ALL = "all"  # every operation of a half, whatever its scope
FLASH = "flash_fwd"
PLAN_COUNTS = ("steps", "lane_steps", "prompt_tokens", "ctx_tokens", "prompt_pairs")
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
    -> ScopedOps, sorted."""
    raw = sorted(raw)
    out = []
    for (start, dur, name, text), half in zip(raw, program_spans.halves(raw)):
        if COMPILER_NAMED in name and not program_spans.scope_of(text):
            scope = EXPERTS
        else:
            scope = scope_of(text)
            if not scope and program_spans.kernel_of(name) == FLASH:
                half, scope = half or ADMIT, CTX
        out.append((start, dur, half, scope))
    return out


def pair_resolves(spans: Sequence[program_spans.Span],
                  executions: Sequence[Tuple[float, float]]):
    """(stats of `engine.resolve(seq)`, the execution of dispatch seq) for
    every resolve the trace holds. The device runs the dispatches in order, so
    dispatch seq ran as executions[seq + offset] for ONE offset. A resolve
    returns only when its execution has ended, and the host is at most one
    dispatch ahead, so the last execution that ended before the resolve did
    is its own or the next: the least such index minus seq over the resolves
    is the offset, and so is that of every execution paired with its
    `engine.dispatch`."""
    ends = [s + d for s, d in executions]
    resolves = sorted(((s + d, st) for n, s, d, st in spans
                       if n == program_spans.RESOLVE and "seq" in st), key=lambda r: r[0])
    offsets = [bisect.bisect_right(ends, end) - 1 - int(st["seq"]) for end, st in resolves
               if end >= ends[0]]
    at = {ex: i for i, ex in enumerate(executions)}
    pairs, _, _ = program_spans.pair_dispatches(
        [s for s in spans if s[0] == program_spans.DISPATCH and "seq" in s[3]], executions)
    offsets += [at[ex] - int(dsp[3]["seq"]) for dsp, ex in pairs]
    if not offsets:
        return []
    offset = min(offsets)
    return [(st, executions[int(st["seq"]) + offset])
            for st in sorted((st for _, st in resolves), key=lambda st: int(st["seq"]))
            if 0 <= int(st["seq"]) + offset < len(executions)]


def view(trace: Dict[str, Any], ops: Sequence[ScopedOp]) -> Optional[Dict[str, Any]]:
    """Seconds under each (half, scope) in the window's macro-step executions
    (`window`), and in those that lie WHOLE in the window and whose resolve
    span, with the plan's counts on it, the trace holds (`counted`: not one
    that began before the window, nor the trace's last, which the profiler's
    stop cuts), with the counts those carry."""
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
    for key in PLAN_COUNTS + DEVICE_COUNTERS:
        out["counted_" + key] = sum(int(st.get(key, 0)) for st, _ in counted)
    return out


def mla_view(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`view` of this run's trace, worked out once for all its readers; None
    for an untraced run or a trace without the macro-step's marks."""
    trace = program_spans.run_trace(facts)
    if trace is None:
        return None
    if "mla_view" not in trace:
        trace["mla_view"] = view(trace, scoped(trace["named_ops"]))
    return trace["mla_view"]
