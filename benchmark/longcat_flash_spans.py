"""The LongCat-Flash model's own marks in a run's device trace: device time
under the `jax.named_scope`s that `ray_tpu/models/sarvam_mla.py` (the shared
attention), `ray_tpu/models/afmoe.py` (the shared router and expert products)
and `ray_tpu/models/longcat_flash.py` put inside the macro-step's
`admit_prefill` and `decode_chunk`, kept apart by the half they lie in:

  mla_proj, mla_absorb, mla_ctx   as `sarvam_mla_spans` has them, over BOTH
               attentions of every layer
  ffn_dense    the two dense FFNs of every layer
  moe_route, moe_experts          as `afmoe_spans` has them
  moe_zero     the identity experts' term, w x m summed over a row's chosen
               identity indices, and the counting of the choices

and the counts that go with them, all from the `engine.resolve` span of each
counted execution (`sarvam_mla_spans.pair_resolves` says why the resolve): the
plan's `steps`, `lane_steps`, `prompt_tokens`, `ctx_tokens`, `prompt_pairs`,
and the device's own `expert_rows`, `experts_hit`, `expert_rows_max` (of HELD
experts) and `real_choices`, `zero_choices` (the live rows' chosen indices
under and past the real experts).

The arithmetic is `sarvam_mla_spans.view`'s over these scopes and counts. A
program without these scopes gives zeros, a trace without the spans None, and
every reader then returns None.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import program_spans
from benchmark.program_spans import ADMIT, COMPILER_NAMED, DECODE
from benchmark.sarvam_mla_spans import pair_resolves

PROJ, ABSORB, CTX, DENSE, ROUTE, EXPERTS, ZERO = (
    "mla_proj", "mla_absorb", "mla_ctx", "ffn_dense", "moe_route", "moe_experts", "moe_zero")
SCOPES = (PROJ, ABSORB, CTX, DENSE, ROUTE, EXPERTS, ZERO)
MLA, SHORTCUT = (PROJ, ABSORB, CTX), (ROUTE, EXPERTS, ZERO)
ALL = "all"  # every operation of a half, whatever its scope
FLASH = "flash_fwd"
PLAN_COUNTS = ("steps", "lane_steps", "prompt_tokens", "ctx_tokens", "prompt_pairs")
DEVICE_COUNTERS = ("expert_rows", "experts_hit", "expert_rows_max", "real_choices",
                   "zero_choices")
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
    -> ScopedOps, sorted. A ragged product the compiler named itself is the
    experts' and takes its half from the operation before it
    (`program_spans.halves`); the flash forward whose event carries no stack
    is the admission's attention."""
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


def view(trace: Dict[str, Any], ops: Sequence[ScopedOp]) -> Optional[Dict[str, Any]]:
    """Seconds under each (half, scope) in the window's macro-step executions
    (`window`), and in those that lie WHOLE in the window and whose resolve
    span, with the plan's counts on it, the trace holds (`counted`), with the
    counts those carry."""
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


def longcat_flash_view(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`view` of this run's trace, worked out once for all its readers; None
    for an untraced run or a trace without the macro-step's marks."""
    trace = program_spans.run_trace(facts)
    if trace is None:
        return None
    if "longcat_flash_view" not in trace:
        trace["longcat_flash_view"] = view(trace, scoped(trace["named_ops"]))
    return trace["longcat_flash_view"]
