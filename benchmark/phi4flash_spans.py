"""The `phi4flash` model's own marks in a run's device trace: device time
under the `jax.named_scope`s that `ray_tpu/models/phi4flash.py` and
`phi4flash_decode.py` put inside the macro-step's `admit_prefill` and
`decode_chunk`, kept apart by the half they lie in:

  s6_proj      the Mamba-1 mixers' projections: in_proj, x_proj, dt_proj and
               the softplus, the gate, out_proj
  s6_scan      the admission's conv and selective scan
  s6_update    the decode step's conv tail and one-position recurrence (the
               Pallas kernel of that name among them)
  diff_window  a window layer's differential attention: Wqkv, the ring's write
               and read (the admission's flash kernel), the pairs' difference
               and sub-norm, out_proj
  diff_full    the full layer's, over the block pool, which it writes
  cross_attn   a cross-decoder attention: Wq, the read of the full layer's
               pool (in an admission: of the row's own keys and values, one
               query a row), the difference and sub-norm, out_proj
  gmu          a gated memory unit

and the counts that go with them, from the `engine.resolve` span of each
counted execution (`sarvam_mla_spans.pair_resolves` says why the resolve and
not the dispatch): the plan's `steps`, `lane_steps`, `state_lanes`,
`prompt_tokens`, `ctx_tokens`, `past_window_lane_steps`, `admit_rows`,
`admissions`, which the span repeats from its `engine.dispatch`, and the
device's own `self_rows` and `cross_rows` (token rows the self-decoder and the
cross-decoder ran in the dispatch's admissions).

`phi4flash_view(facts)` works this out once a run; the readers of this model's
metrics are a few lines each on top of it. A program without these scopes
(another model's, or a tree that has not this one) gives zeros, a trace
without the spans None, and every reader then returns None.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import program_spans
from benchmark.program_spans import ADMIT, DECODE
from benchmark.sarvam_mla_spans import pair_resolves

PROJ, SCAN, UPDATE, WINDOW, FULL, CROSS, GMU = (
    "s6_proj", "s6_scan", "s6_update", "diff_window", "diff_full", "cross_attn", "gmu")
SCOPES = (PROJ, SCAN, UPDATE, WINDOW, FULL, CROSS, GMU)
S6, CROSS_DECODER, DIFF_ATTN = (PROJ, SCAN, UPDATE), (CROSS, GMU), (WINDOW, FULL, CROSS)
ALL = "all"  # every operation of a half, whatever its scope
FLASH = "flash_fwd"
PLAN_COUNTS = ("steps", "lane_steps", "state_lanes", "prompt_tokens", "ctx_tokens",
               "past_window_lane_steps", "admit_rows", "admissions")
DEVICE_COUNTERS = ("self_rows", "cross_rows")
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
    -> ScopedOps, sorted. A kernel of ours whose event carries no stack is
    known by its name: the state update is the decode step's; the flash
    forward is an admission's attention (eight of its nine calls a window
    layer's, and `programs.diff_attn_share_pct` sums the two)."""
    raw = sorted(raw)
    out = []
    for (start, dur, name, text), half in zip(raw, program_spans.halves(raw)):
        scope = scope_of(text)
        if not scope and UPDATE in name:
            half, scope = half or DECODE, UPDATE
        elif not scope and program_spans.kernel_of(name) == FLASH:
            half, scope = half or ADMIT, WINDOW
        out.append((start, dur, half, scope))
    return out


def view(trace: Dict[str, Any], ops: Sequence[ScopedOp]) -> Optional[Dict[str, Any]]:
    """Seconds under each (half, scope) in the window's macro-step executions
    (`window`), and in those that lie WHOLE in the window and whose resolve
    span, with the plan's counts on it, the trace holds (`counted`), with the
    counts those carry: `sarvam_mla_spans.view`'s arithmetic over this model's
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
    for key in PLAN_COUNTS + DEVICE_COUNTERS:
        out["counted_" + key] = sum(int(st.get(key, 0)) for st, _ in counted)
    return out


def phi4flash_view(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`view` of this run's trace, worked out once for all its readers; None
    for an untraced run or a trace without the macro-step's marks."""
    trace = program_spans.run_trace(facts)
    if trace is None:
        return None
    if "phi4flash_view" not in trace:
        trace["phi4flash_view"] = view(trace, scoped(trace["named_ops"]))
    return trace["phi4flash_view"]


def share_reading(facts: Dict[str, Any], scopes: Sequence[str]) -> Optional[Dict[str, Any]]:
    """What the three `programs.*_share_pct` readers of this model print: the
    share of the window's macro-step device time under `scopes` in both
    halves, beside every scope's seconds."""
    v = phi4flash_view(facts)
    if not v or not v["macro_step_s"]:
        return None
    w = v["window"]
    under = sum(w[f"{half}/{scope}"] for half in (ADMIT, DECODE) for scope in scopes)
    if not under:
        return None
    return {"value": 100.0 * under / v["macro_step_s"], "macro_step_s": v["macro_step_s"],
            "admit_share_of_macro_steps_pct": 100.0 * w[f"{ADMIT}/{ALL}"] / v["macro_step_s"],
            "executions": v["executions"], "counted_executions": v["counted_executions"],
            "self_rows": v["counted_self_rows"], "cross_rows": v["counted_cross_rows"],
            "admissions": v["counted_admissions"],
            **{k.replace("/", "_") + "_s": s for k, s in w.items()}}
