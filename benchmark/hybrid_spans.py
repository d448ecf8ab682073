"""The hybrid model's own marks in a run's device trace: device time under the
`jax.named_scope`s that `ray_tpu/models/granite_hybrid.py` puts inside the
macro-step's `admit_prefill` and `decode_chunk`:

  ssm_scan    the mixer's chunked scan in admission, conv included
  ssm_update  the one-token conv and state update of a decode step
  ssm_proj    the mixers' in/out projections and gated norm, both halves
  attn_mix    the attention layers' mixers, both halves

What `program_spans` already reads (the window mark, the engine's spans, the
macro-step's executions and their pairing with `engine.dispatch`, every
operation's name stack) is taken from there. The readers `programs.ssm_share_pct`,
`kernels.ssm_update_roofline_pct` and `kernels.ssm_scan_roofline_pct` are a
few lines each on top of `hybrid_view`. A program without these scopes gives
zeros, and every reader then returns None.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import program_spans

SCAN, UPDATE, PROJ, ATTN = "ssm_scan", "ssm_update", "ssm_proj", "attn_mix"
SCOPES = (SCAN, UPDATE, PROJ, ATTN)

ScopedOp = Tuple[float, float, str]  # start_s, duration_s, scope ("" = none of SCOPES)


def scope_of(text: str) -> str:
    """The innermost of SCOPES in a name stack, "" where there is none."""
    at, best = -1, ""
    for scope in SCOPES:
        i = text.rfind(scope)
        if i > at:
            at, best = i, scope
    return best


def scoped_ops(trace: Dict[str, Any]) -> List[ScopedOp]:
    """Every device operation of the trace with its scope, sorted."""
    return [(s, d, scope_of(text)) for s, d, _, text in trace["named_ops"]]


def by_execution(ops: Sequence[ScopedOp], executions: Sequence[Tuple[float, float]]):
    """{execution: {scope: seconds}}: each operation goes to the execution
    that holds its middle (both lists sorted), as `serve_view` counts them."""
    out = {ex: dict.fromkeys(SCOPES + ("",), 0.0) for ex in executions}
    i = 0
    for s, d, scope in ops:
        mid = s + d / 2
        while i < len(executions) and sum(executions[i]) < mid:
            i += 1
        if i < len(executions) and executions[i][0] <= mid:
            out[executions[i]][scope] += d
    return out


def view(trace: Dict[str, Any], ops: Sequence[ScopedOp]) -> Optional[Dict[str, Any]]:
    """Seconds under each scope in the window's macro-step executions and in
    those paired with their dispatch, with the dispatches' own counts."""
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
    in_window = [ex for ex in executions if inside(*ex)]
    per = by_execution(ops, executions)
    total = lambda execs: {k: sum(per[ex][k] for ex in execs) for k in SCOPES}  # noqa: E731
    count = lambda key: sum(int(dsp[3].get(key, 0)) for dsp, _ in pairs)  # noqa: E731
    return {"macro_step_s": sum(d for _, d in in_window), "executions": len(in_window),
            "window": total(in_window), "paired_executions": len(pairs),
            "paired": total([ex for _, ex in pairs]),
            "paired_state_lanes": count("state_lanes"), "paired_steps": count("steps"),
            "paired_prompt_tokens": count("prompt_tokens")}


def hybrid_view(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`view` of this run's trace, worked out once for all its readers; None
    for an untraced run or a trace without the macro-step's marks."""
    trace = program_spans.run_trace(facts)
    if trace is None:
        return None
    if "hybrid_view" not in trace:
        trace["hybrid_view"] = view(trace, scoped_ops(trace))
    return trace["hybrid_view"]
