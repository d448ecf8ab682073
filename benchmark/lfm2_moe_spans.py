"""The LFM2-MoE train step's own marks in a run's device trace: device time
under the `jax.named_scope`s that `ray_tpu/models/lfm2_moe.py` and
`ray_tpu/train/step.py` put around the parts of a step, forward and backward
together (a backward operation's name stack holds its forward scope:
`transpose(jvp(...))/checkpoint/moe_experts/...`, and a rematerialised one's
`checkpoint/rematted_computation/short_conv/...`):

  moe_route    the router: scores, the choice, the weights
  moe_experts  sort, gathers, the ragged products, the weighted sum back
  short_conv   a conv layer's mixer: in-projection, gates, three taps, out-projection
  attn         an attention layer's mixer: projections, head norms, RoPE, the flash kernels
  dense_ffn    the leading dense SwiGLU
  head_loss    the last norm, the tied head in pieces, the log-softmax
  optimizer    clip, AdamW, the update (train/step.py)

What `program_spans` already reads (the window mark, every operation's HLO
name and name stack) is taken from there; the readers
`programs.moe_train_share_pct`, `programs.short_conv_share_pct`,
`programs.attn_train_share_pct`, `programs.optimizer_share_pct` and
`kernels.moe_train_roofline_pct` are a few
lines each on top of `train_view`. A program without these scopes (another
model's step, the parent of PR 57) gives zeros, and every reader then returns
None.

The ragged products themselves carry NO scope: the TPU compiler makes each a
kernel of its own and names it itself (`ragged-dot-none`, and the
`ragged-dot-metadata` before it; `program_spans.COMPILER_NAMED`). A train step
has no other ragged product than the expert layer's nine a layer (three
forward, made again under remat, six backward), so such a kernel counts under
`moe_experts`, and `ragged_s` is the products' own device time.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Sequence

from benchmark import program_spans
from benchmark.program_spans import COMPILER_NAMED

ROUTE, EXPERTS, CONV, ATTN, DENSE, HEAD, OPTIMIZER = (
    "moe_route", "moe_experts", "short_conv", "attn", "dense_ffn", "head_loss", "optimizer")
SCOPES = (ROUTE, EXPERTS, CONV, ATTN, DENSE, HEAD, OPTIMIZER)
MOE = (ROUTE, EXPERTS)
REST = "rest"  # under no scope: norms, residuals, the embedding, the compiler's own copies
# a scope as a whole word of the name stack (`jvp(attn)`, `/attn/`), not a part of another name
_SCOPE = re.compile(r"(?<![A-Za-z0-9_])(" + "|".join(SCOPES) + r")(?![A-Za-z0-9_])")
_METADATA = "ragged-dot-metadata"


def scope_of(name: str, text: str) -> str:
    """The innermost of SCOPES in an operation's name stack; a kernel the
    compiler named itself is the expert layer's; REST where there is none."""
    found = _SCOPE.findall(text)
    if found:
        return found[-1]
    return EXPERTS if COMPILER_NAMED in name else REST


def view(named_ops: Sequence[program_spans.NamedOp], window) -> Optional[Dict[str, Any]]:
    """Seconds of device time under each scope in the window (an operation
    counts whole where its middle lies inside, as `trace_reduce` counts),
    `ragged_s` of them in the ragged products' kernels and `ragged_calls`
    their number; None where the window holds no operation under any scope."""
    if not window:
        return None
    lo, hi = window
    out = dict.fromkeys(SCOPES + (REST,), 0.0)
    ragged_s, ragged_calls, metadata_s = 0.0, 0, 0.0
    for start, dur, name, text in named_ops:
        if not lo <= start + dur / 2 <= hi:
            continue
        out[scope_of(name, text)] += dur
        if COMPILER_NAMED in name:
            if _METADATA in name:
                metadata_s += dur
            else:
                ragged_s, ragged_calls = ragged_s + dur, ragged_calls + 1
    if not any(out[s] for s in SCOPES):
        return None
    return {"by_scope": out, "ops_s": sum(out.values()), "ragged_s": ragged_s,
            "ragged_calls": ragged_calls, "ragged_metadata_s": metadata_s}


def train_view(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """`view` of this run's trace, worked out once for all its readers; None
    for an untraced run or a trace without the step's scopes."""
    trace = program_spans.run_trace(facts)
    if trace is None:
        return None
    if "lfm2_moe_view" not in trace:
        trace["lfm2_moe_view"] = view(trace["named_ops"], trace["window"])
    return trace["lfm2_moe_view"]


def share(facts: Dict[str, Any], scopes: Sequence[str]) -> Optional[Dict[str, Any]]:
    """What a `programs.*_share_pct` reader of this family returns: the
    scopes' device time over the window's device busy time, with every
    scope's seconds and how far their sum is from the busy time beside it."""
    v = train_view(facts)
    busy = (facts.get("reduced") or {}).get("busy_s")
    if not v or not busy:
        return None
    seconds = sum(v["by_scope"][s] for s in scopes)
    if not seconds:
        return None
    devices = max(1, facts["reduced"].get("devices", 1))
    return {"value": 100.0 * seconds / devices / busy, "busy_s": busy,
            "scopes_sum_over_busy": v["ops_s"] / devices / busy,
            **{k + "_s": s / devices for k, s in v["by_scope"].items()}}
