"""Draft-model resolution + reference acceptance math for speculative
decoding.

The device-side implementation (models/llama_decode.py:
spec_round_slots_paged) is the hot path; this module holds the two
host-side pieces the engine and the tests need:

- resolve_draft_model(): coerce the deployment-facing `draft_model`
  knob (None | "self" | LlamaConfig | dict) into (draft_params,
  draft_cfg) and validate the one geometry the acceptance rule
  REQUIRES the two models to share — the vocabulary. Everything else
  (depth, width, heads) is free: the draft runs its own paged KV pool
  sized from its own config, addressed through the target's block
  tables.
- numpy reference implementations of the lossless acceptance rule
  (greedy prefix-match and the Leviathan et al. 2023 residual/rejection
  construction), small enough to verify by eye — the tests cross-check
  the jitted kernel against these.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import numpy as np


def resolve_draft_model(draft_model: Any, params, cfg) -> Tuple[Any, Any]:
    """Coerce the `draft_model` knob into (draft_params, draft_cfg).

    Accepted forms:
      - None          -> (None, None): speculation off.
      - "self"        -> the target drafts for itself (params shared,
                         zero extra weights). Every greedy proposal
                         matches the target argmax by construction, so
                         this is the acceptance-rate ceiling — the
                         test/bench harness configuration.
      - "self:N"      -> SELF-SPECULATIVE layer truncation (Zhang et
                         al. 2023, "Draft & Verify"): the draft is the
                         target's own first N transformer layers with
                         the shared embed/final_norm/lm_head — zero
                         extra weights, draft passes ~N/n_layers the
                         cost, and acceptance degrades gracefully with
                         the truncation depth while staying LOSSLESS
                         (the rule never depends on draft quality).
      - LlamaConfig   -> fresh random init from seed 0 (tests).
      - dict          -> {"cfg": LlamaConfig, and one of
                         "params": pytree | "checkpoint_dir": str |
                         "seed": int (random init, default 0)}.

    Raises ValueError when the draft vocabulary differs from the
    target's: acceptance compares the two distributions token-by-token,
    so a vocab mismatch is a config error, not a degraded mode.
    """
    if draft_model is None:
        return None, None
    if isinstance(draft_model, str):
        if draft_model == "self":
            return params, cfg
        if draft_model.startswith("self:"):
            import dataclasses

            import jax

            try:
                n = int(draft_model.split(":", 1)[1])
            except ValueError:
                n = 0
            if not 1 <= n <= cfg.n_layers:
                raise ValueError(
                    f"'self:N' draft needs 1 <= N <= n_layers "
                    f"({cfg.n_layers}), got {draft_model!r}"
                )
            draft_cfg = dataclasses.replace(cfg, n_layers=n)
            # layers are scan-stacked (leading dim n_layers): the first
            # N slices ARE the truncated draft, views over the target's
            # own weights — no copy, no extra memory
            draft_params = dict(params)
            draft_params["layers"] = jax.tree_util.tree_map(
                lambda a: a[:n], params["layers"])
            return draft_params, draft_cfg
        raise ValueError(
            f"string draft_model must be 'self' or 'self:N', "
            f"got {draft_model!r}"
        )

    def is_config(obj) -> bool:
        # a model config object names its own modules (models/*.py)
        return hasattr(obj, "model_module")

    seed = 0
    if is_config(draft_model):
        draft_cfg = draft_model
        draft_params = None
    elif isinstance(draft_model, dict):
        body = dict(draft_model)
        draft_cfg = body.pop("cfg", None)
        if not is_config(draft_cfg):
            raise ValueError(
                "dict draft_model must carry a 'cfg' model config "
                f"(got {type(draft_cfg).__name__})"
            )
        draft_params = body.pop("params", None)
        ckpt = body.pop("checkpoint_dir", None)
        seed = int(body.pop("seed", 0))
        if body:
            raise ValueError(f"unknown draft_model field(s) {sorted(body)}")
        if draft_params is None and ckpt is not None:
            from ray_tpu.train.orbax_utils import load_pytree_from_checkpoint

            draft_params = load_pytree_from_checkpoint(ckpt)
    else:
        raise ValueError(
            "draft_model must be None, 'self', a model config, or a dict "
            f"(got {type(draft_model).__name__})"
        )
    if draft_cfg.vocab_size != cfg.vocab_size:
        raise ValueError(
            f"draft vocab_size {draft_cfg.vocab_size} != target "
            f"{cfg.vocab_size}: lossless acceptance compares the two "
            "distributions over one shared vocabulary"
        )
    if draft_params is None:
        import jax

        draft_params = draft_cfg.model_module.init_params(
            jax.random.PRNGKey(seed), draft_cfg)
    return draft_params, draft_cfg


# ---------------------------------------------------------------- reference
# numpy mirrors of the device acceptance rule, used by the tests to
# cross-check the jitted kernel. Shapes: draft (S,) proposed tokens,
# target_argmax (S+1,) per-position target argmax, p/q (V,) warped
# probability rows.


def greedy_accept_len(draft: np.ndarray, target_argmax: np.ndarray) -> int:
    """Length of the accepted prefix under the greedy rule: the longest
    prefix where every draft token equals the target argmax at its
    position. The emitted correction/bonus is target_argmax[n]."""
    n = 0
    for j in range(len(draft)):
        if int(draft[j]) != int(target_argmax[j]):
            break
        n += 1
    return n


def accept_token(p_d: float, q_d: float, u: float) -> bool:
    """One rejection-sampling acceptance test: keep the draft token
    with probability min(1, p(d)/q(d)) given uniform u in [0, 1)."""
    return u * max(q_d, 1e-20) < p_d


def residual_distribution(p: np.ndarray, q: np.ndarray) -> np.ndarray:
    """The rejection-case distribution normalize(max(p - q, 0)). Sampling
    the correction token from it makes (accepted prefix + correction)
    an EXACT sample from the target distribution p — Leviathan et al.
    2023, Theorem 1. At the bonus position q := 0, so this degrades to
    p itself (a pure target sample)."""
    r = np.maximum(np.asarray(p, np.float64) - np.asarray(q, np.float64), 0.0)
    s = r.sum()
    if s <= 0.0:  # p == q exactly: residual mass underflows, fall back to p
        return np.asarray(p, np.float64) / max(np.asarray(p).sum(), 1e-20)
    return r / s


def expected_accept_prob(p: np.ndarray, q: np.ndarray) -> float:
    """Marginal acceptance probability of one draft position:
    sum_d q(d) * min(1, p(d)/q(d)) = 1 - 0.5 * ||p - q||_1. Useful for
    sizing num_speculative_tokens against a measured draft gap."""
    p = np.asarray(p, np.float64)
    q = np.asarray(q, np.float64)
    return float(1.0 - 0.5 * np.abs(p - q).sum())
