"""Llama autoregressive inference: KV-cache prefill + decode.

The serving-side counterpart of models/llama.py (reference analogue:
the reference serves LLMs through integrated engines inside Serve
replicas — vLLM in examples — rather than in-tree; on TPU the engine
IS the jitted jax program). Two cache generations and a mirror:

- the DENSE BATCH cache (`init_cache`, `prefill`, `decode_step`,
  `decode_loop`, `sample_loop`, `generate`): every row at one position,
  what `serve/llm.py` runs with `continuous=False` and the plain reference
  that tests compare the paged programs with;
- the PAGED halves of the engine's macro-step (`init_paged_cache`,
  `admit_slots_paged`, `decode_step_slots_paged`, bound into
  models/paged.py's skeleton as `macro_step_slots_paged`; that module's
  docstring says what a decode module offers the engine);
- the SPECULATIVE mirror of the paged macro-step (`macro_step_slots_spec`).

The dense cache's design:

- Static shapes: the cache is (L, B, max_len, kv_heads, head_dim),
  written with dynamic_update_slice at the current position; attention
  masks positions beyond `pos` — one compiled decode step serves every
  position, no recompiles.
- One lax.scan over the stacked layer params per step (same O(1)
  compile-depth trick as training), GQA via kv-head broadcast, bf16
  compute with fp32 softmax/logits.
- `prefill` runs the full training forward over the prompt while
  capturing per-layer K/V as scan outputs — the prompt pass costs one
  matmul-bound forward, not T decode steps.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models import paged
from ray_tpu.models.llama import LlamaConfig, _qkv
from ray_tpu.models.paged import ADMIT_SCOPE, DECODE_SCOPE  # noqa: F401  (benchmark/ reads both here)
from ray_tpu.ops.normalization import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies


def init_cache(cfg: LlamaConfig, batch: int, max_len: int) -> Dict[str, Any]:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def _gqa_attend(q, k_cache, v_cache, pos, cfg: LlamaConfig):
    """q: (B, 1, h, hd); caches: (B, S, kvh, hd); mask > pos."""
    B, _, h, hd = q.shape
    S = k_cache.shape[1]
    groups = h // cfg.n_kv_heads
    # decode is CACHE-BANDWIDTH bound: read K/V in their stored bf16 and
    # let the MXU accumulate in f32 (preferred_element_type) — upcasting
    # the whole cache to f32 doubled the HBM traffic of every step
    qg = q.reshape(B, cfg.n_kv_heads, groups, hd)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32
    ) * (hd**-0.5)
    mask = jnp.arange(S)[None, None, None, :] <= pos
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgs,bskd->bkgd", probs.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, 1, h * hd).astype(cfg.dtype)


def decode_step(params, cache, tokens, cfg: LlamaConfig):
    """One token per sequence: tokens (B,) int32 → (logits (B, vocab),
    updated cache). Jit with donate_argnums on the cache."""
    B = tokens.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = cache["pos"]
    x = params["embed"][tokens][:, None, :].astype(cfg.dtype)  # (B, 1, d)
    cos, sin = rope_frequencies(hd, cache["k"].shape[2], cfg.rope_theta)
    positions = jnp.full((B, 1), pos, jnp.int32)

    def body(carry, layer_and_idx):
        # the FULL stacked cache rides the carry and is updated in place
        # (one dynamic_update_slice per layer). Scanning per-layer caches
        # as xs with stacked ys instead makes XLA materialize a second
        # full-cache copy every step — at B=16/S=1024 that is ~512 MB of
        # extra writes per decoded token.
        x, k_full, v_full = carry
        layer, li = layer_and_idx
        a = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q = (a @ layer["wq"]).reshape(B, 1, h, hd)
        k = (a @ layer["wk"]).reshape(B, 1, kvh, hd)
        v = (a @ layer["wv"]).reshape(B, 1, kvh, hd)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        k_full = jax.lax.dynamic_update_slice(k_full, k[None], (li, 0, pos, 0, 0))
        v_full = jax.lax.dynamic_update_slice(v_full, v[None], (li, 0, pos, 0, 0))
        k_cache = jax.lax.dynamic_index_in_dim(k_full, li, 0, keepdims=False)
        v_cache = jax.lax.dynamic_index_in_dim(v_full, li, 0, keepdims=False)
        o = _gqa_attend(q, k_cache, v_cache, pos, cfg) @ layer["wo"]
        x = x + o
        m = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
        gate = jax.nn.silu((m @ layer["w_gate"]).astype(jnp.float32)).astype(cfg.dtype)
        x = x + (gate * (m @ layer["w_up"])) @ layer["w_down"]
        return (x, k_full, v_full), None

    (x, new_k, new_v), _ = jax.lax.scan(
        body,
        (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)),
        unroll=True,
    )
    x = rms_norm(x[:, 0, :], params["final_norm"], cfg.rms_eps)
    logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v, "pos": pos + 1}


def prefill(params, tokens, cache, cfg: LlamaConfig):
    """Prompt pass: tokens (B, T) → (last-position logits, cache filled
    for positions [0, T))."""
    B, T = tokens.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][tokens].astype(cfg.dtype)
    cos, sin = rope_frequencies(hd, cache["k"].shape[2], cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))

    from ray_tpu.ops.blockwise_attention import blockwise_attention

    def body(x, layer):
        a = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q = (a @ layer["wq"]).reshape(B, T, h, hd)
        k = (a @ layer["wk"]).reshape(B, T, kvh, hd)
        v = (a @ layer["wv"]).reshape(B, T, kvh, hd)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        o = blockwise_attention(q, k, v, True, min(512, T)).reshape(B, T, h * hd)
        x = x + o @ layer["wo"]
        m = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
        gate = jax.nn.silu((m @ layer["w_gate"]).astype(jnp.float32)).astype(cfg.dtype)
        x = x + (gate * (m @ layer["w_up"])) @ layer["w_down"]
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    # write prompt K/V into the cache at [0, T)
    new_k = jax.lax.dynamic_update_slice(cache["k"], ks, (0, 0, 0, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache["v"], vs, (0, 0, 0, 0, 0))
    x = rms_norm(x[:, -1, :], params["final_norm"], cfg.rms_eps)
    logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v, "pos": jnp.asarray(T, jnp.int32)}


def decode_loop(params, cache, first_token, n_steps: int, cfg: LlamaConfig):
    """Greedy decode of `n_steps` tokens entirely on device: one jitted
    lax.scan, zero host round-trips inside the loop — the TPU-native
    serving inner loop (a python-level step loop pays a host dispatch
    per token; what that costs against a step's compute on a directly
    attached chip: not measured). Returns (tokens (B, n_steps), cache)."""

    def body(carry, _):
        cache, token = carry
        logits, cache = decode_step(params, cache, token, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, nxt), nxt

    (cache, _), tokens = jax.lax.scan(body, (cache, first_token), None, length=n_steps)
    return jnp.moveaxis(tokens, 0, 1), cache


# ---------------------------------------------------------------------------
# The paged halves: this model's cache pytree, one-token step and admission
# for models/paged.py's macro-step (that module's docstring describes the
# block pool, the null block and the sampling that runs inside the scan).
# ---------------------------------------------------------------------------


def init_paged_cache(cfg: LlamaConfig, n_slots: int, n_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    """Paged decode state: the block pool plus per-slot scalars. Block
    tables are NOT device state — the host allocator owns them. The pool
    never exists in (n_slots, max_len) form, nor does a lane's context
    outside it: the decode step and the admission read it in place, a
    chunk of blocks at a time (paged.attend_decode_paged,
    paged._attend_admission)."""
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
        # per-slot raw PRNG keys (threefry), reseeded at admission from
        # the request seed and split once per decode step — a request's
        # sample stream depends only on its seed and token index, never
        # on what else is co-scheduled
        "rng": jnp.zeros((n_slots, 2), jnp.uint32),
    }


def state_bytes_per_lane(cfg: LlamaConfig) -> int:
    """Bytes of per-lane recurrent state beside the K/V blocks: none, a
    lane's whole state is its block table. A decode module whose answer
    is not 0 makes the engine refuse what needs a state snapshot (prefix
    reuse, speculation's rollback, migration)."""
    return 0


def _gather_block_ctx(k_layer, v_layer, tables):
    """Materialize each row's whole table span from a layer's pool:
    k_layer (n_blocks, bs, kvh, hd), tables (R, MB) -> (R, MB*bs, kvh, hd).
    Only _forward_tokens_paged (speculation and the draft pool's mirror)
    still reads a context this way; the decode step and the admission
    read the pool a chunk of blocks at a time."""
    B, MB = tables.shape
    bs = k_layer.shape[1]
    ctx_k = k_layer[tables].reshape(B, MB * bs, *k_layer.shape[2:])
    ctx_v = v_layer[tables].reshape(B, MB * bs, *v_layer.shape[2:])
    return ctx_k, ctx_v


def decode_step_slots_paged(params, cache, tokens, tables, temps, top_ks,
                            top_ps, stop_ids, cfg: LlamaConfig,
                            sampled: bool = True):
    """One token on every slot against the PAGED cache. tables (B, MB)
    i32 name each slot's blocks (0-padded -> null block); temps/top_ks/
    top_ps are the per-slot sampling plan; stop_ids (B, NS) i32 are
    -1-padded stop sets. Inactive lanes (remaining == 0) aim their KV
    write at the null block — their old blocks may already belong to a
    later-phase admission of the same macro plan. Each layer attends the
    lanes' contexts in place (paged.attend_decode_paged): as many chunks of
    the pool as the longest live lane holds, no copy of the layer, no
    gather of the table span. Returns
    (logits, next_tokens, cache); a sampled stop token zeroes the
    slot's `remaining` device-side (the host observes it one macro-step
    later and repairs its speculative plan).

    sampled=False is the STATIC greedy variant (host plans know whether
    any resident request samples): next tokens come from one argmax —
    no vocab sort/softmax/cumsum, no rng splits — so an all-greedy
    workload pays exactly the pre-sampling per-step cost. Stop-token
    detection stays (greedy requests may carry stop ids)."""
    hd = cfg.head_dim
    bs = cache["k"].shape[2]
    S = tables.shape[1] * bs
    pos = cache["pos"]
    active = cache["remaining"] > 0
    x = params["embed"][tokens][:, None, :].astype(cfg.dtype)
    cos, sin = rope_frequencies(hd, S, cfg.rope_theta)
    positions = pos[:, None]

    def body(carry, layer_and_idx):
        x, k_full, v_full = carry
        layer, li = layer_and_idx
        a = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q, k, v = _qkv(a, layer, cfg)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)

        k_full, v_full = paged.write_decode_kv(
            k_full, v_full, li, k, v, tables, pos, active)
        o = paged.attend_decode_paged(
            q[:, 0], k_full, v_full, li, tables, pos, active, hd**-0.5)
        x = x + o[:, None, :] @ layer["wo"]
        m = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
        gate = jax.nn.silu((m @ layer["w_gate"]).astype(jnp.float32)).astype(cfg.dtype)
        x = x + (gate * (m @ layer["w_up"])) @ layer["w_down"]
        return (x, k_full, v_full), None

    # rolled: one layer body in the program, `li` a run-time value (see _qkv
    # for what the compiler does to sixteen unrolled layers)
    (x, new_k, new_v), _ = jax.lax.scan(
        body,
        (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    x = rms_norm(x[:, 0, :], params["final_norm"], cfg.rms_eps)
    logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    nxt, new_pos, remaining, new_rng = paged.finish_decode_step(
        logits, cache, active, temps, top_ks, top_ps, stop_ids, sampled)
    new_cache = {"k": new_k, "v": new_v, "pos": new_pos,
                 "remaining": remaining, "rng": new_rng}
    return logits, nxt, new_cache


def _gqa_attend_span(q, k_ctx, v_ctx, positions, cfg: LlamaConfig):
    """Few-query attention against a gathered table span: q (R, T, h, hd)
    at absolute `positions` (R, T); k_ctx/v_ctx (R, S, kvh, hd) hold the
    full context INCLUDING the queries' own just-written K/V, so the
    causal mask s <= positions[r, t] covers both the older context and
    causality among the T queries in one (T x S) score. For T = n_spec + 1
    (speculative verify and draft passes); admission, whose T is a whole
    prompt, goes through paged._attend_admission."""
    A, P, h, hd = q.shape
    S = k_ctx.shape[1]
    qg = q.reshape(A, P, cfg.n_kv_heads, h // cfg.n_kv_heads, hd)
    scores = jnp.einsum(
        "apkgd,askd->akgps", qg, k_ctx, preferred_element_type=jnp.float32
    ) * (hd**-0.5)
    mask = jnp.arange(S)[None, None, :] <= positions[:, :, None]  # (A, P, S)
    scores = jnp.where(mask[:, None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "akgps,askd->apkgd", probs.astype(v_ctx.dtype), v_ctx,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(A, P, h * hd).astype(cfg.dtype)


def admit_slots_paged(params, prompts, lengths, starts, slots, rems, seeds,
                      cache, feed, tables, temps, top_ks, top_ps, stop_ids,
                      cfg: LlamaConfig, sampled: bool = True):
    """Fused PAGED admission: prefill A right-padded SUFFIXES (A, P) —
    `prompts` holds only the tokens after each row's cached prefix of
    `starts[n]` tokens (block-aligned; 0 for a cache miss) — and land
    rows with length > 0 in their target `slots`. The radix-prefix-hit
    prefill skip happens exactly here: reused blocks are never
    recomputed, the suffix attends to them read-only through the slot's
    block table. P must be a multiple of block_size.

    Per layer the body writes EVERY row's suffix K/V before ANY row
    reads a prefix from the pool, so two same-phase admissions sharing a
    prefix (the second's table naming blocks the first is filling right
    now) stay correct: plan order == write order <= read order. The
    attention (paged._attend_admission) is causal over the row's own suffix
    plus a loop over its prefix blocks: its work follows starts[n] +
    lengths[n], never the table span. Right-pad columns
    write into the slot's own reserved (beyond-pos) cells or, past the
    table's edge, the null block. Each row's first output token is
    SAMPLED from its true-last-position logits with a key seeded from
    `seeds[n]`; the carried key lands in the slot's rng state.
    Returns (first tokens (A,), cache, feed)."""
    A, P = prompts.shape
    hd = cfg.head_dim
    S = tables.shape[1] * cache["k"].shape[2]
    adm_tables = tables[slots]  # (A, MB)
    valid = lengths > 0
    x = params["embed"][prompts].astype(cfg.dtype)
    cos, sin = rope_frequencies(hd, S, cfg.rope_theta)
    positions = starts[:, None] + jnp.broadcast_to(
        jnp.arange(P, dtype=jnp.int32)[None, :], (A, P)
    )

    def body(carry, layer_and_idx):
        x, k_full, v_full = carry
        layer, li = layer_and_idx
        a = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q, k, v = _qkv(a, layer, cfg)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)

        # phase 1: write all rows' suffix K/V block by block
        k_full, v_full = paged.write_admission_kv(
            k_full, v_full, li, k, v, adm_tables, starts, valid)
        # phase 2: every row reads its prefix (sees all phase-1 writes)
        k_layer = jax.lax.dynamic_index_in_dim(k_full, li, 0, keepdims=False)
        v_layer = jax.lax.dynamic_index_in_dim(v_full, li, 0, keepdims=False)
        o = paged._attend_admission(q, k, v, k_layer, v_layer, adm_tables, starts, cfg)
        x = x + o @ layer["wo"]
        m = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
        gate = jax.nn.silu((m @ layer["w_gate"]).astype(jnp.float32)).astype(cfg.dtype)
        x = x + (gate * (m @ layer["w_up"])) @ layer["w_down"]
        return (x, k_full, v_full), None

    (x, k_big, v_big), _ = jax.lax.scan(  # rolled, as the decode step's
        body,
        (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits_all = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    last = jnp.take_along_axis(
        logits_all, (jnp.maximum(lengths, 1) - 1)[:, None, None], axis=1
    )[:, 0, :]
    first, pos, rem, feed, rng = paged.finish_admission(
        last, cache, feed, valid, lengths, starts, slots, rems, seeds, temps,
        top_ks, top_ps, stop_ids, sampled)
    cache = {"k": k_big, "v": v_big, "pos": pos, "remaining": rem, "rng": rng}
    return first, cache, feed


def macro_step_slots_paged(params, cache, feed, *plan, chunk: int, cfg: LlamaConfig,
                           sampled: bool = True):
    """models/paged.py's macro-step skeleton with this model's two halves,
    under the skeleton's name (a device trace finds the program by it)."""
    return paged.macro_step_slots_paged(
        params, cache, feed, *plan, chunk=chunk, cfg=cfg, sampled=sampled,
        admit=admit_slots_paged, decode_step=decode_step_slots_paged)


# ---------------------------------------------------------------------------
# Draft-model speculative decoding (Leviathan et al. 2023; Chen et al.
# 2023) on the paged substrate: a small DRAFT model proposes n_spec
# tokens per lane from its OWN paged KV pool (mirroring the target's
# block tables — one allocator plan serves both pools), then the target
# verifies all of them in ONE batched multi-position pass
# (verify-style scoring through the same block tables). Acceptance is
# LOSSLESS: greedy lanes accept a draft token iff it equals the target
# argmax; sampled lanes run residual/rejection sampling (accept d with
# prob min(1, p(d)/q(d)); on rejection sample from the normalized
# residual max(0, p - q)), which preserves the target's (warped)
# distribution exactly. Rejected KV writes are safe by the
# position-rollback discipline: `pos` only ever advances past VERIFIED
# tokens, the attention mask s <= pos hides cells beyond it, and every
# pass writes its whole position span before gathering — so stale
# rejected cells are overwritten before they can become visible. The
# draft pool's one possible hole (the last draft token's KV when all
# n_spec are accepted and the bonus token is taken) is patched for free
# by the next round's first draft pass, which is 2 positions wide: it
# re-processes the tracked previous token at pos - 1 (an idempotent
# rewrite when the cell was already correct, the hole-fill when it
# wasn't) alongside the feed token at pos.
# ---------------------------------------------------------------------------


def init_spec_cache(draft_cfg: LlamaConfig, n_slots: int, n_blocks: int,
                    block_size: int) -> Dict[str, Any]:
    """Draft-model paged state: its own K/V pool with the SAME block
    geometry as the target (block tables are shared — one host plan
    addresses both pools) plus the per-slot previous token (`prev`, the
    token at pos - 1). Each round's first draft pass re-processes it so
    the one possible draft-pool hole — the last draft token's KV when a
    whole round was accepted and the bonus token taken — is refilled
    without a separate catch-up dispatch."""
    shape = (draft_cfg.n_layers, n_blocks, block_size, draft_cfg.n_kv_heads,
             draft_cfg.head_dim)
    return {
        "k": jnp.zeros(shape, draft_cfg.dtype),
        "v": jnp.zeros(shape, draft_cfg.dtype),
        "prev": jnp.zeros((n_slots,), jnp.int32),
    }


def _forward_tokens_paged(params, kv_k, kv_v, tokens, row_tables, base_pos,
                          active, cfg: LlamaConfig, with_logits: bool = True):
    """Multi-position paged forward: process tokens (R, T) at absolute
    positions base_pos[:, None] + arange(T), writing each position's
    K/V into the pool and attending through row_tables (R, MB).
    Inactive rows and positions past the table edge aim their writes at
    the null block. Per layer EVERY row writes before ANY row gathers
    (the admit_slots_paged discipline) and position t's causal mask is
    s <= base_pos + t, so one call scores T positions per row exactly
    as T sequential decode steps would — the speculative verify kernel.
    Returns (logits (R, T, V) f32 or None, kv_k, kv_v)."""
    R, T = tokens.shape
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    bs = kv_k.shape[2]
    MB = row_tables.shape[1]
    S = MB * bs
    x = params["embed"][tokens].astype(cfg.dtype)
    # rope span covers worst-case overshoot positions (a lane near the
    # table edge writes its tail into the null block, but the angle
    # lookup must stay in range)
    cos, sin = rope_frequencies(hd, S + T, cfg.rope_theta)
    positions = base_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]

    def body(carry, layer_and_idx):
        x, k_full, v_full = carry
        layer, li = layer_and_idx
        a = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q, k, v = _qkv(a, layer, cfg)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)

        def write_row(r, kv):
            kf, vf = kv
            pb = jax.lax.dynamic_index_in_dim(base_pos, r, keepdims=False)
            ab = jax.lax.dynamic_index_in_dim(active, r, keepdims=False)
            row = jax.lax.dynamic_index_in_dim(row_tables, r, 0, keepdims=False)
            for t in range(T):  # static: T positions per row
                p = pb + t
                idx = p // bs
                blk = jax.lax.dynamic_index_in_dim(
                    row, jnp.minimum(idx, MB - 1), keepdims=False)
                ok = ab & (idx < MB)
                blk = jnp.where(ok, blk, 0)  # overshoot/inactive -> null
                off = jnp.where(ok, p % bs, 0)
                kc = jax.lax.dynamic_slice(k, (r, t, 0, 0), (1, 1, kvh, hd))
                vc = jax.lax.dynamic_slice(v, (r, t, 0, 0), (1, 1, kvh, hd))
                kf = jax.lax.dynamic_update_slice(kf, kc[None], (li, blk, off, 0, 0))
                vf = jax.lax.dynamic_update_slice(vf, vc[None], (li, blk, off, 0, 0))
            return kf, vf

        k_full, v_full = jax.lax.fori_loop(0, R, write_row, (k_full, v_full))
        k_layer = jax.lax.dynamic_index_in_dim(k_full, li, 0, keepdims=False)
        v_layer = jax.lax.dynamic_index_in_dim(v_full, li, 0, keepdims=False)
        ctx_k, ctx_v = _gather_block_ctx(k_layer, v_layer, row_tables)
        o = _gqa_attend_span(q, ctx_k, ctx_v, positions, cfg)
        x = x + o @ layer["wo"]
        m = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
        gate = jax.nn.silu((m @ layer["w_gate"]).astype(jnp.float32)).astype(cfg.dtype)
        x = x + (gate * (m @ layer["w_up"])) @ layer["w_down"]
        return (x, k_full, v_full), None

    (x, k_full, v_full), _ = jax.lax.scan(
        body, (x, kv_k, kv_v),
        (params["layers"], jnp.arange(cfg.n_layers)), unroll=True)
    if not with_logits:
        return None, k_full, v_full
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    return logits, k_full, v_full


def verify_step_slots_paged(params, cache, feed, draft_toks, tables,
                            cfg: LlamaConfig):
    """Target verification pass: score feed + the n_spec draft
    proposals for every lane in ONE batched paged dispatch. Writes the
    target K/V for all n_spec + 1 positions (pos .. pos + n_spec) and
    returns logits (B, n_spec + 1, V) f32 — logits[:, j] is the target
    distribution AFTER consuming [feed, d_1 .. d_j], i.e. the verifier
    for draft token j+1 (and column n_spec is the bonus distribution
    when every draft token is accepted) — plus the updated (k, v)
    pools. Position rollback (the caller advancing `pos` only past
    accepted tokens) is what keeps the rejected tail's writes
    invisible: the mask s <= pos hides them and the next round's span
    overwrites them before any gather."""
    toks = jnp.concatenate([feed[:, None], draft_toks], axis=1)
    logits, tk, tv = _forward_tokens_paged(
        params, cache["k"], cache["v"], toks, tables, cache["pos"],
        cache["remaining"] > 0, cfg, with_logits=True)
    return logits, tk, tv


def spec_round_slots_paged(params, draft_params, cache, draft_cache, feed,
                           tables, temps, top_ks, top_ps, stop_ids,
                           n_spec: int, cfg: LlamaConfig,
                           draft_cfg: LlamaConfig, sampled: bool = True):
    """One speculative round on every slot: n_spec sequential draft
    proposals (draft pool) + one batched target verification
    (verify_step_slots_paged) + lossless acceptance.

    Greedy lanes accept the longest draft prefix matching the target
    argmax and emit the target argmax at the first mismatch (or the
    bonus column) — the emitted stream is bit-identical to target-only
    greedy decode. Sampled lanes accept d_j with probability
    min(1, p_j(d_j) / q_j(d_j)) over the SAME temperature/top-k/top-p
    warping on both models, and on rejection sample from the
    normalized residual max(0, p_j − q_j) — the emitted stream is an
    exact sample from the target's warped distribution (speculative
    sampling, Leviathan et al. 2023 Thm 1). Returns
    (out (B, n_spec+1) emitted-token rows, counts (B,) valid lengths
    (0 = lane inactive), feed, cache, draft_cache): row b's first
    counts[b] columns are real tokens — counts[b]-1 accepted draft
    tokens plus one correction/bonus token."""
    B = feed.shape[0]
    S1 = n_spec + 1
    pos = cache["pos"]
    rem = cache["remaining"]
    active = rem > 0
    # draft_cache None => SELF-drafting with a SHARED pool: the draft
    # weights are the target weights, so verify's writes of
    # [feed, d_1 .. d_S] are bit-identical to the draft's own — one
    # pool serves both models, there is no draft-pool hole (verify
    # writes d_S's KV at pos + n_spec itself), and the first draft
    # pass needs no previous-token rewrite
    shared = draft_cache is None
    if shared:
        dk, dv = cache["k"], cache["v"]
        prev = None
    else:
        dk, dv = draft_cache["k"], draft_cache["v"]
        prev = draft_cache["prev"]

    if sampled:
        # one split per round; per-use keys fold in their stage index —
        # a lane's key chain depends only on its seed and round count,
        # never on co-scheduling
        carried, round_key = paged._split_slot_keys(cache["rng"])
        fold = jax.vmap(jax.random.fold_in, in_axes=(0, None))
        step_keys = [fold(round_key, j) for j in range(n_spec + 2)]
    else:
        carried = cache["rng"]

    # n_spec sequential draft proposals, each writing its token's draft
    # KV at pos + j before attending (write-then-gather keeps the
    # just-written position visible to its own score). The FIRST pass
    # is 2 wide: [prev @ pos-1, feed @ pos]. When the previous round
    # accepted all n_spec proposals, the last draft token's KV was
    # never written to the draft pool (the bonus came straight from the
    # target) and its position is exactly pos - 1 — re-processing prev
    # there fills the hole; on every other lane it's a bit-identical
    # rewrite of a cell that was already correct. Fusing the patch into
    # the proposal pass saves a whole draft dispatch per round.
    tok = feed
    draft_list = []
    q_list = []
    for j in range(n_spec):
        if j == 0 and not shared:
            lg, dk, dv = _forward_tokens_paged(
                draft_params, dk, dv, jnp.stack([prev, tok], axis=1),
                tables, jnp.maximum(pos - 1, 0), active, draft_cfg,
                with_logits=True)
        else:
            lg, dk, dv = _forward_tokens_paged(
                draft_params, dk, dv, tok[:, None], tables, pos + j, active,
                draft_cfg, with_logits=True)
        lg = lg[:, -1, :]
        if sampled:
            # one top-k/top-p warp serves BOTH the proposal draw and
            # the acceptance q — the masked logits are the (warped)
            # draft distribution, so sampling categorical over them is
            # exactly sample_tokens' draw with the vocab sort done once
            safe_t = jnp.where(temps > 0.0, temps, 1.0)
            masked = paged._topk_topp_mask(lg / safe_t[:, None], top_ks, top_ps)
            smp = jax.vmap(jax.random.categorical)(
                step_keys[j], masked).astype(jnp.int32)
            greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            nxt = jnp.where(temps > 0.0, smp, greedy)
            q_list.append(jax.nn.softmax(masked, axis=-1))
        else:
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        draft_list.append(nxt)
        tok = nxt
    draft_toks = jnp.stack(draft_list, axis=1)  # (B, n_spec)

    if shared:
        # verify continues from the draft-written pool: it rewrites the
        # very same cells with the very same values (same weights, same
        # tokens, same positions), so threading dk/dv through keeps the
        # buffer donation chain unbroken instead of forking the pool
        logits, tk, tv = _forward_tokens_paged(
            params, dk, dv,
            jnp.concatenate([feed[:, None], draft_toks], axis=1),
            tables, pos, active, cfg, with_logits=True)
    else:
        logits, tk, tv = verify_step_slots_paged(
            params, cache, feed, draft_toks, tables, cfg)

    tgt_argmax = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, S1)
    greedy_match = draft_toks == tgt_argmax[:, :n_spec]
    if sampled:
        safe_t = jnp.where(temps > 0.0, temps, 1.0)
        flat = logits.reshape(B * S1, -1) / jnp.repeat(safe_t, S1)[:, None]
        p = jax.nn.softmax(
            paged._topk_topp_mask(flat, jnp.repeat(top_ks, S1),
                                  jnp.repeat(top_ps, S1)),
            axis=-1).reshape(B, S1, -1)
        q = jnp.stack(q_list, axis=1)  # (B, n_spec, V)
        p_d = jnp.take_along_axis(
            p[:, :n_spec], draft_toks[..., None], axis=-1)[..., 0]
        q_d = jnp.take_along_axis(q, draft_toks[..., None], axis=-1)[..., 0]
        u = jax.vmap(lambda kk: jax.random.uniform(kk, (n_spec,)))(
            step_keys[n_spec])
        # accept iff u < p(d)/q(d)  (q(d) > 0: d was sampled from q)
        samp_accept = u * jnp.maximum(q_d, 1e-20) < p_d
        accept = jnp.where(temps[:, None] > 0.0, samp_accept, greedy_match)
    else:
        accept = greedy_match
    n_acc = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(axis=1)  # (B,)

    next_g = jnp.take_along_axis(tgt_argmax, n_acc[:, None], axis=1)[:, 0]
    if sampled:
        # residual distribution at the rejection column: max(0, p − q),
        # with q := 0 at the bonus column (pure target sample there)
        p_at = jnp.take_along_axis(p, n_acc[:, None, None], axis=1)[:, 0]
        q_pad = jnp.concatenate([q, jnp.zeros_like(q[:, :1])], axis=1)
        q_at = jnp.take_along_axis(q_pad, n_acc[:, None, None], axis=1)[:, 0]
        resid = jnp.maximum(p_at - q_at, 0.0)
        # a rejection guarantees residual mass (p(d) < q(d) somewhere
        # => p > q elsewhere); the fallback only covers f32 underflow
        resid = jnp.where(resid.sum(-1, keepdims=True) > 0, resid, p_at)
        next_s = jax.vmap(jax.random.categorical)(
            step_keys[n_spec + 1],
            jnp.where(resid > 0, jnp.log(resid), -jnp.inf),
        ).astype(jnp.int32)
        nxt = jnp.where(temps > 0.0, next_s, next_g)
    else:
        nxt = next_g

    # emitted row: the accepted draft prefix, then the correction (or
    # bonus) token at column n_acc; columns past it are garbage the
    # host never reads (counts says where the row ends)
    cols = jnp.arange(S1, dtype=jnp.int32)[None, :]
    d_pad = jnp.concatenate([draft_toks, jnp.zeros((B, 1), jnp.int32)], axis=1)
    out = jnp.where(cols < n_acc[:, None], d_pad,
                    jnp.where(cols == n_acc[:, None], nxt[:, None], 0))
    m = n_acc + 1  # emitted tokens this round
    stop_hit = jnp.any(
        (out[:, :, None] == stop_ids[:, None, :])
        & (cols < m[:, None])[:, :, None],
        axis=(1, 2),
    ) & active
    new_cache = {
        "k": tk,
        "v": tv,
        "pos": pos + jnp.where(active, m, 0),
        "remaining": jnp.where(
            active, jnp.where(stop_hit, 0, jnp.maximum(rem - m, 0)), rem),
        "rng": carried,
    }
    if shared:
        new_draft = None
    else:
        # the token now sitting at (new pos) - 1: the last accepted
        # draft token, or the old feed when nothing was accepted — next
        # round's first draft pass re-processes it (hole-fill /
        # idempotent rewrite)
        last_acc = jnp.take_along_axis(
            out, jnp.maximum(n_acc - 1, 0)[:, None], axis=1)[:, 0]
        new_draft = {
            "k": dk,
            "v": dv,
            "prev": jnp.where(active,
                              jnp.where(n_acc > 0, last_acc, feed), prev),
        }
    counts = jnp.where(active, m, 0)
    return out, counts, jnp.where(active, nxt, feed), new_cache, new_draft


def macro_step_slots_spec(params, draft_params, cache, draft_cache, feed,
                          steps, has_admit, prompts, lengths, starts, slots,
                          rems, seeds, tables, temps, top_ks, top_ps,
                          stop_ids, chunk: int, n_spec: int, cfg: LlamaConfig,
                          draft_cfg: LlamaConfig, sampled: bool = True):
    """Speculative macro-step: the macro_step_slots_paged plan shape
    where each of the up-to-`chunk` per-phase steps is a SPECULATIVE
    ROUND (draft proposals + one target verification) instead of one
    decode step — still ONE jitted dispatch, and the THIRD static
    program family beside the PR-7 greedy/sampled pair (non-speculative
    deployments never trace this function, so they pay zero draft
    FLOPs). Admissions prefill BOTH pools: the target admission is the
    stock admit_slots_paged; the draft pool mirrors the same suffix
    through the same block tables, and the slot's tracked previous
    token is reset; both as the pieces of the phase's own count
    (`paged.admit_phase`, as in macro_step_slots_paged). Returns
    (toks (K, chunk, B, n_spec+1),
    counts (K, chunk, B), firsts (K, A), feed, cache, draft_cache) —
    counts[k, t, b] is the number of real tokens in toks[k, t, b] (0
    for skipped phases and inactive lanes); the host's plan-and-repair
    loop reconciles its round ESTIMATES against these observed
    accepted lengths."""
    B = feed.shape[0]
    S1 = n_spec + 1

    def phase(carry, xs):
        (steps_k, admit_k, prompts_k, lengths_k, starts_k, slots_k, rems_k,
         seeds_k, tables_k, temps_k, topk_k, topp_k, stop_k) = xs

        def admit_rows(rows, op):
            c, dc, fd = op
            prompts_w, lengths_w, starts_w, slots_w = rows[:4]
            first, c, fd = admit_slots_paged(
                params, *rows, c, fd, tables_k, temps_k, topk_k, topp_k,
                stop_k, cfg, sampled=sampled,
            )
            if dc is None:
                # shared-pool self-drafting: the target admission IS the
                # draft admission — no mirror prefill, no bookkeeping
                return first, (c, None, fd)
            _, dk2, dv2 = _forward_tokens_paged(
                draft_params, dc["k"], dc["v"], prompts_w,
                tables_k[slots_w], starts_w, lengths_w > 0, draft_cfg,
                with_logits=False,
            )
            # seed the slot's previous token with the last prompt token
            # (position pos - 1, whose draft KV the mirror prefill just
            # wrote — the first round's 2-wide pass rewrites it
            # idempotently). Plan-padding rows route to index B and the
            # scatter drops them, so a real admission is never clobbered.
            last = jnp.take_along_axis(
                prompts_w, jnp.maximum(lengths_w - 1, 0)[:, None],
                axis=1)[:, 0]
            prev = dc["prev"].at[
                jnp.where(lengths_w > 0, slots_w, B)
            ].set(last, mode="drop")
            return first, (c, {"k": dk2, "v": dv2, "prev": prev}, fd)

        # both pools' admissions as the pieces of the phase's own count
        first, (cache, draft_cache, feed) = paged.admit_phase(
            admit_rows, admit_k,
            (prompts_k, lengths_k, starts_k, slots_k, rems_k, seeds_k), carry)

        def step(c, t):
            def run(op):
                cc, dc, fd = op
                with jax.named_scope(DECODE_SCOPE):
                    out, counts, fd, cc, dc = spec_round_slots_paged(
                        params, draft_params, cc, dc, fd, tables_k, temps_k,
                        topk_k, topp_k, stop_k, n_spec, cfg, draft_cfg,
                        sampled=sampled,
                    )
                return (cc, dc, fd), (out, counts)

            def skip(op):
                return op, (jnp.zeros((B, S1), jnp.int32),
                            jnp.zeros((B,), jnp.int32))

            return jax.lax.cond(t < steps_k, run, skip, c)

        (cache, draft_cache, feed), (toks, counts) = jax.lax.scan(
            step, (cache, draft_cache, feed), jnp.arange(chunk))
        return (cache, draft_cache, feed), (toks, counts, first)

    (cache, draft_cache, feed), (toks, counts, firsts) = jax.lax.scan(
        phase, (cache, draft_cache, feed),
        (steps, has_admit, prompts, lengths, starts, slots, rems, seeds,
         tables, temps, top_ks, top_ps, stop_ids),
    )
    return toks, counts, firsts, feed, cache, draft_cache


@functools.lru_cache(maxsize=64)
def _jitted_prefill(cfg: LlamaConfig):
    return jax.jit(paged._bind(prefill, cfg=cfg))


@functools.lru_cache(maxsize=16)
def jitted_macro_step_slots_paged(cfg: LlamaConfig, chunk: int,
                                  sampled: bool = True):
    return jax.jit(
        paged._bind(macro_step_slots_paged, chunk=chunk, cfg=cfg, sampled=sampled),
        donate_argnums=(1,),
    )


@functools.lru_cache(maxsize=16)
def jitted_macro_step_slots_spec(cfg: LlamaConfig, draft_cfg: LlamaConfig,
                                 chunk: int, n_spec: int,
                                 sampled: bool = True):
    """The speculative macro program — the THIRD static variant family
    beside the greedy/sampled pair. Keyed on (cfg, draft_cfg, chunk,
    n_spec, sampled); both KV pools are donated."""
    return jax.jit(
        paged._bind(macro_step_slots_spec, chunk=chunk, n_spec=n_spec,
                    cfg=cfg, draft_cfg=draft_cfg, sampled=sampled),
        donate_argnums=(2, 3),
    )


@functools.lru_cache(maxsize=64)
def _jitted_decode_loop(cfg: LlamaConfig, n_steps: int):
    return jax.jit(
        paged._bind(decode_loop, cfg=cfg, n_steps=n_steps), donate_argnums=(1,)
    )


@functools.lru_cache(maxsize=64)
def _jitted_decode_step(cfg: LlamaConfig):
    return jax.jit(paged._bind(decode_step, cfg=cfg), donate_argnums=(1,))


def sample_loop(params, cache, logits, rng, temperature, top_k, top_p,
                n_steps: int, cfg: LlamaConfig):
    """Sampled decode of `n_steps` tokens as ONE device-side lax.scan —
    the sampled twin of decode_loop (the old sampled path fell out of
    the fused scan into a per-token host loop: one host dispatch per
    token). Carries (cache, logits, rng); each step splits the key,
    draws categorical over temperature-scaled top-k/top-p-masked
    logits, then advances the cache. temperature/top_k/top_p ride as
    traced scalars so one compile serves every setting. Returns
    (tokens (B, n_steps), cache)."""
    B = logits.shape[0]

    def body(carry, _):
        cache, logits, rng = carry
        rng, k = jax.random.split(rng)
        masked = paged._topk_topp_mask(
            logits / jnp.maximum(temperature, 1e-6),
            jnp.broadcast_to(top_k, (B,)), jnp.broadcast_to(top_p, (B,)),
        )
        tok = jax.random.categorical(k, masked, axis=-1).astype(jnp.int32)
        logits, cache = decode_step(params, cache, tok, cfg)
        return (cache, logits, rng), tok

    (cache, _, _), toks = jax.lax.scan(
        body, (cache, logits, rng), None, length=n_steps
    )
    return jnp.moveaxis(toks, 0, 1), cache


@functools.lru_cache(maxsize=64)
def _jitted_sample_loop(cfg: LlamaConfig, n_steps: int):
    return jax.jit(
        paged._bind(sample_loop, cfg=cfg, n_steps=n_steps),
        donate_argnums=(1,),
    )


def generate(params, prompt, cfg: LlamaConfig, max_new_tokens: int,
             temperature: float = 0.0, rng=None, max_len: int = 0,
             top_k: int = 0, top_p: float = 1.0):
    """Greedy (or sampled) generation. prompt: (B, T) int32 → (B,
    max_new_tokens) int32. Jitted callables are memoized per (cfg,
    n_steps) — repeat calls with the same shapes hit XLA's compile
    cache instead of rebuilding jit wrappers (a serving hot path).
    BOTH paths run the whole decode as one device-side scan: greedy via
    decode_loop, sampled via sample_loop (rng threaded through the scan
    carry — a per-token host loop would pay one host dispatch per
    token)."""
    import numpy as np

    prompt = jnp.asarray(prompt, jnp.int32)
    B, T = prompt.shape
    if T == 0:
        raise ValueError("generate() requires a non-empty prompt")
    S = max_len or min(cfg.max_seq_len, T + max_new_tokens)
    cache = init_cache(cfg, B, S)
    logits, cache = _jitted_prefill(cfg)(params, prompt, cache)

    if temperature <= 0:
        # greedy: the whole decode runs as ONE device-side scan
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        rest, _ = _jitted_decode_loop(cfg, max_new_tokens - 1)(params, cache, first)
        return np.concatenate([np.asarray(first)[:, None], np.asarray(rest)], axis=1)

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    toks, _ = _jitted_sample_loop(cfg, max_new_tokens)(
        params, cache, logits, rng,
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(top_k, jnp.int32),
        jnp.asarray(top_p, jnp.float32),
    )
    return np.asarray(toks)
