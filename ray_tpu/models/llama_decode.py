"""Llama autoregressive inference: KV-cache prefill + decode.

The serving-side counterpart of models/llama.py (reference analogue:
the reference serves LLMs through integrated engines inside Serve
replicas — vLLM in examples — rather than in-tree; on TPU the engine
IS the jitted jax program). TPU-first decode design:

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
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.blockwise_attention import NEG_INF
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
# Per-slot decode: the continuous-batching substrate (serve/llm_engine.py).
# The reference delegates continuous batching to vLLM inside replicas; on
# TPU the engine is this jitted program — SURVEY §7 step 10 green-field.
# Design: a fixed pool of B cache SLOTS, each an independent sequence at
# its own position (`pos` is (B,), not a scalar); decode runs in CHUNKS
# of C tokens as one device-side lax.scan (a python step loop pays a
# host dispatch per token), and the host admits/evicts sequences at
# chunk boundaries. Finished slots stop advancing via the `remaining`
# mask; their compute is wasted lanes, which is exactly the waste
# continuous batching bounds (<= C-1 tokens per sequence).
# ---------------------------------------------------------------------------


def init_slot_cache(cfg: LlamaConfig, n_slots: int, max_len: int) -> Dict[str, Any]:
    shape = (cfg.n_layers, n_slots, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
    }


def _gqa_attend_slots(q, k_cache, v_cache, pos, cfg):
    """Per-slot positions: q (B, 1, h, hd), pos (B,) — slot b attends
    its own [0, pos_b] prefix of the dense slot cache."""
    B, _, h, hd = q.shape
    S = k_cache.shape[1]
    qg = q.reshape(B, cfg.n_kv_heads, h // cfg.n_kv_heads, hd)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32
    ) * (hd**-0.5)
    mask = jnp.arange(S)[None, None, None, :] <= pos[:, None, None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgs,bskd->bkgd", probs.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, 1, h * hd).astype(cfg.dtype)


def decode_step_slots(params, cache, tokens, cfg: LlamaConfig):
    """One token on every slot at its own position. Slots with
    remaining == 0 emit garbage (discarded by the engine) and do not
    advance — their cache cells get overwritten on the next admit."""
    B = tokens.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = cache["pos"]                                  # (B,)
    active = cache["remaining"] > 0
    x = params["embed"][tokens][:, None, :].astype(cfg.dtype)
    cos, sin = rope_frequencies(hd, cache["k"].shape[2], cfg.rope_theta)
    positions = pos[:, None]

    def body(carry, layer_and_idx):
        x, k_full, v_full = carry
        layer, li = layer_and_idx
        a = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q = (a @ layer["wq"]).reshape(B, 1, h, hd)
        k = (a @ layer["wk"]).reshape(B, 1, kvh, hd)
        v = (a @ layer["wv"]).reshape(B, 1, kvh, hd)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)

        # per-slot write at each slot's own pos_b: a fori_loop of tiny
        # dynamic_update_slices, NOT .at[li, slot_ids, pos].set — that
        # advanced-index form lowers to an XLA scatter that measured
        # ~25 ms/step (15x the whole step's compute) on TPU
        def write_slot(b, kv):
            kf, vf = kv
            kb = jax.lax.dynamic_slice_in_dim(k, b, 1, axis=0)[None]
            vb = jax.lax.dynamic_slice_in_dim(v, b, 1, axis=0)[None]
            pb = jax.lax.dynamic_index_in_dim(pos, b, keepdims=False)
            kf = jax.lax.dynamic_update_slice(kf, kb, (li, b, pb, 0, 0))
            vf = jax.lax.dynamic_update_slice(vf, vb, (li, b, pb, 0, 0))
            return kf, vf

        k_full, v_full = jax.lax.fori_loop(0, B, write_slot, (k_full, v_full))
        k_cache = jax.lax.dynamic_index_in_dim(k_full, li, 0, keepdims=False)
        v_cache = jax.lax.dynamic_index_in_dim(v_full, li, 0, keepdims=False)
        o = _gqa_attend_slots(q, k_cache, v_cache, pos, cfg) @ layer["wo"]
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
    new_cache = {
        "k": new_k,
        "v": new_v,
        "pos": pos + active.astype(jnp.int32),
        "remaining": jnp.maximum(cache["remaining"] - 1, 0),
    }
    return logits, new_cache


def decode_chunk_slots(params, cache, tokens, chunk: int, cfg: LlamaConfig):
    """Greedy-decode `chunk` tokens on every slot as ONE device-side
    scan. Returns (tokens (B, chunk), cache) — the engine discards the
    tail of slots that finished mid-chunk."""

    def body(carry, _):
        cache, token = carry
        logits, cache = decode_step_slots(params, cache, token, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, nxt), nxt

    (cache, _), toks = jax.lax.scan(body, (cache, tokens), None, length=chunk)
    return jnp.moveaxis(toks, 0, 1), cache


def prefill_into_slots(params, prompts, lengths, slots, cache, cfg: LlamaConfig):
    """BATCHED admission prefill: N right-padded prompts (N, Tb) with
    true `lengths` (N,) land in cache slots `slots` (N,) in ONE program
    — one dispatch per admission batch, not one per sequence (the cost
    of a dispatch on a directly attached chip: not measured). Right-padding is
    safe: causal attention keeps pad positions out of real positions'
    context, and every decode step WRITES its kv at `pos` before
    attending, so a pad cell is overwritten before it ever becomes
    visible. Returns (first tokens (N,), cache).

    Implemented as admit_slots_masked with every row valid and identity
    rems/feed (the caller manages `remaining` and the feed host-side)."""
    first, cache, _ = admit_slots_masked(
        params, prompts, lengths, slots, cache["remaining"][slots], cache,
        jnp.zeros(cache["pos"].shape[0], jnp.int32), cfg,
    )
    return first, cache


def _prefill_all_positions(params, tokens, cache, cfg: LlamaConfig):
    """prefill() variant returning logits for EVERY position (the
    batched-admission path needs per-sequence true-last-position
    logits, not x[:, -1])."""
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
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    return logits, {"k": ks, "v": vs}


def admit_slots_masked(params, prompts, lengths, slots, rems, cache, feed,
                       cfg: LlamaConfig):
    """Fused masked admission (the macro-step building block): prefill A
    right-padded prompts (A, P) and land the rows with length > 0 in
    their target `slots` — cache K/V rows, per-slot `pos`, `remaining`
    AND the decode feed token all update inside the same program, so an
    admission costs ZERO extra dispatches when called from
    macro_step_slots. Rows with length == 0 are plan padding: their
    forward pass computes garbage that is never written anywhere.
    Returns (first tokens (A,), cache, feed)."""
    N, Tb = prompts.shape
    small = init_cache(cfg, N, Tb)
    logits_all, filled = _prefill_all_positions(params, prompts, small, cfg)
    last = jnp.take_along_axis(
        logits_all, (jnp.maximum(lengths, 1) - 1)[:, None, None], axis=1
    )[:, 0, :]
    first = jnp.argmax(last, axis=-1).astype(jnp.int32)
    ks, vs = filled["k"], filled["v"]

    def write_one(n, state):
        # same sequential-DMA trick as prefill_into_slots (advanced-index
        # scatter on the full cache rows measured ~200 ms/call on TPU),
        # with a row-validity cond so plan padding writes nothing
        def wr(st):
            k_big, v_big, pos, rem, fd = st
            s = jax.lax.dynamic_index_in_dim(slots, n, keepdims=False)
            k_big = jax.lax.dynamic_update_slice(
                k_big, jax.lax.dynamic_slice_in_dim(ks, n, 1, axis=1),
                (0, s, 0, 0, 0),
            )
            v_big = jax.lax.dynamic_update_slice(
                v_big, jax.lax.dynamic_slice_in_dim(vs, n, 1, axis=1),
                (0, s, 0, 0, 0),
            )
            pos = pos.at[s].set(lengths[n])
            rem = rem.at[s].set(rems[n])
            fd = fd.at[s].set(first[n])
            return (k_big, v_big, pos, rem, fd)

        return jax.lax.cond(lengths[n] > 0, wr, lambda st: st, state)

    k_big, v_big, pos, rem, feed = jax.lax.fori_loop(
        0, N, write_one,
        (cache["k"], cache["v"], cache["pos"], cache["remaining"], feed),
    )
    return first, {"k": k_big, "v": v_big, "pos": pos, "remaining": rem}, feed


# The two halves of a macro-step phase, as `jax.named_scope`s: every device
# operation of the admission branch carries ADMIT_SCOPE in its name stack and
# every one of a decode step DECODE_SCOPE, so a device trace splits one
# dispatch's time into prefill and decode (benchmark/program_spans.py reads
# them). Metadata only: the compiled program is the same without them.
ADMIT_SCOPE = "admit_prefill"
DECODE_SCOPE = "decode_chunk"


def macro_step_slots(params, cache, feed, steps, has_admit, prompts, lengths,
                     slots, rems, chunk: int, cfg: LlamaConfig):
    """Execute a K-phase macro plan as ONE jitted dispatch: a lax.scan
    over host-planned phases, each phase = cond-guarded fused admission
    prefill (admit_slots_masked) + up to `chunk` decode steps.

    Greedy decode to a requested length means scheduling never depends
    on token values, so the host plans K phases of admissions/evictions
    ahead from counters alone and ships the whole plan (plus the raw
    prompt tokens) as arguments of this single program — collapsing
    one-dispatch-per-chunk + one-dispatch-per-prefill-bucket into
    one dispatch per K chunks.

    Per-phase plan arrays (K = steps.shape[0], A admission lanes, P
    padded prompt width — both host-bucketed so the jit cache stays
    small):
      steps     (K,)       real decode steps this phase (<= chunk);
                           steps beyond it are skipped via lax.cond, so
                           an adaptive (shrunk-to-event) phase costs
                           only its real steps
      has_admit (K,)  bool phase opens with an admission prefill
      prompts   (K, A, P)  right-padded admission prompts
      lengths   (K, A)     true prompt lengths (0 = padding row)
      slots     (K, A)     target slot per admission row
      rems      (K, A)     decode tokens owed after the prefill token

    Returns (toks (K, chunk, B), firsts (K, A), feed (B,), cache):
    toks[k, t] is garbage for t >= steps[k] and for slots whose
    `remaining` hit zero — the host's plan knows exactly which entries
    are real, so it never reads the garbage."""
    A = prompts.shape[1]

    def phase(carry, xs):
        cache, feed = carry
        steps_k, admit_k, prompts_k, lengths_k, slots_k, rems_k = xs

        def do_admit(op):
            c, fd = op
            with jax.named_scope(ADMIT_SCOPE):
                return admit_slots_masked(
                    params, prompts_k, lengths_k, slots_k, rems_k, c, fd, cfg
                )

        def no_admit(op):
            c, fd = op
            return jnp.zeros((A,), jnp.int32), c, fd

        first, cache, feed = jax.lax.cond(admit_k, do_admit, no_admit, (cache, feed))

        def step(c, t):
            def run(op):
                cc, fd = op
                with jax.named_scope(DECODE_SCOPE):
                    logits, cc = decode_step_slots(params, cc, fd, cfg)
                    return cc, jnp.argmax(logits, axis=-1).astype(jnp.int32)

            cc, fd = jax.lax.cond(t < steps_k, run, lambda op: op, c)
            return (cc, fd), fd

        (cache, feed), toks = jax.lax.scan(step, (cache, feed), jnp.arange(chunk))
        return (cache, feed), (toks, first)

    (cache, feed), (toks, firsts) = jax.lax.scan(
        phase, (cache, feed), (steps, has_admit, prompts, lengths, slots, rems)
    )
    return toks, firsts, feed, cache


# ---------------------------------------------------------------------------
# Paged KV decode: block-table attention + real sampling (serve/_internal).
# The dense per-slot cache above welds KV memory to slots x max_len; here
# the device cache is a global pool of fixed-size blocks,
# (L, n_blocks, block_size, kvh, hd), and each slot's sequence lives in
# the blocks its BLOCK TABLE names — PagedAttention (Kwon et al., SOSP
# '23) restated for static shapes: tables are host-planned i32 arrays
# that ride every dispatch as program arguments exactly like prompt
# tokens do, so slot count decouples from sequence length with zero
# recompiles. Block 0 is the NULL block: inactive lanes and plan-padding
# rows aim their writes at it, which is what makes speculative macro
# plans safe when blocks are freed and reused mid-plan (a stopped slot
# cannot corrupt its block's next owner). Sampling (temperature/top-k/
# top-p via jax.random.categorical) and stop-token detection run INSIDE
# the decode scan with per-slot rng threaded through the cache, so
# scheduling stays host-plannable: the host plans speculatively and
# repairs when resolved tokens reveal early stops (serve/llm_engine.py).
# ---------------------------------------------------------------------------


def init_paged_cache(cfg: LlamaConfig, n_slots: int, n_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    """Paged decode state: the block pool plus per-slot scalars. Block
    tables are NOT device state — the host allocator owns them. The pool
    never exists in (n_slots, max_len) form, nor does a lane's context
    outside it: the decode step and the admission read it in place, a
    chunk of blocks at a time (attend_decode_paged, _attend_admission)."""
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


def copy_kv_blocks(cache: Dict[str, Any], src, dst) -> Dict[str, Any]:
    """Copy-on-write block copies: rows dst[i] <- src[i] across every
    layer, K and V. src/dst are (N,) i32 block ids (host-planned by
    BlockAllocator.ensure_writable)."""
    out = dict(cache)
    out["k"] = cache["k"].at[:, dst].set(cache["k"][:, src])
    out["v"] = cache["v"].at[:, dst].set(cache["v"][:, src])
    return out


def gather_kv_blocks(cache, blocks):
    """Lift `blocks` (N,) i32 out of the pool as contiguous device
    slices: -> (k (L, N, bs, kvh, hd), v (...)). The KV-plane export
    kernel — a migrating request's blocks leave the pool as ONE pair of
    arrays (the object plane ships them zero-copy), never block by
    block. Callers bucket-pad `blocks` with the null block; its slices
    are garbage the importer writes straight back into ITS null block."""
    return cache["k"][:, blocks], cache["v"][:, blocks]


def import_kv_blocks(cache, dst, k, v, slot, pos, remaining, rng):
    """KV-plane import: scatter gathered slices into this pool's `dst`
    (N,) i32 blocks and arm `slot` to resume decoding mid-stream at
    absolute position `pos` with `remaining` tokens owed and the
    request's carried rng key (2,) u32. dst's bucket-padding entries
    are the null block — duplicate index-0 writes race only over which
    garbage lands in the garbage block. One fused dispatch per
    migration; the pool buffers are donated."""
    out = dict(cache)
    out["k"] = cache["k"].at[:, dst].set(k)
    out["v"] = cache["v"].at[:, dst].set(v)
    out["pos"] = cache["pos"].at[slot].set(pos)
    out["remaining"] = cache["remaining"].at[slot].set(remaining)
    out["rng"] = cache["rng"].at[slot].set(rng)
    return out


def scatter_kv_blocks(cache, dst, k, v):
    """Prefix-import scatter: land fetched cluster-cache KV slices in
    this pool's `dst` blocks WITHOUT arming any slot — the blocks go to
    the radix prefix cache, not a resuming request, so pos/remaining/rng
    stay untouched (a slot-armed variant would corrupt slot 0 for
    imports that have no slot). dst's padding entries are the null
    block."""
    out = dict(cache)
    out["k"] = cache["k"].at[:, dst].set(k)
    out["v"] = cache["v"].at[:, dst].set(v)
    return out


def _split_slot_keys(keys):
    """(B, 2) u32 raw keys -> (carried (B, 2), subkeys (B, 2))."""
    pairs = jax.vmap(jax.random.split)(keys)  # (B, 2, 2)
    return pairs[:, 0], pairs[:, 1]


def _topk_topp_mask(scaled, top_ks, top_ps):
    """Mask `scaled` logits (B, V) to the per-row top-k / nucleus
    (top-p) support: entries outside it go to -inf. top_k == 0 and
    top_p == 1.0 disable their filters; ties at the cutoff are kept."""
    V = scaled.shape[-1]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_eff = jnp.where(top_ks > 0, jnp.minimum(top_ks, V), V)
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    keep = cum_before < top_ps[:, None]  # the argmax column is always kept
    pth = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True)
    cutoff = jnp.maximum(kth, pth)
    return jnp.where(scaled >= cutoff, scaled, -jnp.inf)


def sample_tokens(logits, temps, top_ks, top_ps, keys):
    """Per-slot sampling: logits (B, V) f32, temps/top_ps (B,) f32,
    top_ks (B,) i32, keys (B, 2) u32 raw PRNG keys -> (B,) i32.
    temperature == 0 lanes take the argmax (bit-identical to the greedy
    path); sampled lanes draw jax.random.categorical over the
    temperature-scaled, top-k/top-p-masked logits with their OWN key."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0.0, temps, 1.0)
    masked = _topk_topp_mask(logits / safe_t[:, None], top_ks, top_ps)
    sampled = jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


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


def _online_softmax_update(carry, s, live, vc, pv: str):
    """One chunk of an online softmax: carry (acc, m, l) in f32, the
    chunk's scores s (..., C) f32 with `live` (broadcastable to s) marking
    the positions that count, its values vc, and the einsum `pv` of
    probabilities (cast to the value dtype) with values."""
    acc, m, l = carry
    m_new = jnp.maximum(m, jnp.where(live, s, NEG_INF).max(axis=-1))
    p = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m - m_new)
    acc = acc * corr[..., None] + jnp.einsum(
        pv, p.astype(vc.dtype), vc, preferred_element_type=jnp.float32)
    return acc, m_new, l * corr + p.sum(axis=-1)


# positions of context one iteration of the decode attention's loop reads
# from the pool for every lane (rounded to whole blocks). Chosen once on a
# v5e (PR 30, the attention alone, ms a layer at a longest context of 560 /
# 4096): 32 lanes of flat 512-column rows 0.17 / 1.04 at 128, 0.22 / 1.13 at
# 256, 0.28 / 1.08 at 512 (a chunk's gather is its bytes three times over, so
# whole chunks past the longest lane cost); 4 lanes of (8, 128) rows 0.035 /
# 0.22, 0.040 / 0.20, 0.043 / 0.16: an iteration's own overhead is small
DECODE_CHUNK = 128


def decode_chunk_positions(block_size: int, max_blocks: int) -> int:
    """Positions a chunk of attend_decode_paged covers, given the pool's
    block size and the tables' width: what the engine's `ctx_chunks` and
    `span_chunks` count in."""
    return min(max(DECODE_CHUNK // block_size, 1), max_blocks) * block_size


def attend_decode_paged(q, k_full, v_full, li, tables, pos, active, scale,
                        v_cols: int = 0):
    """Decode attention in proportion to the context the lanes hold: one
    query a lane, q (B, h, hd), lane b attending positions [0, pos[b]] of
    layer `li` of the pools AFTER the step's own K/V write; tables
    (B, MB), active (B,) bool. Returns (B, h * hd) in q's dtype.

    The context is read straight out of the pool, a chunk of blocks at a
    time under an online softmax (the arithmetic of _attend_admission's
    prefix loop with one query a row), for ceil((longest live context) /
    chunk) iterations: a trip count that is data in the program, so the
    step's attention follows what the lanes hold and never the table
    span. The gather carries the layer index (slicing the layer off the
    pool first copies it whole, every step). An inactive lane does not
    lengthen the loop; its output is whatever the live lanes' chunks
    covered of it (all zeros when no lane is live) and is discarded by
    the caller. bf16 operands, f32 scores, softmax and accumulation,
    probabilities cast to the value dtype for the PV product.

    Both pool layouts: rows of (kvh, hd), pools of rank 5, take the GQA
    products; flat rows of kvh * hd columns, rank 4 (a head size under
    128 would be padded to it on a TPU), keep the gathered chunk as it
    lies and lay the QUERY out flat instead: each query head's vector in
    its KV head's columns, zeros elsewhere, the products over all
    kvh * hd columns (kvh times the operations, on one query nothing;
    splitting the chunk's minor axis into heads would relayout it).

    The single-pool form, `v_full` None: the pool's rows are ONE key a
    position, as wide as a query (flat, one "KV head"), and a position's
    value is the first `v_cols` columns of its own key row (a latent cache:
    [c | rotary part], values c). A chunk is gathered once and read twice;
    the return is (B, h * v_cols)."""
    B, h, hd = q.shape
    bs, MB = k_full.shape[2], tables.shape[1]
    row = k_full.shape[3:]
    flat = len(row) == 1
    kvh = row[0] // hd if flat else row[0]
    hv = v_cols or hd  # a head's value columns
    if flat:
        own = jnp.eye(kvh, dtype=q.dtype)[None, :, None, :, None]  # head k's columns
        qx = (q.reshape(B, kvh, h // kvh, 1, hd) * own).reshape(B, h, kvh * hd)
        qk, pv = "bhc,bsc->bhs", "bhs,bsc->bhc"
    else:
        qx = q.reshape(B, kvh, h // kvh, hd)
        qk, pv = "bkgd,bskd->bkgs", "bkgs,bskd->bkgd"
    stat = qx.shape[:-1]
    C = decode_chunk_positions(bs, MB)
    cb = C // bs  # blocks a chunk
    # whole chunks only: the tail names the null block and is never live
    chunked = jnp.pad(tables, ((0, 0), (0, -MB % cb)))
    longest = jnp.max(jnp.where(active, pos + 1, 0))

    def chunk(i, carry):
        blocks = jax.lax.dynamic_slice_in_dim(chunked, i * cb, cb, axis=1)
        kc = k_full[li, blocks].reshape((B, C) + row)
        vc = kc[..., :v_cols] if v_full is None else v_full[li, blocks].reshape((B, C) + row)
        s = jnp.einsum(qk, qx, kc, preferred_element_type=jnp.float32) * scale
        live = (i * C + jnp.arange(C))[None, :] <= pos[:, None]  # (B, C)
        live = live.reshape((B,) + (1,) * (len(stat) - 1) + (C,))
        return _online_softmax_update(carry, s, live, vc, pv)

    acc, _, l = jax.lax.fori_loop(
        0, (longest + C - 1) // C, chunk,
        (jnp.zeros(stat + (qx.shape[-1] // hd * hv,), jnp.float32),
         jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32)),
    )
    o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]  # no chunk ran: zeros
    if flat:
        o = (o.reshape(B, kvh, h // kvh, kvh, hv) * own.astype(jnp.float32)).sum(axis=3)
    return o.reshape(B, h * hv).astype(q.dtype)


def write_decode_kv(k_full, v_full, li, k, v, tables, pos, active):
    """One decode step's K/V (B, 1, *row) into layer `li` of a pool
    (L, n_blocks, bs, *row): per-slot write into the slot's CURRENT
    block at its own offset (same sequential-DMA trick as the dense
    path: the advanced-index scatter form measured ~25 ms/step on TPU).
    Inactive lanes write the null block. A cache of ONE pool (a latent
    row a position) passes `v_full` and `v` None and gets None back."""
    B = k.shape[0]
    bs = k_full.shape[2]
    row0 = (0,) * (k_full.ndim - 3)
    pools, new = _pools(k_full, v_full), _pools(k, v)

    def write_slot(b, pools):
        rows = [jax.lax.dynamic_slice_in_dim(n, b, 1, axis=0)[None] for n in new]
        pb = jax.lax.dynamic_index_in_dim(pos, b, keepdims=False)
        ab = jax.lax.dynamic_index_in_dim(active, b, keepdims=False)
        row = jax.lax.dynamic_index_in_dim(tables, b, 0, keepdims=False)
        blk = jax.lax.dynamic_index_in_dim(row, pb // bs, keepdims=False)
        blk = jnp.where(ab, blk, 0)  # inactive lanes write the null block
        off = jnp.where(ab, pb % bs, 0)
        return tuple(jax.lax.dynamic_update_slice(f, r, (li, blk, off) + row0)
                     for f, r in zip(pools, rows))

    return _k_and_v(jax.lax.fori_loop(0, B, write_slot, pools))


def _pools(k, v):
    """(k, v), or (k,) for a cache of one pool."""
    return (k,) if v is None else (k, v)


def _k_and_v(pools):
    return pools if len(pools) == 2 else (pools[0], None)


def finish_decode_step(logits, cache, active, temps, top_ks, top_ps, stop_ids,
                       sampled: bool):
    """What every model's paged decode step ends with: the next token
    of each lane from its logits (B, V) f32 and the per-slot scalars
    after the step (`active` = remaining > 0 before it).
    Returns (next tokens, pos, remaining, rng)."""
    if sampled:
        new_rng, sub = _split_slot_keys(cache["rng"])
        nxt = sample_tokens(logits, temps, top_ks, top_ps, sub)
    else:
        new_rng = cache["rng"]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    stopped = jnp.any(nxt[:, None] == stop_ids, axis=-1) & active
    pos = cache["pos"] + active.astype(jnp.int32)
    remaining = jnp.where(stopped, 0, jnp.maximum(cache["remaining"] - 1, 0))
    return nxt, pos, remaining, new_rng


def _qkv(a, layer, cfg: LlamaConfig):
    """The paged programs' q / k / v projections: a (..., d_model) against
    one layer's `wq`, `wk`, `wv`, split into heads AFTER the products.
    Returns q (..., h, hd), k and v (..., kvh, hd), each `a @ w` bit for bit.

    The barrier keeps the head split out of the product. Without it the TPU
    compiler folds `.reshape(..., h, hd)` into the matmul and reads the
    weight as (head, head_dim, d_model); to feed that it copies every
    layer's slice out of the stacked parameter in EVERY decode step and
    transposes the three whole stacks in every dispatch. Compiled for a
    v5e at Mistral-7B's widths, 16 layers, 4 lanes (compiled only, PR 32;
    tests/test_tpu_compile.py holds it): three multi-output fusions a step
    that write 16 x bf16[1,4096,4096] + 2 x 16 x bf16[1,4096,1024], 805 MB
    (2.22 ms of an 11.69 ms step on the chip, PR 30's trace, segment
    `slice`), three to six copies of a bf16[16,4096,*] stack a dispatch,
    and 1.56 / 1.87 GB of temporaries at (A, P) = (1, 16) / (4, 512); with
    it the product reads `params["layers"]["wq"]` where it lies, as `wo`
    and the MLP's do, and the temporaries are 0.002 / 0.27 GB. The barrier
    alone is NOT enough: with the sixteen layers unrolled every form that
    drops the stack copies makes the compiler copy the whole K pool
    (bf16[16,1025,16,8,128], 537 MB) twice a decode step, so the decode
    step and the admission walk their layers in a rolled scan. Do not
    simplify either away without running that compile test."""
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lead = a.shape[:-1]
    q, k, v = jax.lax.optimization_barrier(
        (a @ layer["wq"], a @ layer["wk"], a @ layer["wv"]))
    return (q.reshape(*lead, h, hd), k.reshape(*lead, kvh, hd),
            v.reshape(*lead, kvh, hd))


def decode_step_slots_paged(params, cache, tokens, tables, temps, top_ks,
                            top_ps, stop_ids, cfg: LlamaConfig,
                            sampled: bool = True):
    """One token on every slot against the PAGED cache. tables (B, MB)
    i32 name each slot's blocks (0-padded -> null block); temps/top_ks/
    top_ps are the per-slot sampling plan; stop_ids (B, NS) i32 are
    -1-padded stop sets. Inactive lanes (remaining == 0) aim their KV
    write at the null block — their old blocks may already belong to a
    later-phase admission of the same macro plan. Each layer attends the
    lanes' contexts in place (attend_decode_paged): as many chunks of
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

        k_full, v_full = write_decode_kv(
            k_full, v_full, li, k, v, tables, pos, active)
        o = attend_decode_paged(
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
    nxt, new_pos, remaining, new_rng = finish_decode_step(
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
    prompt, goes through _attend_admission."""
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


# positions of reused prefix one iteration of the admission's prefix loop
# gathers from the pool and scores (rounded to whole blocks)
PREFIX_CHUNK = 512


def _attend_admission(q, k, v, k_layer, v_layer, adm_tables, starts,
                      cfg: LlamaConfig):
    """Admission attention, in proportion to the context a row has: q
    (A, P, h, hd) and the rows' own just-projected k/v (A, P, kvh, hd) at
    positions starts[n] + t; k_layer/v_layer (n_blocks, bs, kvh, hd) the
    layer's pool AFTER every row's suffix write; adm_tables (A, MB).

    A row's context has two parts. Its own SUFFIX is causal P x P on k/v
    as they are, no pool read (the flash forward: Pallas on the chip,
    blockwise XLA elsewhere); a real query at t < length never sees a
    right-pad key at s > t. Its reused PREFIX, positions s < starts[n],
    is read from the pool PREFIX_CHUNK positions at a time under an
    online softmax, with a trip count ceil(max(starts) / chunk) that is
    data in the program; the two merge by their log-sum-exp. When no row
    has a prefix the loop and the merge are skipped (a cond on the same
    plan array). Nowhere is there a (P x table span) score, nor a gather
    of the span. bf16 operands, f32 accumulation and softmax,
    probabilities cast to the value dtype for the PV product."""
    # imported where it is traced, as models/llama.py does: Pallas takes a
    # second to import, and only a process that traces a program needs it
    from ray_tpu.ops.flash_attention import flash_attention_fwd

    A, P, h, hd = q.shape
    kvh = cfg.n_kv_heads
    bs = k_layer.shape[1]
    MB = adm_tables.shape[1]
    o_s, lse_s = flash_attention_fwd(q, k, v, causal=True)  # (A,P,h,hd), (A,P,h)

    cb = min(max(PREFIX_CHUNK // bs, 1), MB)  # blocks a chunk
    C = cb * bs
    # whole chunks only: the tail names the null block and is never live
    chunked = jnp.pad(adm_tables, ((0, 0), (0, -MB % cb)))
    qg = q.reshape(A, P, kvh, h // kvh, hd)
    longest = jnp.max(starts)

    def chunk(i, carry):
        blocks = jax.lax.dynamic_slice_in_dim(chunked, i * cb, cb, axis=1)
        kc = k_layer[blocks].reshape(A, C, kvh, hd)
        vc = v_layer[blocks].reshape(A, C, kvh, hd)
        s = jnp.einsum(
            "apkgd,ackd->akgpc", qg, kc, preferred_element_type=jnp.float32
        ) * (hd**-0.5)
        live = (i * C + jnp.arange(C))[None, :] < starts[:, None]  # (A, C)
        live = live[:, None, None, None, :]
        return _online_softmax_update(carry, s, live, vc, "akgpc,ackd->akgpd")

    def with_prefix(o_s):
        stat = (A, kvh, h // kvh, P)
        acc, m, l = jax.lax.fori_loop(
            0, (longest + C - 1) // C, chunk,
            (jnp.zeros(stat + (hd,), jnp.float32),
             jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32)),
        )
        # a row without prefix keeps l = 0, lse_p = NEG_INF: its weight is 0
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_p = (acc / l_safe[..., None]).transpose(0, 3, 1, 2, 4).reshape(A, P, h, hd)
        lse_p = (m + jnp.log(l_safe)).transpose(0, 3, 1, 2).reshape(A, P, h)
        lse = jnp.logaddexp(lse_s, lse_p)
        out = (o_s.astype(jnp.float32) * jnp.exp(lse_s - lse)[..., None]
               + o_p * jnp.exp(lse_p - lse)[..., None])
        return out.astype(o_s.dtype)

    # no row with a prefix (every admission without a radix-cache hit):
    # the suffix part is the answer, no accumulator and no merge
    out = jax.lax.cond(longest > 0, with_prefix, lambda o_s: o_s, o_s)
    return out.reshape(A, P, h * hd).astype(cfg.dtype)


def write_admission_kv(k_full, v_full, li, k, v, adm_tables, starts, valid):
    """Every valid admission row's K/V (A, P, *row) into layer `li` of
    a pool (L, n_blocks, bs, *row), block by block from block
    starts[n] // bs of the row's table; blocks past the table's edge go
    to the null block. `v_full` and `v` None: a cache of one pool, as
    write_decode_kv takes it."""
    A, P = k.shape[:2]
    row = k.shape[2:]
    row0 = (0,) * len(row)
    bs = k_full.shape[2]
    MB = adm_tables.shape[1]
    n_chunks = P // bs
    new = _pools(k, v)

    def write_row(n, kv):
        def wr(kv):
            s0 = jax.lax.dynamic_index_in_dim(starts, n, keepdims=False) // bs
            table = jax.lax.dynamic_index_in_dim(adm_tables, n, 0, keepdims=False)

            def write_block(j, kv):
                idx = s0 + j
                blk = jax.lax.dynamic_index_in_dim(
                    table, jnp.minimum(idx, MB - 1), keepdims=False
                )
                blk = jnp.where(idx < MB, blk, 0)  # pad overshoot -> null
                cs = [jax.lax.dynamic_slice(
                    r, (n, j * bs) + row0, (1, bs) + row)[0][None, None] for r in new]
                return tuple(jax.lax.dynamic_update_slice(f, c, (li, blk, 0) + row0)
                             for f, c in zip(kv, cs))

            # a loop, eight blocks an iteration: spelled out as P // bs
            # blocks in Python, the (4, 1024) program at 16 layers took
            # twice as long to lower, to compile (78 s against 32 on a
            # v5e host, PR 28) and to load from the compile cache
            return jax.lax.fori_loop(0, n_chunks, write_block, kv,
                                     unroll=min(8, n_chunks))

        return jax.lax.cond(valid[n], wr, lambda kv: kv, kv)

    return _k_and_v(jax.lax.fori_loop(0, A, write_row, _pools(k_full, v_full)))


def finish_admission(last, cache, feed, valid, lengths, starts, slots, rems,
                     seeds, temps, top_ks, top_ps, stop_ids, sampled: bool):
    """What every model's paged admission ends with: each row's first
    output token from its true-last-position logits `last` (A, V) f32
    (sampled with a key seeded from `seeds[n]`), and the per-slot
    scalars armed for the `valid` rows (length > 0). Returns (first
    tokens, pos, remaining, feed, rng)."""
    A = last.shape[0]
    if sampled:
        row_keys = jax.vmap(jax.random.PRNGKey)(seeds)
        carried, sub = _split_slot_keys(row_keys)
        first = sample_tokens(
            last, temps[slots], top_ks[slots], top_ps[slots], sub
        )
    else:
        carried = None  # greedy plans never consume slot keys
        first = jnp.argmax(last, axis=-1).astype(jnp.int32)
    first_stopped = jnp.any(first[:, None] == stop_ids[slots], axis=-1)

    def write_one(n, state):
        def wr(st):
            pos, rem, fd, rng = st
            s = jax.lax.dynamic_index_in_dim(slots, n, keepdims=False)
            pos = pos.at[s].set(starts[n] + lengths[n])
            rem = rem.at[s].set(jnp.where(first_stopped[n], 0, rems[n]))
            fd = fd.at[s].set(first[n])
            if sampled:
                rng = rng.at[s].set(carried[n])
            return (pos, rem, fd, rng)

        return jax.lax.cond(valid[n], wr, lambda st: st, state)

    pos, rem, feed, rng = jax.lax.fori_loop(
        0, A, write_one,
        (cache["pos"], cache["remaining"], feed, cache["rng"]),
    )
    return first, pos, rem, feed, rng


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
    attention (_attend_admission) is causal over the row's own suffix
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
        k_full, v_full = write_admission_kv(
            k_full, v_full, li, k, v, adm_tables, starts, valid)
        # phase 2: every row reads its prefix (sees all phase-1 writes)
        k_layer = jax.lax.dynamic_index_in_dim(k_full, li, 0, keepdims=False)
        v_layer = jax.lax.dynamic_index_in_dim(v_full, li, 0, keepdims=False)
        o = _attend_admission(q, k, v, k_layer, v_layer, adm_tables, starts, cfg)
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
    first, pos, rem, feed, rng = finish_admission(
        last, cache, feed, valid, lengths, starts, slots, rems, seeds, temps,
        top_ks, top_ps, stop_ids, sampled)
    cache = {"k": k_big, "v": v_big, "pos": pos, "remaining": rem, "rng": rng}
    return first, cache, feed


# Tokens a pass over bfloat16 weights is worth on a v5e: 197 TFLOP/s over
# 819 GB/s (`benchmark/peaks.json`) is 240 operations a byte, at two
# operations a token and two bytes a weight ~240 tokens, taken at the next
# power of two. An admission of fewer tokens waits for the weights and
# costs a pass whatever its rows; one of more is bound by its rows, and
# the pass hides behind them (on the chip, PR 46: Mistral admits 1, 2 and
# 4 rows of 256 in 15.2, 30.8 and 53.4 ms where a pass is ~9; PERF.md
# section 6).
RIDGE_TOKENS = 256


def admit_pieces(n: int, lanes: int, P: int) -> Tuple[int, ...]:
    """The widths, widest first, of the admissions that a phase of `n`
    prompts runs in a program `lanes` admission rows wide and `P` tokens
    long: the binary pieces of its count (3 = 2 + 1, 7 = 4 + 2 + 1) where
    the rows left out are worth the further admissions, one piece of a
    phase that admits nobody. The candidates keep the top j bits of `n`
    and round what is left up to a power of two (n = 11: (16,), (8, 4),
    (8, 2, 1); `lanes` caps the single piece), so the widths of a choice
    are distinct. A piece costs its tokens, or a pass over the weights
    where it has fewer than `RIDGE_TOKENS`; the candidate of the least
    cost is taken, the fewest pieces among equals: from rows of 256 up
    every piece is worth its rows and the pieces are the count's own
    bits, at 16 a phase runs one piece as it did before PR 46. The device
    runs its bodies by this function (`admit_phase`) and the engine
    counts `admit_rows` and `admit_pieces` by it (`_dispatch_counts`):
    plain Python on host integers."""
    def cost(pieces):
        return sum(max(w * P, RIDGE_TOKENS) for w in pieces)

    n = max(n, 1)
    bits = [1 << b for b in reversed(range(n.bit_length())) if n >> b & 1]
    best = (min(1 << (n - 1).bit_length(), lanes),)
    for j in range(1, len(bits)):
        rest = n - sum(bits[:j])
        pieces = (*bits[:j], 1 << (rest - 1).bit_length())
        # (4, 4) is (8,), a candidate already; a sum past `lanes` has no rows
        if pieces[-1] < bits[j - 1] and sum(pieces) <= lanes and cost(pieces) < cost(best):
            best = pieces
    return best


def admit_phase(admit_rows, has_admit, rows, carry):
    """A phase's admission as the pieces of its count. `rows` are the
    phase's per-row plan arrays, each with a leading A, the prompts (A, P)
    first and their true lengths second (0 = a padding row);
    `admit_rows(rows, carry) -> (first (w,), carry)` is the admission
    proper, row-independent, for any leading w. One `lax.cond` a width w
    = A, .., 4, 2, 1, of which an admitting phase takes those that
    `admit_pieces(reach, A, P)` names, widest first, `reach` the phase's
    last non-empty row (for a plan that fills rows 0 .. n - 1,
    `_dispatch_macro`, its count n): each runs `admit_rows` under
    ADMIT_SCOPE on the w rows behind those of the pieces before it, so
    rows are admitted in plan order (a row whose table names blocks that
    an earlier row of its phase fills reads them written), and the rows
    behind the last piece are padding and are not computed. Which widths
    run and where they start is a static table the device indexes by
    `reach`. A chain of two-way conds and not one `lax.switch`: under a
    switch of three or more branches the TPU compiler copies the K/V pool
    (the hybrid's state) twice a layer in every branch but the widest
    (compiled only, PR 42); through a cond that either admits or hands
    its operands on, as the skeleton always had, they stay in place, and
    the branches share one set of temporaries, the widest's.
    -> (first (A,), carry)."""
    A, P = rows[0].shape
    reach = jnp.max(jnp.where(rows[1] > 0, jnp.arange(1, A + 1), 0))
    begins: Dict[int, List[int]] = {}  # width -> its first row by reach, -1 where it does not run
    for n in range(A + 1):
        at = 0
        for w in admit_pieces(n, A, P):
            begins.setdefault(w, [-1] * (A + 1))[n] = at
            at += w
    first = jnp.zeros((A,), jnp.int32)
    for w in sorted(begins, reverse=True):
        at = jnp.asarray(begins[w], jnp.int32)[reach]

        def run(carry, w=w, at=at):
            with jax.named_scope(ADMIT_SCOPE):
                got, carry = admit_rows(
                    tuple(jax.lax.dynamic_slice_in_dim(r, at, w) for r in rows), carry)
            return jax.lax.dynamic_update_slice(jnp.zeros((A,), jnp.int32), got, (at,)), carry

        got, carry = jax.lax.cond(
            has_admit & (at >= 0), run,
            lambda carry: (jnp.zeros((A,), jnp.int32), carry), carry)
        first = first + got
    return first, carry


def macro_step_slots_paged(params, cache, feed, steps, has_admit, prompts,
                           lengths, starts, slots, rems, seeds, tables, temps,
                           top_ks, top_ps, stop_ids, chunk: int,
                           cfg, sampled: bool = True, admit=None,
                           decode_step=None):
    """Paged macro-step: the macro_step_slots plan shape extended with
    the paged/sampling plan arrays, still ONE jitted dispatch. The phase
    and step skeleton is every model's: `admit` and `decode_step` are
    the model's own admission and one-token step over its own cache
    pytree, with the signatures of admit_slots_paged and
    decode_step_slots_paged (the defaults, Llama's). A is the widest a
    phase can admit (the engine passes its lanes' bucket, `_variant`),
    not the width an admission runs at: each admitting phase runs the
    model's `admit` on the rows up to its last non-empty one as the
    pieces of its count, 3 rows as 2 + 1 (`admit_phase`: a plan fills a
    phase's rows from 0 up, and what lies behind them is not computed),
    each piece through the body of its width, so the program holds one
    admission body a width 1, 2, 4, .., A and one decode body. Extra
    per-phase arrays (K phases, B slots, A admission lanes, MB table
    width, NS stop width):
      starts   (K, A)        cached-prefix length per admission row
                             (block-aligned; its blocks are reused, not
                             re-prefilled)
      seeds    (K, A) u32    per-request sampling seeds
      tables   (K, B, MB)    per-phase block tables — admissions and
                             plan-time evictions swap tables at exactly
                             the phase boundary they were planned for
      temps    (K, B) f32    0.0 => greedy argmax for that slot
      top_ks   (K, B) i32    0 => disabled
      top_ps   (K, B) f32    1.0 => disabled
      stop_ids (K, B, NS)    -1-padded device-side stop sets

    The plan is SPECULATIVE under sampling: a slot that samples a stop
    token goes inactive device-side (writes aim at the null block, pos
    freezes) while later planned phases still burn its lane — the host
    bills those steps as speculative waste and repairs its plan when
    the tokens resolve. `sampled` is STATIC (two compiled variants):
    the host knows at plan time whether any resident request samples,
    and an all-greedy plan must not pay the per-step sort/softmax/rng
    pipeline. Returns (toks (K, chunk, B), firsts (K, A), feed,
    cache)."""
    admit = admit or admit_slots_paged
    decode_step = decode_step or decode_step_slots_paged

    def phase(carry, xs):
        (steps_k, admit_k, prompts_k, lengths_k, starts_k, slots_k, rems_k,
         seeds_k, tables_k, temps_k, topk_k, topp_k, stop_k) = xs

        def admit_rows(rows, op):
            first, c, fd = admit(
                params, *rows, *op, tables_k, temps_k, topk_k, topp_k, stop_k,
                cfg, sampled=sampled,
            )
            return first, (c, fd)

        first, (cache, feed) = admit_phase(
            admit_rows, admit_k,
            (prompts_k, lengths_k, starts_k, slots_k, rems_k, seeds_k), carry)

        def step(c, t):
            def run(op):
                cc, fd = op
                with jax.named_scope(DECODE_SCOPE):
                    _, nxt, cc = decode_step(
                        params, cc, fd, tables_k, temps_k, topk_k, topp_k,
                        stop_k, cfg, sampled=sampled,
                    )
                return cc, nxt

            cc, fd = jax.lax.cond(t < steps_k, run, lambda op: op, c)
            return (cc, fd), fd

        (cache, feed), toks = jax.lax.scan(step, (cache, feed), jnp.arange(chunk))
        return (cache, feed), (toks, first)

    (cache, feed), (toks, firsts) = jax.lax.scan(
        phase, (cache, feed),
        (steps, has_admit, prompts, lengths, starts, slots, rems, seeds,
         tables, temps, top_ks, top_ps, stop_ids),
    )
    return toks, firsts, feed, cache


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
        carried, round_key = _split_slot_keys(cache["rng"])
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
            masked = _topk_topp_mask(lg / safe_t[:, None], top_ks, top_ps)
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
            _topk_topp_mask(flat, jnp.repeat(top_ks, S1),
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
    (`admit_phase`, as in macro_step_slots_paged). Returns
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
        first, (cache, draft_cache, feed) = admit_phase(
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


def write_lane_rows(full, li, rows, slots, valid, lane_axis: int = 1):
    """full[li, ..., slots[n], ...] = rows[n] for the valid rows (lanes on
    `lane_axis` of `full`), one in-place update a row; invalid rows all
    name lane 0 and write nothing."""
    rest = rows.shape[1:]
    shape = (1,) + rest[:lane_axis - 1] + (1,) + rest[lane_axis - 1:]

    def write(n, full):
        def wr(full):
            row = jax.lax.dynamic_index_in_dim(rows, n, 0, keepdims=False)
            at = [0] * full.ndim
            at[0], at[lane_axis] = li, slots[n]
            return jax.lax.dynamic_update_slice(
                full, row.reshape(shape).astype(full.dtype), at)

        return jax.lax.cond(valid[n], wr, lambda full: full, full)

    return jax.lax.fori_loop(0, rows.shape[0], write, full)


def rows_a_piece(R: int, T: int, tokens: int) -> int:
    """How many of an admission's R rows of T positions one pass of a mixer
    takes so that it holds `tokens` tokens at the most (one row at the
    least): a divisor of R, so that the pieces are of one shape (rows are
    independent sequences, so a mixer may walk them in pieces)."""
    n = max(1, min(R, tokens // T))
    while R % n:
        n -= 1
    return n


def generate_through_paged_cache(init_cache, admit, decode_step, params, prompt,
                                 cfg, n_new: int, block: int = 16):
    """Greedy tokens (R, n_new) for prompts (R, T) of one length, for a
    model that has only the paged halves: one admission and n_new - 1
    decode steps through a paged cache that holds exactly these rows
    (`init_cache`, `admit`, `decode_step`: the model's init_paged_cache,
    admit_slots_paged and decode_step_slots_paged)."""
    R, T = prompt.shape
    mb = -(-(T + n_new) // block)
    P = -(-T // block) * block
    cache = init_cache(cfg, R, R * mb + 1, block)
    tables = 1 + jnp.arange(R * mb, dtype=jnp.int32).reshape(R, mb)
    zeros = jnp.zeros((R,), jnp.int32)
    plan = dict(temps=jnp.zeros((R,), jnp.float32), top_ks=zeros,
                top_ps=jnp.ones((R,), jnp.float32),
                stop_ids=jnp.full((R, 1), -1, jnp.int32))
    first, cache, feed = admit(
        params, jnp.pad(prompt, ((0, 0), (0, P - T))), jnp.full((R,), T, jnp.int32), zeros,
        jnp.arange(R, dtype=jnp.int32), jnp.full((R,), n_new - 1, jnp.int32),
        zeros.astype(jnp.uint32), cache, zeros, tables, cfg=cfg, sampled=False, **plan)

    def step(carry, _):
        cache, feed = carry
        _, nxt, cache = decode_step(
            params, cache, feed, tables, cfg=cfg, sampled=False, **plan)
        return (cache, nxt), nxt

    _, rest = jax.lax.scan(step, (cache, feed), None, length=n_new - 1)
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def _bind(f, **static):
    """`functools.partial(f, **static)` under `f`'s own name. `jax.jit`
    names a program after its function's `__name__`, and a bare partial
    has none: every program below would read `jit__unknown` in a device
    trace, where a reader has to find the macro-step by name."""
    bound = functools.partial(f, **static)
    bound.__name__ = f.__name__
    return bound


@functools.lru_cache(maxsize=64)
def _jitted_prefill(cfg: LlamaConfig):
    return jax.jit(_bind(prefill, cfg=cfg))


# engine-side jitted programs, memoized per (cfg, chunk) so every
# ContinuousBatchingEngine with the same geometry shares ONE jit wrapper
# (and therefore one compile cache) — a replica restart or an A/B pair
# of engines used to recompile the whole macro program from scratch
@functools.lru_cache(maxsize=16)
def jitted_prefill_into_slots(cfg: LlamaConfig):
    return jax.jit(_bind(prefill_into_slots, cfg=cfg))


@functools.lru_cache(maxsize=16)
def jitted_decode_chunk_slots(cfg: LlamaConfig, chunk: int):
    return jax.jit(
        _bind(decode_chunk_slots, chunk=chunk, cfg=cfg),
        donate_argnums=(1,),
    )


@functools.lru_cache(maxsize=16)
def jitted_macro_step_slots(cfg: LlamaConfig, chunk: int):
    return jax.jit(
        _bind(macro_step_slots, chunk=chunk, cfg=cfg),
        donate_argnums=(1,),
    )


@functools.lru_cache(maxsize=16)
def jitted_macro_step_slots_paged(cfg: LlamaConfig, chunk: int,
                                  sampled: bool = True):
    return jax.jit(
        _bind(macro_step_slots_paged, chunk=chunk, cfg=cfg, sampled=sampled),
        donate_argnums=(1,),
    )


@functools.lru_cache(maxsize=4)
def jitted_gather_kv_blocks():
    """KV-plane export gather. Shape-polymorphic: jit re-specializes
    per bucketed block count, so callers pad block-id arrays to
    power-of-2 buckets (null-block padding) to bound the variant set."""
    return jax.jit(gather_kv_blocks)


@functools.lru_cache(maxsize=4)
def jitted_import_kv_blocks():
    """KV-plane import scatter; the pool is donated (the engine swaps
    its cache handle for the return value)."""
    return jax.jit(import_kv_blocks, donate_argnums=(0,))


@functools.lru_cache(maxsize=4)
def jitted_scatter_kv_blocks():
    """Slot-less prefix-import scatter (cluster prefix cache); donated
    pool, same bucketing discipline as the gather."""
    return jax.jit(scatter_kv_blocks, donate_argnums=(0,))


@functools.lru_cache(maxsize=16)
def jitted_macro_step_slots_spec(cfg: LlamaConfig, draft_cfg: LlamaConfig,
                                 chunk: int, n_spec: int,
                                 sampled: bool = True):
    """The speculative macro program — the THIRD static variant family
    beside the greedy/sampled pair. Keyed on (cfg, draft_cfg, chunk,
    n_spec, sampled); both KV pools are donated."""
    return jax.jit(
        _bind(macro_step_slots_spec, chunk=chunk, n_spec=n_spec,
              cfg=cfg, draft_cfg=draft_cfg, sampled=sampled),
        donate_argnums=(2, 3),
    )


@functools.lru_cache(maxsize=64)
def _jitted_decode_loop(cfg: LlamaConfig, n_steps: int):
    return jax.jit(
        _bind(decode_loop, cfg=cfg, n_steps=n_steps), donate_argnums=(1,)
    )


@functools.lru_cache(maxsize=64)
def _jitted_decode_step(cfg: LlamaConfig):
    return jax.jit(_bind(decode_step, cfg=cfg), donate_argnums=(1,))


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
        masked = _topk_topp_mask(
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
        _bind(sample_loop, cfg=cfg, n_steps=n_steps),
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
