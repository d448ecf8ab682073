"""The Qwen3-Next decoder on the paged serving path: a lane holds a matrix
state a head beside its K/V blocks, and every layer is an expert layer that
holds a part of its experts.

The macro-step is models/paged.macro_step_slots_paged, handed this
module's admission and decode step and this module's cache pytree, the
hybrid decoder's kind (models/granite_hybrid_decode.py) with other rows:

  k, v      (attention layers, n_blocks, bs, kvh * hd)  the block pool, for
            the one-in-four attention layers only; tables are host state as
            ever. Heads and head size share the minor axis (two KV heads on
            a second-minor axis would be padded to a whole tile on a TPU)
  conv      (linear layers, taps - 1, lanes, conv_dim)  each lane's conv tail:
            the last taps - 1 inputs of the depthwise conv over [q | k | v],
            activation type
  state     (linear layers, lanes, H, K, V) float32     each lane's delta-rule
            state, a (key, value) matrix a value head, stepped in place in
            the stack: on a TPU by the second body of ops/ssm_update.py,
            which is handed the whole stack, the layer's index and the live
            lanes; no layer is sliced out or written back
  counts    (3,) int32  DEVICE_COUNTERS, summed over the dispatch's decode
            steps and expert layers, of HELD experts only
  pos, remaining, rng                                   per-lane scalars

Admission computes a row's conv tail and final state from zero (the chunked
form of the rule, `qwen3_next.gdn_chunked`) and writes them to the row's
lane (a padded admission row writes nothing); the decode step updates the
lanes that are active (the one-position form) and leaves the others bit for
bit alone; release needs no device work, the next admission overwrites the
row. Padding is not harmless in a recurrence: past a row's length g = 0 and
beta = 0 (the state stands), the conv tail is taken from the last real
positions, and the head is applied at the last real position only.

A lane's state at a block boundary is not kept, so nothing here can resume a
sequence from blocks alone (serve/llm_engine.py refuses what needs that when
`state_bytes_per_lane` is not 0: prefix reuse, speculation, migration).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import afmoe
from ray_tpu.models import paged
from ray_tpu.models import qwen3_next as M
from ray_tpu.models.afmoe_decode import DEVICE_COUNTERS  # noqa: F401  (the engine reads it here: paged.py)
from ray_tpu.models.granite_hybrid import live_rows
from ray_tpu.models.qwen3_next import FULL, LINEAR, Qwen3NextConfig


def init_paged_cache(cfg: Qwen3NextConfig, n_slots: int, n_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    pool = (cfg.n_full_layers, n_blocks, block_size, cfg.n_kv_heads * cfg.head_dim)
    return {
        "k": jnp.zeros(pool, cfg.dtype),
        "v": jnp.zeros(pool, cfg.dtype),
        "conv": jnp.zeros((cfg.n_linear_layers, cfg.lin_conv - 1, n_slots, cfg.conv_dim),
                          cfg.dtype),
        "state": jnp.zeros((cfg.n_linear_layers, n_slots, cfg.lin_v_heads, cfg.lin_k_dim,
                            cfg.lin_v_dim), jnp.float32),
        "counts": jnp.zeros((len(DEVICE_COUNTERS),), jnp.int32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
        "rng": jnp.zeros((n_slots, 2), jnp.uint32),
    }


def state_bytes_per_lane(cfg: Qwen3NextConfig) -> int:
    """Bytes of recurrent state a lane holds beside its K/V blocks: the conv
    tail and the float32 matrix state of every linear layer."""
    conv = (cfg.lin_conv - 1) * cfg.conv_dim * jnp.dtype(cfg.dtype).itemsize
    state = cfg.lin_v_heads * cfg.lin_k_dim * cfg.lin_v_dim * 4
    return cfg.n_linear_layers * (conv + state)


def admit_slots_paged(params, prompts, lengths, starts, slots, rems, seeds,
                      cache, feed, tables, temps, top_ks, top_ps, stop_ids,
                      cfg: Qwen3NextConfig, sampled: bool = True):
    """Fused paged admission of A right-padded prompts (A, P), with
    llama_decode.admit_slots_paged's arguments and returns. `starts` is all
    zeros here: without the state at a block boundary no prefix is reused."""
    A, P = prompts.shape
    adm_tables = tables[slots]
    valid = lengths > 0
    cos, sin = M.rope_tables(cfg, P)
    # the rows that are a prompt's: a padded row chooses no expert
    real = (jnp.arange(P)[None, :] < lengths[:, None]).reshape(-1)

    def linear_mixer(layer, li, a, carry):
        k_full, v_full, conv, state = carry
        out, tail, S = M.linear_sequence(layer, a, lengths, cfg)
        conv = paged.write_lane_rows(conv, li, tail, slots, valid, lane_axis=2)
        state = paged.write_lane_rows(state, li, S, slots, valid)
        return out, (k_full, v_full, conv, state)

    def full_mixer(layer, fi, a, carry):
        k_full, v_full, conv, state = carry
        with jax.named_scope(M.SCOPE_ATTN):
            q, k, v, gate = M.qkvg(layer, a, cos, sin, None, cfg)
            k_full, v_full = paged.write_admission_kv(
                k_full, v_full, fi, k.reshape(A, P, -1), v.reshape(A, P, -1),
                adm_tables, starts, valid)
            out = afmoe.gated_out(afmoe.sequence_attention(q, k, v, cfg, None), gate, layer, cfg)
        return out, (k_full, v_full, conv, state)

    x, (k_full, v_full, conv, state) = M.run_layers(
        params, M.embed_tokens(params, prompts, cfg),
        (cache["k"], cache["v"], cache["conv"], cache["state"]), cfg,
        {LINEAR: linear_mixer, FULL: full_mixer},
        lambda p, m, carry: (afmoe.moe_ffn(m, p, cfg, live=real)[0], carry))
    # the head at each row's last real position only
    x_last = jnp.take_along_axis(
        x, (jnp.maximum(lengths, 1) - 1)[:, None, None], axis=1)[:, 0, :]
    first, pos, rem, feed, rng = paged.finish_admission(
        M.logits_of(params, x_last, cfg), cache, feed, valid, lengths, starts,
        slots, rems, seeds, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"k": k_full, "v": v_full, "conv": conv, "state": state, "counts": cache["counts"],
             "pos": pos, "remaining": rem, "rng": rng}
    return first, cache, feed


def decode_step_slots_paged(params, cache, tokens, tables, temps, top_ks,
                            top_ps, stop_ids, cfg: Qwen3NextConfig,
                            sampled: bool = True):
    """One token on every lane, with llama_decode.decode_step_slots_paged's
    arguments and returns. An inactive lane (remaining == 0) keeps its conv
    tail and state as they are, aims its K/V write at the null block,
    chooses no expert, and its logits mean nothing."""
    B = tokens.shape[0]
    pos = cache["pos"]
    active = cache["remaining"] > 0
    live = live_rows(active)  # one list for the step's every layer
    cos, sin = M.rope_tables(cfg, tables.shape[1] * cache["k"].shape[2])

    def linear_mixer(layer, li, a, carry):
        k_full, v_full, conv, state, counts = carry
        tail = jax.lax.dynamic_index_in_dim(conv, li, 0, keepdims=False)
        out, new_tail, state = M.linear_token(layer, li, a, tail, state, live, cfg)
        with jax.named_scope(M.SCOPE_UPDATE):
            new_tail = jnp.where(active[None, :, None], new_tail, tail)
            conv = jax.lax.dynamic_update_index_in_dim(conv, new_tail, li, 0)
        return out, (k_full, v_full, conv, state, counts)

    def full_mixer(layer, fi, a, carry):
        k_full, v_full, conv, state, counts = carry
        with jax.named_scope(M.SCOPE_ATTN):
            q, k, v, gate = M.qkvg(layer, a[:, None, :], cos, sin, pos[:, None], cfg)
            k_full, v_full = paged.write_decode_kv(
                k_full, v_full, fi, k.reshape(B, 1, -1), v.reshape(B, 1, -1),
                tables, pos, active)
            out = afmoe.gated_out(
                paged.attend_decode_paged(q[:, 0], k_full, v_full, fi, tables, pos, active,
                                          cfg.head_dim ** -0.5),
                gate[:, 0], layer, cfg)
        return out, (k_full, v_full, conv, state, counts)

    def experts(p, m, carry):
        out, sizes = afmoe.moe_ffn(m, p, cfg, live=active)
        seen = jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()]).astype(jnp.int32)
        return out, carry[:4] + (carry[4] + seen,)

    x, (k_full, v_full, conv, state, counts) = M.run_layers(
        params, M.embed_tokens(params, tokens, cfg),
        (cache["k"], cache["v"], cache["conv"], cache["state"], cache["counts"]), cfg,
        {LINEAR: linear_mixer, FULL: full_mixer}, experts)
    logits = M.logits_of(params, x, cfg)
    nxt, new_pos, remaining, rng = paged.finish_decode_step(
        logits, cache, active, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"k": k_full, "v": v_full, "conv": conv, "state": state, "counts": counts,
             "pos": new_pos, "remaining": remaining, "rng": rng}
    return logits, nxt, cache


def macro_step_slots_paged(params, cache, feed, *plan, chunk: int, cfg: Qwen3NextConfig,
                           sampled: bool = True):
    """models/paged.py's macro-step skeleton with this model's two halves, under
    the skeleton's name (a device trace finds the program by it), and
    DEVICE_COUNTERS of this dispatch alone as a fifth return."""
    cache = {**cache, "counts": jnp.zeros_like(cache["counts"])}
    toks, firsts, feed, cache = paged.macro_step_slots_paged(
        params, cache, feed, *plan, chunk=chunk, cfg=cfg, sampled=sampled,
        admit=admit_slots_paged, decode_step=decode_step_slots_paged)
    return toks, firsts, feed, cache, cache["counts"] + 0


@functools.lru_cache(maxsize=16)
def jitted_macro_step_slots_paged(cfg: Qwen3NextConfig, chunk: int, sampled: bool = True):
    return jax.jit(
        paged._bind(macro_step_slots_paged, chunk=chunk, cfg=cfg, sampled=sampled),
        donate_argnums=(1,),
    )


# ------------------------------------------------------- static generation
def _generate(params, prompt, cfg: Qwen3NextConfig, n_new: int):
    return paged.generate_through_paged_cache(
        init_paged_cache, admit_slots_paged, decode_step_slots_paged, params, prompt, cfg, n_new)


@functools.lru_cache(maxsize=64)
def _jitted_generate(cfg: Qwen3NextConfig, n_new: int):
    return jax.jit(paged._bind(_generate, cfg=cfg, n_new=n_new))


def generate(params, prompt, cfg: Qwen3NextConfig, max_new_tokens: int):
    """Greedy static generation: prompt (R, T) int32 -> (R, max_new_tokens)
    int32, one device program."""
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.shape[1] == 0:
        raise ValueError("generate() requires a non-empty prompt")
    return np.asarray(_jitted_generate(cfg, max_new_tokens)(params, prompt))
