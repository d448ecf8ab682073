"""The hybrid decoder on the paged serving path: a lane holds recurrent
state beside its K/V blocks.

The macro-step is models/paged.macro_step_slots_paged, handed this
module's admission and decode step and this module's cache pytree:

  k, v      (attention layers, n_blocks, bs, kvh * hd)  the block pool, for
            the few attention layers only; tables are host state as ever.
            Heads and head size share the minor axis: a minor axis of 64
            would be padded to 128 on a TPU, twice the pool
  conv      (Mamba layers, taps - 1, lanes, conv_dim)  each lane's conv tail:
            the last taps - 1 inputs of the depthwise conv, activation type
            (lanes on the second-minor axis, not the 3 taps: the same padding)
  ssm       (Mamba layers, lanes, H, P, N) float32     each lane's SSM state,
            stepped in place in the stack: on a TPU by the kernel of
            ops/ssm_update.py, which is handed the whole stack, the layer's
            index and the live lanes; no layer is sliced out or written back
  pos, remaining, rng                                   per-lane scalars

Admission computes a row's conv tail and final state from zero and writes
them to the row's lane (a padded admission row writes nothing); the decode
step updates the lanes that are active and leaves the others bit for bit
alone (the kernel makes no pass over an inactive lane's state; off the TPU
`ssm_step` runs on every lane and a select keeps the inactive ones);
release needs no device work, the next admission overwrites the row.

Padding is not harmless in a recurrence: past a row's length the step size
is zeroed (decay 1, input 0), the conv tail is taken from the last real
positions, and the head is applied at the last real position only.

A lane's state at a block boundary is not kept, so nothing here can resume a
sequence from blocks alone: there is no gather / import / scatter of blocks
and no speculative round (serve/llm_engine.py refuses what needs them when
`state_bytes_per_lane` is not 0).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import granite_hybrid as G
from ray_tpu.models import paged
from ray_tpu.models.granite_hybrid import ATTENTION, MAMBA, GraniteHybridConfig


def init_paged_cache(cfg: GraniteHybridConfig, n_slots: int, n_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    pool = (cfg.n_attn_layers, n_blocks, block_size, cfg.n_kv_heads * cfg.head_dim)
    return {
        "k": jnp.zeros(pool, cfg.dtype),
        "v": jnp.zeros(pool, cfg.dtype),
        "conv": jnp.zeros((cfg.n_mamba_layers, cfg.mamba_d_conv - 1, n_slots, cfg.conv_dim),
                          cfg.dtype),
        "ssm": jnp.zeros((cfg.n_mamba_layers, n_slots, cfg.mamba_n_heads, cfg.mamba_d_head,
                          cfg.mamba_d_state), jnp.float32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
        "rng": jnp.zeros((n_slots, 2), jnp.uint32),
    }


def state_bytes_per_lane(cfg: GraniteHybridConfig) -> int:
    """Bytes of recurrent state a lane holds beside its K/V blocks: the conv
    tail and the float32 SSM state of every Mamba layer."""
    conv = (cfg.mamba_d_conv - 1) * cfg.conv_dim * jnp.dtype(cfg.dtype).itemsize
    ssm = cfg.mamba_n_heads * cfg.mamba_d_head * cfg.mamba_d_state * 4
    return cfg.n_mamba_layers * (conv + ssm)


def admit_slots_paged(params, prompts, lengths, starts, slots, rems, seeds,
                      cache, feed, tables, temps, top_ks, top_ps, stop_ids,
                      cfg: GraniteHybridConfig, sampled: bool = True):
    """Fused paged admission of A right-padded prompts (A, P), with
    llama_decode.admit_slots_paged's arguments and returns. `starts` is all
    zeros here: without the state at a block boundary no prefix is reused."""
    A, P = prompts.shape
    adm_tables = tables[slots]
    valid = lengths > 0

    def mamba_mixer(layer, mi, a, carry):
        k_full, v_full, conv, ssm = carry
        out, tail, h = G.mamba_sequence(layer, a, lengths, cfg)
        conv = paged.write_lane_rows(conv, mi, tail, slots, valid, lane_axis=2)
        ssm = paged.write_lane_rows(ssm, mi, h, slots, valid)
        return out, (k_full, v_full, conv, ssm)

    def attn_mixer(layer, ai, a, carry):
        k_full, v_full, conv, ssm = carry
        with jax.named_scope(G.SCOPE_ATTN):
            q, k, v = G.qkv(layer, a, cfg)
            k_full, v_full = paged.write_admission_kv(
                k_full, v_full, ai, k.reshape(A, P, -1), v.reshape(A, P, -1),
                adm_tables, starts, valid)
            out = G.causal_attention(q, k, v, cfg) @ layer["wo"]
        return out, (k_full, v_full, conv, ssm)

    x, (k_full, v_full, conv, ssm) = G.run_layers(
        params, G.embed_tokens(params, prompts, cfg),
        (cache["k"], cache["v"], cache["conv"], cache["ssm"]), cfg,
        {MAMBA: mamba_mixer, ATTENTION: attn_mixer})
    # the head at each row's last real position only: all P positions in
    # float32 over this vocabulary would be gigabytes
    x_last = jnp.take_along_axis(
        x, (jnp.maximum(lengths, 1) - 1)[:, None, None], axis=1)[:, 0, :]
    first, pos, rem, feed, rng = paged.finish_admission(
        G.logits_of(params, x_last, cfg), cache, feed, valid, lengths, starts,
        slots, rems, seeds, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"k": k_full, "v": v_full, "conv": conv, "ssm": ssm,
             "pos": pos, "remaining": rem, "rng": rng}
    return first, cache, feed


def decode_step_slots_paged(params, cache, tokens, tables, temps, top_ks,
                            top_ps, stop_ids, cfg: GraniteHybridConfig,
                            sampled: bool = True):
    """One token on every lane, with llama_decode.decode_step_slots_paged's
    arguments and returns. An inactive lane (remaining == 0) keeps its conv
    tail and state as they are, aims its K/V write at the null block, and
    its logits mean nothing (its mixer output is not computed on a TPU). The
    attention layers read the lanes' contexts out of the flat pool in place,
    through paged.attend_decode_paged: work follows the longest live
    lane, not the table span."""
    B = tokens.shape[0]
    pos = cache["pos"]
    active = cache["remaining"] > 0
    live = G.live_rows(active)  # one list for the step's every layer

    def mamba_mixer(layer, mi, a, carry):
        k_full, v_full, conv, ssm = carry
        tail = jax.lax.dynamic_index_in_dim(conv, mi, 0, keepdims=False)
        out, new_tail, ssm = G.mamba_token(layer, mi, a, tail, ssm, live, cfg)
        with jax.named_scope(G.SCOPE_UPDATE):
            new_tail = jnp.where(active[None, :, None], new_tail, tail)
            conv = jax.lax.dynamic_update_index_in_dim(conv, new_tail, mi, 0)
        return out, (k_full, v_full, conv, ssm)

    def attn_mixer(layer, ai, a, carry):
        k_full, v_full, conv, ssm = carry
        with jax.named_scope(G.SCOPE_ATTN):
            q, k, v = G.qkv(layer, a[:, None, :], cfg)
            k_full, v_full = paged.write_decode_kv(
                k_full, v_full, ai, k.reshape(B, 1, -1), v.reshape(B, 1, -1),
                tables, pos, active)
            out = paged.attend_decode_paged(
                q[:, 0], k_full, v_full, ai, tables, pos, active,
                cfg.attention_multiplier) @ layer["wo"]
        return out, (k_full, v_full, conv, ssm)

    x, (k_full, v_full, conv, ssm) = G.run_layers(
        params, G.embed_tokens(params, tokens, cfg),
        (cache["k"], cache["v"], cache["conv"], cache["ssm"]), cfg,
        {MAMBA: mamba_mixer, ATTENTION: attn_mixer})
    logits = G.logits_of(params, x, cfg)
    nxt, new_pos, remaining, rng = paged.finish_decode_step(
        logits, cache, active, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"k": k_full, "v": v_full, "conv": conv, "ssm": ssm,
             "pos": new_pos, "remaining": remaining, "rng": rng}
    return logits, nxt, cache


@functools.lru_cache(maxsize=16)
def jitted_macro_step_slots_paged(cfg: GraniteHybridConfig, chunk: int,
                                  sampled: bool = True):
    """models/paged.py's macro-step skeleton with this model's two halves;
    the program keeps the skeleton's name."""
    return jax.jit(
        paged._bind(paged.macro_step_slots_paged, chunk=chunk, cfg=cfg, sampled=sampled,
                    admit=admit_slots_paged, decode_step=decode_step_slots_paged),
        donate_argnums=(1,),
    )


# ------------------------------------------------------- static generation
def _generate(params, prompt, cfg: GraniteHybridConfig, n_new: int):
    return paged.generate_through_paged_cache(
        init_paged_cache, admit_slots_paged, decode_step_slots_paged, params, prompt, cfg, n_new)


@functools.lru_cache(maxsize=64)
def _jitted_generate(cfg: GraniteHybridConfig, n_new: int):
    return jax.jit(paged._bind(_generate, cfg=cfg, n_new=n_new))


def generate(params, prompt, cfg: GraniteHybridConfig, max_new_tokens: int):
    """Greedy static generation: prompt (R, T) int32 -> (R, max_new_tokens)
    int32, one device program."""
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.shape[1] == 0:
        raise ValueError("generate() requires a non-empty prompt")
    return np.asarray(_jitted_generate(cfg, max_new_tokens)(params, prompt))
