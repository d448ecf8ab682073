"""The latent-attention decoder on the paged serving path: a pool of latent
rows, an expanded admission and an absorbed decode step, an expert layer that
holds a part of its experts.

The macro-step is models/paged.macro_step_slots_paged, handed this
module's admission and decode step and this module's cache pytree:

  latent    (layers, n_blocks, bs, ROW)  the block pool: ONE row a position
            and layer, [c (kv_lora_rank) | RoPE(k_r) (qk_rope_head_dim) | 0s],
            where another model's pool has a K and a V row of every KV head.
            Tables, allocator and planner are every model's: a block is
            `bs` positions whatever a row holds. The row is padded with
            zeros to whole 128-column tiles (`pool_row`): 576 columns lie
            in 640 on a TPU whatever the program says, the zero tail costs
            the scores nothing (the query's tail is zero too), and the
            values are the row's first `kv_lora_rank` columns, a slice at a
            tile's edge
  counts    (3,) int32  DEVICE_COUNTERS, summed over the dispatch's decode
            steps and expert layers, of HELD experts only
  pos, remaining, rng   per-lane scalars

A lane holds nothing beside its blocks (`state_bytes_per_lane` 0). But what
copies, ships or re-reads blocks elsewhere in the program (`copy_kv_blocks`,
the KV plane's gather / import / scatter, the admission's prefix loop, the
speculative programs) is written for a K and a V pool: LATENT_POOL makes the
engine refuse those options by name until they know this pool.

The admission EXPANDS (every head's keys and values from c, a causal
attention over the prompt through ops/flash_attention) and writes the rows;
the decode step ABSORBS (W_uk into the query, W_uv onto the output) and
attends the pool's rows themselves in `attend_decode_paged`'s single-pool
form: one "KV head" whose key is the row and whose value is the same row's
first columns (`decode_mixer`: on a TPU the kernel of
ops/paged_decode_attention.py, elsewhere the definition's loop, a chunk
gathered once).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import afmoe
from ray_tpu.models import paged
from ray_tpu.models import sarvam_mla as M
from ray_tpu.models.afmoe_decode import DEVICE_COUNTERS  # noqa: F401  (the engine reads it here: paged.py)
from ray_tpu.models.sarvam_mla import SarvamMlaConfig

# the pool holds latent rows, not keys and values: serve/llm_engine.py refuses
# what needs a K and a V pool
LATENT_POOL = True


def pool_row(cfg: SarvamMlaConfig) -> int:
    """Columns of a pool row: the latent row in whole 128-column tiles."""
    return -(-cfg.latent_row // 128) * 128


def init_paged_cache(cfg: SarvamMlaConfig, n_slots: int, n_blocks: int,
                     block_size: int) -> Dict[str, Any]:
    return {
        "latent": jnp.zeros((cfg.n_layers, n_blocks, block_size, pool_row(cfg)), cfg.dtype),
        "counts": jnp.zeros((len(DEVICE_COUNTERS),), jnp.int32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "remaining": jnp.zeros((n_slots,), jnp.int32),
        "rng": jnp.zeros((n_slots, 2), jnp.uint32),
    }


def state_bytes_per_lane(cfg: SarvamMlaConfig) -> int:
    """A lane's whole state is its block table."""
    return 0


def _padded(row, cfg: SarvamMlaConfig):
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pool_row(cfg) - cfg.latent_row)])


def admit_mixer(layer, plane, a, pool, cos, sin, adm_tables, starts, valid, cfg):
    """The attention half of an admission: whole rows a (A, P, d) the expanded
    way, their cache rows written to plane `plane` of the pool."""
    out, rows = M.sequence_mixer(layer, a, cos, sin, cfg)
    with jax.named_scope(M.SCOPE_CTX):
        pool, _ = paged.write_admission_kv(pool, None, plane, _padded(rows, cfg), None,
                                           adm_tables, starts, valid)
    return out, pool


def decode_mixer(layer, plane, a, pool, cos, sin, tables, pos, active, cfg):
    """The attention half of a decode step: one position a (B, d) a lane the
    absorbed way, over plane `plane` of the pool, read where it lies: on a TPU,
    for a pool its tiles take, the kernel of ops/paged_decode_attention.py
    (each lane for its own blocks, ONE DMA a block, the value sliced from the
    key's buffer); elsewhere the definition, which gathers every lane's chunks
    up to the longest live lane's."""
    from ray_tpu.ops import paged_decode_attention as kernel  # Pallas: imported where it is traced

    B, r = a.shape[0], cfg.kv_lora_rank
    with jax.named_scope(M.SCOPE_PROJ):
        q_nope, q_rope, row = M.project(layer, a[:, None, :], cos, sin, pos[:, None], cfg)
        q = _padded(jnp.concatenate([M.absorb_q(layer, q_nope[:, 0]), q_rope[:, 0]], axis=-1), cfg)
    with jax.named_scope(M.SCOPE_CTX):
        pool, _ = paged.write_decode_kv(pool, None, plane, _padded(row, cfg), None, tables, pos, active)
        read = kernel.attend if kernel.engages(q, pool, None, r) else paged.attend_decode_paged
        o_lat = read(q, pool, None, plane, tables, pos, active, cfg.sm_scale, v_cols=r)
    with jax.named_scope(M.SCOPE_PROJ):
        out = M.absorbed_out(layer, o_lat.reshape(B, cfg.n_heads, r), cfg) @ layer["wo"]
    return out, pool


def admit_slots_paged(params, prompts, lengths, starts, slots, rems, seeds,
                      cache, feed, tables, temps, top_ks, top_ps, stop_ids,
                      cfg: SarvamMlaConfig, sampled: bool = True):
    """Fused paged admission of A right-padded prompts (A, P), with
    llama_decode.admit_slots_paged's arguments and returns. `starts` is all
    zeros here: no prefix is reused over latent blocks yet."""
    A, P = prompts.shape
    adm_tables = tables[slots]
    valid = lengths > 0
    cos, sin = M.rope_tables(cfg, P)
    # the rows that are a prompt's: a padded row chooses no expert (what it
    # leaves is read by nothing: attention is causal, the head reads each
    # row's last real position, the pool's padded positions are masked)
    real = (jnp.arange(P)[None, :] < lengths[:, None]).reshape(-1)

    def mixer(layer, li, a, pool):
        return admit_mixer(layer, li, a, pool, cos, sin, adm_tables, starts, valid, cfg)

    x, pool = M.run_layers(
        params, M.embed_tokens(params, prompts, cfg), cache["latent"], cfg, mixer,
        lambda p, m, carry: (afmoe.moe_ffn(m, p, cfg, live=real)[0], carry))
    # the head at each row's last real position only
    x_last = jnp.take_along_axis(
        x, (jnp.maximum(lengths, 1) - 1)[:, None, None], axis=1)[:, 0, :]
    first, pos, rem, feed, rng = paged.finish_admission(
        afmoe.logits_of(params, x_last, cfg), cache, feed, valid, lengths, starts,
        slots, rems, seeds, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"latent": pool, "counts": cache["counts"], "pos": pos, "remaining": rem, "rng": rng}
    return first, cache, feed


def decode_step_slots_paged(params, cache, tokens, tables, temps, top_ks,
                            top_ps, stop_ids, cfg: SarvamMlaConfig,
                            sampled: bool = True):
    """One token on every lane, with llama_decode.decode_step_slots_paged's
    arguments and returns; attention the absorbed way, the expert layers over
    the live lanes' rows only."""
    pos = cache["pos"]
    active = cache["remaining"] > 0
    cos, sin = M.rope_tables(cfg, tables.shape[1] * cache["latent"].shape[2])

    def mixer(layer, li, a, carry):
        out, pool = decode_mixer(layer, li, a, carry[0], cos, sin, tables, pos, active, cfg)
        return out, (pool, carry[1])

    def experts(p, m, carry):
        out, sizes = afmoe.moe_ffn(m, p, cfg, live=active)
        seen = jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()]).astype(jnp.int32)
        return out, (carry[0], carry[1] + seen)

    x, (pool, counts) = M.run_layers(
        params, M.embed_tokens(params, tokens, cfg), (cache["latent"], cache["counts"]), cfg,
        mixer, experts)
    logits = afmoe.logits_of(params, x, cfg)
    nxt, new_pos, remaining, rng = paged.finish_decode_step(
        logits, cache, active, temps, top_ks, top_ps, stop_ids, sampled)
    cache = {"latent": pool, "counts": counts, "pos": new_pos, "remaining": remaining, "rng": rng}
    return logits, nxt, cache


def macro_step_slots_paged(params, cache, feed, *plan, chunk: int, cfg: SarvamMlaConfig,
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
def jitted_macro_step_slots_paged(cfg: SarvamMlaConfig, chunk: int, sampled: bool = True):
    return jax.jit(
        paged._bind(macro_step_slots_paged, chunk=chunk, cfg=cfg, sampled=sampled),
        donate_argnums=(1,),
    )


# ------------------------------------------------------- static generation
def _generate(params, prompt, cfg: SarvamMlaConfig, n_new: int):
    return paged.generate_through_paged_cache(
        init_paged_cache, admit_slots_paged, decode_step_slots_paged, params, prompt, cfg, n_new)


@functools.lru_cache(maxsize=64)
def _jitted_generate(cfg: SarvamMlaConfig, n_new: int):
    return jax.jit(paged._bind(_generate, cfg=cfg, n_new=n_new))


def generate(params, prompt, cfg: SarvamMlaConfig, max_new_tokens: int):
    """Greedy static generation: prompt (R, T) int32 -> (R, max_new_tokens)
    int32, one device program."""
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.shape[1] == 0:
        raise ValueError("generate() requires a non-empty prompt")
    return np.asarray(_jitted_generate(cfg, max_new_tokens)(params, prompt))
