"""Blockwise (memory-efficient) attention in pure XLA.

The O(T) -memory attention formulation (online softmax over KV blocks,
lax.scan) that underlies both the pallas flash kernel and ring attention.
Nothing equivalent exists in the reference — long-context is absent there
(SURVEY.md §5 "Long-context: not present") — so this is green-field,
built TPU-first: static shapes, scan instead of Python loops, MXU-sized
blocks, fp32 accumulation around bf16 matmuls.

Layout convention: [batch, seq, heads, head_dim] (q may have more heads
than k/v for GQA; kv heads are broadcast).

A custom VJP implements the flash-style backward (one extra pass over KV
blocks, recomputing P from the saved logsumexp) so the backward is also
O(T) memory.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _broadcast_kv(k, num_q_heads):
    """GQA: repeat kv heads to match q heads."""
    kvh = k.shape[2]
    if kvh == num_q_heads:
        return k
    assert num_q_heads % kvh == 0
    return jnp.repeat(k, num_q_heads // kvh, axis=2)


def _mask_bias(q_len, kv_len, q_offset, kv_offset, causal, dtype):
    if not causal:
        return None
    q_ids = q_offset + jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 0)
    kv_ids = kv_offset + jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 1)
    return jnp.where(kv_ids <= q_ids, 0.0, NEG_INF).astype(dtype)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def blockwise_attention(
    q,
    k,
    v,
    causal: bool = True,
    block_size: int = 512,
    sm_scale: Optional[float] = None,
    q_offset: int = 0,
    kv_offset: int = 0,
):
    """Attention with O(block) memory. Shapes [B, T, H, D] / [B, S, Hkv, D].

    q_offset/kv_offset shift the causal mask — the hook ring attention
    uses to mask remote KV blocks by their global position.
    """
    o, _ = _fwd_impl(q, k, v, causal, block_size, sm_scale, q_offset, kv_offset)
    return o


def _fwd_impl(q, k, v, causal, block_size, sm_scale, q_offset, kv_offset, window=None):
    """`window` (forward only, with `causal`): query i attends key j with
    0 <= i - j < window."""
    B, T, H, D = q.shape
    S = k.shape[1]
    scale = sm_scale if sm_scale is not None else D ** -0.5
    k = _broadcast_kv(k, H)
    v = _broadcast_kv(v, H)
    blk = min(block_size, S)
    nblocks = (S + blk - 1) // blk
    pad = nblocks * blk - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblocks, blk, H, D).transpose(1, 0, 2, 3, 4)
    Dv = v.shape[3]  # the forward takes a value size of its own
    vb = v.reshape(B, nblocks, blk, H, Dv).transpose(1, 0, 2, 3, 4)

    qf = q.astype(jnp.float32) * scale

    def step(carry, inputs):
        acc, m, l = carry
        jblk, kj, vj = inputs
        # scores: [B, T, H, blk]
        s = jnp.einsum("bthd,bshd->bths", qf, kj.astype(jnp.float32))
        base = jblk * blk
        if causal:
            q_ids = q_offset + jax.lax.broadcasted_iota(jnp.int32, (T, blk), 0)
            kv_ids = kv_offset + base + jax.lax.broadcasted_iota(jnp.int32, (T, blk), 1)
            seen = kv_ids <= q_ids
            if window is not None:
                seen = seen & (kv_ids > q_ids - window)
            bias = jnp.where(seen, 0.0, NEG_INF)
            s = s + bias[None, :, None, :]
        if pad:
            kv_ids2 = base + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
            s = s + jnp.where(kv_ids2 < S, 0.0, NEG_INF)[:, None, :]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bths,bshd->bthd", p, vj.astype(jnp.float32)
        )
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, T, H, Dv), jnp.float32)
    m0 = jnp.full((B, T, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, T, H), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (jnp.arange(nblocks), kb, vb)
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)  # logsumexp of scaled scores
    return o, lse


def _fwd(q, k, v, causal, block_size, sm_scale, q_offset, kv_offset):
    o, lse = _fwd_impl(q, k, v, causal, block_size, sm_scale, q_offset, kv_offset)
    return o, (q, k, v, o, lse)


def _bwd(causal, block_size, sm_scale, q_offset, kv_offset, res, do):
    q, k, v, o, lse = res
    B, T, H, D = q.shape
    S = k.shape[1]
    kvh = k.shape[2]
    scale = sm_scale if sm_scale is not None else D ** -0.5
    kfull = _broadcast_kv(k, H)
    vfull = _broadcast_kv(v, H)
    blk = min(block_size, S)
    nblocks = (S + blk - 1) // blk
    pad = nblocks * blk - S
    if pad:
        kfull = jnp.pad(kfull, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vfull = jnp.pad(vfull, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = kfull.reshape(B, nblocks, blk, H, D).transpose(1, 0, 2, 3, 4)
    vb = vfull.reshape(B, nblocks, blk, H, D).transpose(1, 0, 2, 3, 4)

    # MATMUL inputs stay in the model dtype (bf16): the MXU multiplies
    # bf16 at full rate with f32 accumulation (preferred_element_type);
    # upcasting inputs first forces f32xf32 multiplies at ~1/4 throughput
    # — measured as the long-context backward running at <15% MFU.
    # Softmax/correction arithmetic stays in f32.
    in_dtype = q.dtype
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(axis=-1)  # [B,T,H]
    pref = dict(preferred_element_type=jnp.float32)

    def step(dq, inputs):
        jblk, kj, vj = inputs
        s = jnp.einsum("bthd,bshd->bths", q, kj, **pref) * scale
        base = jblk * blk
        if causal:
            q_ids = q_offset + jax.lax.broadcasted_iota(jnp.int32, (T, blk), 0)
            kv_ids = kv_offset + base + jax.lax.broadcasted_iota(jnp.int32, (T, blk), 1)
            s = s + jnp.where(kv_ids <= q_ids, 0.0, NEG_INF)[None, :, None, :]
        if pad:
            kv_ids2 = base + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
            s = s + jnp.where(kv_ids2 < S, 0.0, NEG_INF)[:, None, :]
        p = jnp.exp(s - lse[..., None])  # [B,T,H,blk] f32
        pl_ = p.astype(in_dtype)
        dv_j = jnp.einsum("bths,bthd->bshd", pl_, do, **pref)
        dp = jnp.einsum("bthd,bshd->bths", do, vj, **pref)
        ds = (p * (dp - delta[..., None])).astype(in_dtype)
        dq = dq + jnp.einsum("bths,bshd->bthd", ds, kj, **pref)
        dk_j = jnp.einsum("bths,bthd->bshd", ds, q, **pref)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((B, T, H, D), jnp.float32)
    dq, (dkb, dvb) = jax.lax.scan(step, dq0, (jnp.arange(nblocks), kb, vb))
    dq = (dq * scale).astype(q.dtype)
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(B, nblocks * blk, H, D)[:, :S]
    dv = dvb.transpose(1, 0, 2, 3, 4).reshape(B, nblocks * blk, H, D)[:, :S]
    # dk_j was computed against RAW q (bf16 matmul path), so it needs the
    # same scale factor dq does
    dk = (dk * scale).astype(k.dtype)
    dv = dv.astype(v.dtype)
    if kvh != H:
        g = H // kvh
        dk = dk.reshape(B, S, kvh, g, D).sum(axis=3)
        dv = dv.reshape(B, S, kvh, g, D).sum(axis=3)
    return dq, dk, dv


blockwise_attention.defvjp(_fwd, _bwd)


def reference_attention(q, k, v, causal=True, sm_scale=None):
    """O(T^2) reference for tests."""
    B, T, H, D = q.shape
    S = k.shape[1]
    scale = sm_scale if sm_scale is not None else D ** -0.5
    k = _broadcast_kv(k, H)
    v = _broadcast_kv(v, H)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32)).astype(q.dtype)
