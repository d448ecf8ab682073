"""Flash attention — pallas TPU kernel.

The hot attention path: online-softmax over KV blocks entirely in VMEM,
MXU-shaped (128-aligned) tiles, fp32 accumulators around bf16 matmuls.
Forward is the pallas kernel below; backward reuses the O(T)-memory
blockwise XLA backward (ray_tpu/ops/blockwise_attention.py) — XLA already
fuses that well, and it keeps one source of truth for gradients.

Nothing to port from the reference (attention kernels are absent there;
GPU deployments rely on external flash-attn inside train workers). Kernel
structure follows the public flash-attention-on-pallas pattern
(jax-ml pallas ops; guide: /opt/skills/guides/pallas_guide.md).

Layout: [batch, seq, heads, head_dim]; GQA via kv-head broadcast.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.blockwise_attention import _broadcast_kv, _bwd as _blockwise_bwd, _fwd_impl

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, bq, bk, nk, window=None, shared=False):
    # with `shared`, two more operands: a second part of every query head's
    # vector and ONE second key part for all heads (its index map ignores the
    # head); the score is the sum of the two products
    q2_ref, k2_ref = rest[:2] if shared else (None, None)
    o_ref, lse_ref, acc, m_s, l_s = rest[2:] if shared else rest
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    # Skip fully-masked kv blocks (strictly above the causal diagonal).
    run = True
    if causal:
        run = j * bk <= i * bq + bq - 1
    if window is not None:
        # ... and those wholly behind the window of the block's first row
        run = jnp.logical_and(run, j * bk + bk - 1 > i * bq - window)

    @pl.when(run)
    def _():
        # keep the MATMUL INPUTS in their native (bf16) dtype: the MXU
        # multiplies bf16 at full rate with f32 accumulation
        # (preferred_element_type) — upcasting inputs to f32 first forces
        # f32xf32 multiplies at ~1/4 throughput, which measured as the
        # whole kernel running at 5% MFU. Softmax stays in f32.
        q = q_ref[0]                                       # [bq, D] bf16
        k = k_ref[0]                                       # [bk, D] bf16
        v = v_ref[0]                                       # [bk, D] bf16
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if shared:
            s = s + jax.lax.dot_general(
                q2_ref[0], k2_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        s = s * scale                                      # [bq, bk] f32
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
            if window is not None:  # row i attends j with 0 <= i - j < window
                s = jnp.where(cols > rows - window, s, NEG_INF)
        m_prev = m_s[:]                                    # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                             # [bq, bk] f32
        corr = jnp.exp(m_prev - m_new)                     # [bq, 1]
        l_s[:] = l_s[:] * corr + p.sum(axis=-1, keepdims=True)
        m_s[:] = m_new
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _():
        l_safe = jnp.where(l_s[:] == 0.0, 1.0, l_s[:])
        o_ref[0] = (acc[:] / l_safe).astype(o_ref.dtype)
        # lse rides as a compact (1, bq) lane vector — the [bq, 1] sublane
        # column transposed into lanes (vs a 128-lane broadcast tile,
        # which costs 128x the HBM traffic for the same data). The output
        # is (BH, nq, 1, bq) so the block equals the trailing array dims
        # (TPU lowering requires (8,128)-divisible or dim-equal blocks).
        lse_ref[0, 0] = jnp.transpose(m_s[:] + jnp.log(l_safe), (1, 0))


def _flash_fwd_pallas(q, k, v, causal, sm_scale, block_q, block_k, interpret, window=None,
                      q_shared=None, k_shared=None):
    B, T, H, D = q.shape
    S = k.shape[1]
    Dv = v.shape[3]  # the value size may differ from the query / key size
    scale = sm_scale if sm_scale is not None else D ** -0.5
    k = _broadcast_kv(k, H)
    v = _broadcast_kv(v, H)
    bq = min(block_q, T)
    bk = min(block_k, S)
    assert T % bq == 0 and S % bk == 0, "seq lengths must divide block sizes"
    nq, nk = T // bq, S // bk

    qr = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, S, Dv)
    operands = [qr, kr, vr]
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, j, 0)),
    ]
    shared = q_shared is not None
    if shared:  # q_shared [B, T, H, D2]; k_shared [B, S, D2], never copied a head
        D2 = q_shared.shape[3]
        operands += [q_shared.transpose(0, 2, 1, 3).reshape(B * H, T, D2), k_shared]
        in_specs += [
            pl.BlockSpec((1, bq, D2), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D2), lambda b, i, j: (b // H, j, 0)),
        ]

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk, window=window,
        shared=shared,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, nq, 1, bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*operands)
    o = o.reshape(B, H, T, Dv).transpose(0, 2, 1, 3)
    lse = lse.reshape(B, H, T).transpose(0, 2, 1)  # [B, T, H] (from (BH, nq, 1, bq))
    return o, lse


def _fa_bwd_dkdv_kernel(q_ref, do_ref, lse_ref, dl_ref, k_ref, v_ref,
                        dk_ref, dv_ref, dk_acc, dv_acc,
                        *, scale, causal, bq, bk, nq):
    """dK/dV kernel: fixed KV block j (grid dim 1), iterate Q blocks i
    (innermost). P is recomputed from q/k and the saved logsumexp — no
    [T,S] materialization, everything VMEM-resident (FlashAttention-2
    backward structure)."""
    j = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = i * bq + bq - 1 >= j * bk  # q block reaches this kv block

    @pl.when(run)
    def _():
        q = q_ref[0]                                       # [bq, D] bf16
        do = do_ref[0]                                     # [bq, D] bf16
        k = k_ref[0]                                       # [bk, D] bf16
        v = v_ref[0]                                       # [bk, D] bf16
        # compact (1, bq) lane vectors -> [bq, 1] sublane columns
        lse = jnp.transpose(lse_ref[0, 0], (1, 0))         # [bq, 1] f32
        delta = jnp.transpose(dl_ref[0, 0], (1, 0))        # [bq, 1] f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                          # [bq, bk]
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        p = jnp.exp(s - lse)                               # [bq, bk] f32
        pb = p.astype(v.dtype)
        # dv += P^T @ dO
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                  # [bq, bk]
        ds = (p * (dp - delta)).astype(q.dtype)
        # dk += dS^T @ q (scale applied at writeout)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(q_ref, do_ref, lse_ref, dl_ref, k_ref, v_ref,
                      dq_ref, dq_acc, *, scale, causal, bq, bk, nk):
    """dQ kernel: fixed Q block i, iterate KV blocks j (innermost)."""
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = j * bk <= i * bq + bq - 1

    @pl.when(run)
    def _():
        q = q_ref[0]
        do = do_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        lse = jnp.transpose(lse_ref[0, 0], (1, 0))
        delta = jnp.transpose(dl_ref[0, 0], (1, 0))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta)).astype(q.dtype)
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, sm_scale, block_q, block_k):
    B, T, H, D = q.shape
    S = k.shape[1]
    kvh = k.shape[2]
    scale = sm_scale if sm_scale is not None else D ** -0.5
    kf = _broadcast_kv(k, H)
    vf = _broadcast_kv(v, H)
    bq = min(block_q, T)
    bk = min(block_k, S)
    nq, nk = T // bq, S // bk

    qr = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    dor = do.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kr = kf.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vr = vf.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    # lse arrives [B, T, H]; delta = rowsum(do * o). Both ride as compact
    # (BH, nq, 1, bq) f32 — (1, bq) lane-vector blocks transposed to
    # sublane columns inside the kernels (a 128-lane broadcast tile would
    # cost 128x the HBM traffic for the same per-row scalars)
    lse_t = lse.transpose(0, 2, 1).reshape(B * H, nq, 1, bq)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(axis=-1)  # [B,T,H]
    delta_t = delta.transpose(0, 2, 1).reshape(B * H, nq, 1, bq)

    dkdv = functools.partial(
        _fa_bwd_dkdv_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nq=nq
    )
    dk_r, dv_r = pl.pallas_call(
        dkdv,
        grid=(B * H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),    # q
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),    # do
            pl.BlockSpec((1, 1, 1, bq), lambda b, j, i: (b, i, 0, 0)),  # lse
            pl.BlockSpec((1, 1, 1, bq), lambda b, j, i: (b, i, 0, 0)),  # delta
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),    # k
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),    # v
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        name="flash_bwd_dkdv",
    )(qr, dor, lse_t, delta_t, kr, vr)

    dqk = functools.partial(
        _fa_bwd_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk
    )
    dq_r = pl.pallas_call(
        dqk,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * H, T, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        name="flash_bwd_dq",
    )(qr, dor, lse_t, delta_t, kr, vr)[0]

    dq = dq_r.reshape(B, H, T, D).transpose(0, 2, 1, 3)
    dk = dk_r.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    dv = dv_r.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    if kvh != H:
        g = H // kvh
        dk = dk.reshape(B, S, kvh, g, D).sum(axis=3)
        dv = dv.reshape(B, S, kvh, g, D).sum(axis=3)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _on_tpu() -> bool:
    # no fallback around the query: a backend that fails to come up is an
    # error to surface, not a reason to take the XLA route in silence
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    # 1024/1024 blocks measured ~9%% faster than 512/1024 at 8k on v5e
    # (fewer grid steps); bk=2048 was faster still in isolation but its
    # [1024,2048] f32 score tiles overflow VMEM headroom on bigger
    # models (1B-config remote compile failed) — 1024 keeps every
    # benched config compiling
    block_q: int = 1024,
    block_k: int = 1024,
):
    o, _ = _flash_fwd_dispatch(q, k, v, causal, sm_scale, block_q, block_k)
    return o


def _fit_block(seq: int, block: int) -> int:
    """Block size the kernel should use for this sequence: the whole
    sequence when it fits one block (seq <= block — short sequences
    always dispatched this way), else the largest power-of-two block
    <= `block` that divides `seq` (>=128). 0 = unsupported. Raising the
    defaults must not silently push shapes the old defaults handled
    (seq 3072 with the 512 block; seq 64 as a single block) off the
    kernel onto the XLA fallback."""
    if seq <= block:
        return seq
    b = block
    while b >= 128 and seq % b:
        b //= 2
    return b if b >= 128 and seq % b == 0 else 0


def kernel_supported(seq_q: int, seq_k: int, head_dim: int, block_q: int = 1024, block_k: int = 1024,
                     *more_dims: int) -> bool:
    """True iff these shapes dispatch to the pallas kernel on a TPU backend.
    head_dim 64 (validated on-chip; covers most small models) or a
    128-multiple (MXU-native), and so every one of `more_dims` (a value size
    of its own, the shared second part of a key); seq lengths must be
    divisible by SOME power-of-two block >= 128 (the dispatch shrinks blocks
    to fit)."""
    return (
        _fit_block(seq_q, block_q) > 0
        and _fit_block(seq_k, block_k) > 0
        and all(d == 64 or d % 128 == 0 for d in (head_dim,) + more_dims)
    )


def _flash_fwd_dispatch(q, k, v, causal, sm_scale, block_q, block_k, window=None,
                        q_shared=None, k_shared=None):
    T, S = q.shape[1], k.shape[1]
    more = (v.shape[3],) + (() if q_shared is None else (q_shared.shape[3],))
    if _on_tpu() and kernel_supported(T, S, q.shape[3], block_q, block_k, *more):
        return _flash_fwd_pallas(
            q, k, v, causal, sm_scale, _fit_block(T, block_q), _fit_block(S, block_k),
            interpret=False, window=window, q_shared=q_shared, k_shared=k_shared,
        )
    # XLA fallback (CPU tests, odd shapes): the two products as one, the
    # shared key part beside every head's own
    if q_shared is not None:
        q = jnp.concatenate([q, q_shared], axis=-1)
        k = jnp.concatenate(
            [k, jnp.broadcast_to(k_shared[:, :, None, :], k.shape[:3] + k_shared.shape[2:])], axis=-1)
    return _fwd_impl(q, k, v, causal, max(block_q, block_k), sm_scale, 0, 0, window)


def flash_attention_fwd(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                        block_q: int = 1024, block_k: int = 1024,
                        window: Optional[int] = None, q_shared=None, k_shared=None):
    """Forward only, outside the custom VJP: (o [B, T, H, Dv], lse [B, T, H]
    f32). For callers that merge partial attentions by their log-sum-exp
    (paged admission: own suffix here, reused prefix from the pool).
    `window` (with `causal`): position i attends j with 0 <= i - j < window,
    and key blocks wholly behind a query block's window are skipped; the
    training path has no windowed backward and does not take it.

    v's head size may differ from q's and k's. `q_shared` [B, T, H, D2] with
    `k_shared` [B, S, D2] adds a second product to every score, q_shared .
    k_shared, whose key part is ONE vector a position for all heads (latent
    attention's rotary part: 64 wide beside a 128-wide own part, where one
    192-wide key would need the shared part copied to every head and padded
    to 256); `sm_scale` is then the caller's to give."""
    if window is not None and not causal:
        raise ValueError("a window is a causal mask's other edge: it needs causal=True")
    if (q_shared is None) != (k_shared is None) or (q_shared is not None and sm_scale is None):
        raise ValueError("q_shared and k_shared come together, with the sm_scale of the whole key")
    return _flash_fwd_dispatch(q, k, v, causal, sm_scale, block_q, block_k, window,
                               q_shared, k_shared)


# What the custom VJP's forward rule calls its two outputs, so that a remat
# policy can keep them (`models/llama.py`'s `remat_layer`): the backward kernels
# read q, k, v, o, lse, and where q, k, v are a projection and a rotation away
# from a layer's input, o and lse cost the whole forward kernel again. Kept
# below the kernels, the import too: a kernel's source lines are part of its
# program's lowered text, and no serve program's is to change by a character.
from jax.ad_checkpoint import checkpoint_name  # noqa: E402

OUT_NAME = "flash_attention_out"
LSE_NAME = "flash_attention_lse"


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    o, lse = _flash_fwd_dispatch(q, k, v, causal, sm_scale, block_q, block_k)
    # named BEFORE they part into primal output and residuals: what the layer
    # hands on and what the backward kernels read are the one named array
    o, lse = checkpoint_name(o, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, res, do):
    q, k, v, o, lse = res
    T, S = q.shape[1], k.shape[1]
    if _on_tpu() and kernel_supported(T, S, q.shape[3], block_q, block_k):
        return _flash_bwd_pallas(
            q, k, v, o, lse, do, causal, sm_scale,
            _fit_block(T, block_q), _fit_block(S, block_k),
        )
    return _blockwise_bwd(causal, max(block_q, block_k), sm_scale, 0, 0, res, do)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
