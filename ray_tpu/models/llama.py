"""Llama-family transformer, TPU-first.

The flagship model for the framework (BASELINE.json north star:
Llama-2-7B pretraining ≥40% MFU on a v5p slice). Design choices:

- Functional pytree params (no framework Module state): params and a
  twin tree of logical axis names, so any parallelism strategy from
  ray_tpu.parallel.sharding places the same model (DP/FSDP/TP/SP/EP)
  without touching model code. This replaces the reference's
  DDP/FSDP-wrap-the-module approach
  (reference: python/ray/train/torch/train_loop_utils.py:158,453).
- bf16 params/activations, fp32 RMSNorm + softmax + logits, MXU-aligned
  dims, rotary embeddings, GQA, SwiGLU.
- Attention backends: pallas flash kernel ("flash"), O(T)-memory XLA
  ("blockwise"), or ring attention over the sp axis ("ring").
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.blockwise_attention import blockwise_attention
from ray_tpu.ops.normalization import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"  # auto | flash | blockwise | ring
    remat: bool = True
    # MoE: >0 replaces each layer's SwiGLU with moe_experts experts
    # (top-k gated, capacity-bounded; experts shard on the `ep` mesh axis)
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # routed experts per token (k=2 uses GShard-normalized weights)
    moe_top_k: int = 1
    # "grouped": sort-based routing — gather-built queues (EP) / ragged
    # grouped GEMMs (dense), no [T, E, C] intermediates. "onehot": the
    # Switch-style einsum reference, kept for A/B.
    moe_dispatch: str = "grouped"
    # router z-loss coefficient (0 = off); added to the total loss as
    # moe_router_z_weight * mean(logsumexp(router_logits)^2)
    moe_router_z_weight: float = 0.0
    # pipeline parallelism: microbatches for the GPipe schedule when the
    # mesh has a pp axis and the strategy maps the layer stack onto it
    pp_microbatches: int = 4

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    # the modules that run this config: what serve/ asks of any config
    # object instead of naming a model (imported when asked for)
    @property
    def model_module(self):
        from ray_tpu.models import llama

        return llama

    @property
    def decode_module(self):
        from ray_tpu.models import llama_decode

        return llama_decode

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**{**dict(
            vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=32, d_ff=11008, max_seq_len=4096), **kw})

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(**{**dict(
            vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_ff=14336, max_seq_len=8192, rope_theta=500000.0), **kw})

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test-sized model."""
        return LlamaConfig(**{**dict(
            vocab_size=512, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=256, max_seq_len=256), **kw})

    @staticmethod
    def nano_tpu(**kw) -> "LlamaConfig":
        """Single-chip bench model: MXU-aligned, fits one v5e chip."""
        return LlamaConfig(**{**dict(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=8,
            n_kv_heads=8, d_ff=4096, max_seq_len=2048), **kw})

    @staticmethod
    def b1_tpu(**kw) -> "LlamaConfig":
        """~1.2B-param chip-filling bench config (bf16 params ≈ 2.4 GB):
        with grads + Adam state + activations this exercises the remat
        and donation machinery a 165M nano model never touches."""
        return LlamaConfig(**{**dict(
            vocab_size=32000, d_model=2048, n_layers=18, n_heads=16,
            n_kv_heads=16, d_ff=8192, max_seq_len=4096), **kw})


def init_params(key, cfg: LlamaConfig) -> Dict[str, Any]:
    """Returns a params pytree; see logical_axes() for its sharding twin."""
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    d, h, kvh, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)).astype(cfg.dtype)

    layer_keys = jax.random.split(k_layers, cfg.n_layers)

    def make_layer(k):
        ks = jax.random.split(k, 8)
        out = {
            "attn_norm": jnp.ones((d,), cfg.dtype),
            "wq": dense(ks[0], (d, h * hd), d),
            "wk": dense(ks[1], (d, kvh * hd), d),
            "wv": dense(ks[2], (d, kvh * hd), d),
            "wo": dense(ks[3], (h * hd, d), h * hd),
            "mlp_norm": jnp.ones((d,), cfg.dtype),
        }
        if cfg.moe_experts:
            E = cfg.moe_experts
            out["gate_w"] = dense(ks[7], (d, E), d)
            out["moe_gate"] = dense(ks[4], (E, d, f), d)
            out["moe_up"] = dense(ks[5], (E, d, f), d)
            out["moe_down"] = dense(ks[6], (E, f, d), f)
        else:
            out["w_gate"] = dense(ks[4], (d, f), d)
            out["w_up"] = dense(ks[5], (d, f), d)
            out["w_down"] = dense(ks[6], (f, d), f)
        return out

    # stacked layers: one leading layer axis → lax.scan over layers keeps
    # compile time O(1) in depth (XLA-friendly; no Python layer loop)
    layers = jax.vmap(make_layer)(layer_keys)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), cfg.dtype),
        "lm_head": dense(k_out, (d, cfg.vocab_size), d),
    }


def logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Twin tree of logical axis names. The stacked-layer axis is
    "layer" — unsharded by default, mapped to `pp` under pipeline
    parallelism so each stage holds its own slice."""
    layers: Dict[str, Any] = {
        "attn_norm": ("layer", "embed"),
        "wq": ("layer", "embed", "heads"),
        "wk": ("layer", "embed", "kv"),
        "wv": ("layer", "embed", "kv"),
        "wo": ("layer", "heads", "embed"),
        "mlp_norm": ("layer", "embed"),
    }
    if cfg.moe_experts:
        layers.update({
            "gate_w": ("layer", "embed", None),
            "moe_gate": ("layer", "expert", "embed", "mlp"),
            "moe_up": ("layer", "expert", "embed", "mlp"),
            "moe_down": ("layer", "expert", "mlp", "embed"),
        })
    else:
        layers.update({
            "w_gate": ("layer", "embed", "mlp"),
            "w_up": ("layer", "embed", "mlp"),
            "w_down": ("layer", "mlp", "embed"),
        })
    return {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def _qkv(a, layer, cfg: LlamaConfig):
    """The paged serving programs' q / k / v projections (llama_decode's two
    halves and its speculative mirror, afmoe's attention): a (..., d_model) against
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


def _attention(q, k, v, cfg: LlamaConfig, mesh=None, rules=None):
    impl = cfg.attn_impl
    if impl == "auto":
        # TPU default is the pallas flash kernel whenever the shapes
        # dispatch to it; anything else falls back to the XLA blockwise path
        from ray_tpu.ops.flash_attention import _on_tpu, kernel_supported

        impl = (
            "flash"
            if _on_tpu() and kernel_supported(q.shape[1], k.shape[1], q.shape[3])
            else "blockwise"
        )
    if impl == "flash":
        from ray_tpu.ops.flash_attention import flash_attention

        if mesh is None or rules is None or mesh.size == 1:
            return flash_attention(q, k, v, True)
        # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
        # shard_map"): run it on each device's shard, split on batch and
        # heads exactly as the activations already are. KV heads split
        # with the query heads, so every shard keeps whole GQA groups;
        # where they do not divide, heads stay whole instead.
        from jax import shard_map

        n_head_shards = 1
        for a in rules.rules.get("act_heads") or ():
            n_head_shards *= mesh.shape[a]
        heads = "act_heads" if k.shape[2] % n_head_shards == 0 else None
        spec = rules.spec(("batch", None, heads, None))
        return shard_map(
            lambda q, k, v: flash_attention(q, k, v, True), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
        )(q, k, v)
    if impl == "ring":
        sp_axes = rules.rules.get("seq") if rules is not None else None
        if mesh is not None and sp_axes and all(mesh.shape[a] > 1 for a in sp_axes):
            # REAL sequence parallelism inside the jitted program: the
            # shard_map inlines, KV shards rotate over the sp ring via
            # ppermute while each device attends its local Q shard
            import functools as _ft

            from jax import shard_map

            from ray_tpu.parallel.ring_attention import ring_attention

            qspec = rules.spec(("batch", "seq", "act_heads", None))
            kvspec = rules.spec(("batch", "seq", None, None))
            fn = _ft.partial(ring_attention, axis_name=sp_axes[0], causal=True,
                             block_size=min(512, q.shape[1]))
            mapped = shard_map(
                fn, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
                out_specs=qspec, check_vma=False,
            )
            return mapped(q, k, v)
        # no sp axis on the mesh: same math, one device
        return blockwise_attention(q, k, v, True, 512)
    return blockwise_attention(q, k, v, True, min(512, q.shape[1]))


def remat_layer(fn):
    """`fn` (one layer) under `jax.checkpoint`: the backward pass makes every
    activation again from the layer's arguments, but for the flash kernel's
    output and row statistics, which cost a whole forward kernel to make and
    one array of the layer's input's size to hold. The one remat policy of
    every model's layers (`cfg.remat`); where no flash attention runs inside
    `fn` nothing bears the names and this is the plain checkpoint."""
    from ray_tpu.ops.flash_attention import LSE_NAME, OUT_NAME

    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(OUT_NAME, LSE_NAME))


def _moe_expert_fn(pe, t):
    """One expert's SwiGLU on its token queue [C, D]."""
    gate = jax.nn.silu((t @ pe["w_gate"]).astype(jnp.float32)).astype(t.dtype)
    return (gate * (t @ pe["w_up"])) @ pe["w_down"]


def _moe_expert_gemms(pe, sorted_tokens, group_sizes):
    """All experts' SwiGLU on the expert-sorted token list [S, D] as three
    ragged grouped GEMMs — same math as _moe_expert_fn, no capacity
    padding."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    g = grouped_matmul(sorted_tokens, pe["w_gate"], group_sizes)
    u = grouped_matmul(sorted_tokens, pe["w_up"], group_sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(sorted_tokens.dtype) * u
    return grouped_matmul(h, pe["w_down"], group_sizes)


def _layer_fn(layer, x, cos_sin, cfg: LlamaConfig, mesh=None, rules=None):
    cos, sin = cos_sin
    B, T, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def cstr(t, axes):
        if mesh is not None and rules is not None:
            from ray_tpu.parallel.sharding import constraint

            return constraint(t, mesh, axes, rules)
        return t

    # attention block
    a = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
    q = (a @ layer["wq"]).reshape(B, T, h, hd)
    k = (a @ layer["wk"]).reshape(B, T, kvh, hd)
    v = (a @ layer["wv"]).reshape(B, T, kvh, hd)
    q = cstr(q, ("batch", "seq", "act_heads", None))
    k = cstr(k, ("batch", "seq", None, None))
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = _attention(q, k, v, cfg, mesh, rules)
    o = o.reshape(B, T, h * hd) @ layer["wo"]
    x = x + cstr(o, ("batch", "seq", "act_embed"))

    # mlp block: SwiGLU, or top-1-gated MoE when cfg.moe_experts
    m = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
    if cfg.moe_experts:
        from ray_tpu.parallel.moe import (
            expert_parallel_moe_inline, moe_layer_dense, moe_layer_grouped,
        )

        moe_params = {
            "w_gate": layer["moe_gate"], "w_up": layer["moe_up"], "w_down": layer["moe_down"],
        }
        # the gate weights each aux term with its own coefficient
        # (aux = aw·balance + zw·z) and loss_fn adds the channel unscaled,
        # so z-regularization works at any moe_aux_weight — including 0
        moe_kw = dict(
            capacity_factor=cfg.moe_capacity_factor, top_k=cfg.moe_top_k,
            router_z_weight=cfg.moe_router_z_weight,
            aux_weight=cfg.moe_aux_weight,
        )
        ep_axes = rules.rules.get("expert") if rules is not None else None
        if mesh is not None and ep_axes and all(mesh.shape[a] > 1 for a in ep_axes):
            down, aux = expert_parallel_moe_inline(
                mesh, m, layer["gate_w"], _moe_expert_fn, moe_params,
                axis_name=ep_axes[0],
                x_spec=rules.spec(("batch", "seq", "act_embed")),
                dispatch=cfg.moe_dispatch, **moe_kw,
            )
        elif cfg.moe_dispatch == "grouped":
            # no EP axis: ragged grouped GEMMs, no capacity padding at all
            down, aux = moe_layer_grouped(
                m, layer["gate_w"], _moe_expert_gemms, moe_params, **moe_kw,
            )
        else:
            down, aux = moe_layer_dense(
                m, layer["gate_w"], _moe_expert_fn, moe_params,
                dispatch=cfg.moe_dispatch, **moe_kw,
            )
    else:
        gate = jax.nn.silu((m @ layer["w_gate"]).astype(jnp.float32)).astype(cfg.dtype)
        up = m @ layer["w_up"]
        down = (gate * up) @ layer["w_down"]
        aux = jnp.zeros((), jnp.float32)
    return x + cstr(down, ("batch", "seq", "act_embed")), aux


def _unshard_moe_expert_dim(params):
    """jax<=0.4.x silently miscomputes `ragged_dot` when its rhs GROUP dim
    is sharded (see ops/grouped_matmul). When the dense/ragged MoE path is
    about to run on CONCRETE params whose stacked expert weights [L, E, ..]
    are still ep-sharded (the A/B/eval flow: loss_fn without mesh/rules on
    a sharded train state), gather the expert dim here — before lax.scan
    hides the shardings behind tracers. No-op on tracers and unsharded
    params; the EP shard_map path never needs this (experts are local).

    Limits: only the EAGER flow is guarded (under jax.jit the params are
    tracers with no visible sharding, so jitting an eval directly over
    still-ep-sharded params stays exposed to the upstream bug), and the
    gather re-runs per call — for a many-batch eval loop, device_put the
    params off the ep axis once and jit over that instead."""
    from ray_tpu.ops.grouped_matmul import unshard_dim

    layers = params.get("layers") if isinstance(params, dict) else None
    if not isinstance(layers, dict):
        return params
    new_layers = dict(layers)
    changed = False
    for name in ("moe_gate", "moe_up", "moe_down"):
        w = layers.get(name)
        if w is None:
            continue
        new_w = unshard_dim(w, 1)  # stacked [L, E, ...]: dim 1 is experts
        if new_w is not w:
            new_layers[name] = new_w
            changed = True
    return {**params, "layers": new_layers} if changed else params


def forward_with_aux(params, tokens, cfg: LlamaConfig, mesh=None, rules=None):
    """tokens: [B, T] int32 → (logits [B, T, vocab] fp32, moe aux loss)."""
    B, T = tokens.shape
    if cfg.moe_experts and cfg.moe_dispatch == "grouped":
        ep_axes = rules.rules.get("expert") if rules is not None else None
        ep_active = (mesh is not None and ep_axes
                     and all(mesh.shape[a] > 1 for a in ep_axes))
        if not ep_active:
            params = _unshard_moe_expert_dim(params)
    embed = params["embed"]
    if mesh is not None and rules is not None:
        from ray_tpu.parallel.sharding import constraint

        # explicit all-gather of the (fsdp-sharded) table before the
        # lookup: a gather of a value-sharded table by batch-sharded
        # indices otherwise trips SPMD's replicate-as-last-resort path
        # ("Involuntary full rematerialization" warnings)
        embed = constraint(embed, mesh, (None, None), rules)
    x = embed[tokens].astype(cfg.dtype)
    if mesh is not None and rules is not None:
        from ray_tpu.parallel.sharding import constraint

        x = constraint(x, mesh, ("batch", "seq", "act_embed"), rules)
    cos, sin = rope_frequencies(cfg.head_dim, T, cfg.rope_theta)

    pp_axes = rules.rules.get("layer") if rules is not None else None
    if mesh is not None and pp_axes and all(mesh.shape[a] > 1 for a in pp_axes):
        # pipeline parallelism: the stacked layer axis is sharded on pp;
        # the GPipe microbatch schedule runs as one collective program
        # (ray_tpu/parallel/pipeline.py). The stage fn sees mesh=None —
        # inside shard_map the activations are already local shards.
        if cfg.moe_experts:
            raise NotImplementedError("pp+ep in one llama is not supported yet")
        from jax.sharding import PartitionSpec as P
        from ray_tpu.parallel.pipeline import pipelined

        pp = 1
        for a in pp_axes:
            pp *= mesh.shape[a]
        assert cfg.n_layers % pp == 0, f"{cfg.n_layers} layers not divisible by pp={pp}"

        def stage_fn(stage_layers, xm):
            lf = functools.partial(_layer_fn, cfg=cfg)
            if cfg.remat:
                lf = remat_layer(lf)

            def body(x, layer):
                x2, _aux = lf(layer, x, (cos, sin))
                return x2, None

            out, _ = jax.lax.scan(body, xm, stage_layers)
            return out

        layers_pp = jax.tree.map(
            lambda p: p.reshape(pp, cfg.n_layers // pp, *p.shape[1:]), params["layers"]
        )
        batch_entry = rules.spec(("batch",))[0]
        x = pipelined(
            mesh, stage_fn, layers_pp, x, cfg.pp_microbatches, axis_name=pp_axes[0],
            data_spec=P(None, batch_entry),
        )
        aux = jnp.zeros((), jnp.float32)
    else:
        layer_fn = functools.partial(_layer_fn, cfg=cfg, mesh=mesh, rules=rules)
        if cfg.remat:
            layer_fn = remat_layer(layer_fn)

        def scan_body(carry, layer):
            x, aux = carry
            x, aux_l = layer_fn(layer, x, (cos, sin))
            return (x, aux + aux_l), None

        (x, aux), _ = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.float32)), params["layers"]
        )
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = (x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32))
    return logits, aux


def forward(params, tokens, cfg: LlamaConfig, mesh=None, rules=None):
    """tokens: [B, T] int32 → logits [B, T, vocab] (fp32)."""
    return forward_with_aux(params, tokens, cfg, mesh, rules)[0]


def loss_fn(params, batch, cfg: LlamaConfig, mesh=None, rules=None):
    """Next-token cross entropy. batch: {"tokens": [B, T+1]} or
    {"inputs": [B,T], "targets": [B,T]}."""
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    logits, aux = forward_with_aux(params, inputs, cfg, mesh, rules)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    if mask is not None:
        ce = (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    else:
        ce = nll.mean()
    if cfg.moe_experts:
        # aux is already weighted per-term (_layer_fn applies
        # moe_aux_weight and moe_router_z_weight at the layer)
        return ce + aux
    return ce


def num_params(cfg: LlamaConfig, active_only: bool = False) -> int:
    """Total parameter count. `active_only=True` counts the params a
    TOKEN actually touches — for MoE (top-k gate) that is k experts'
    MLPs plus the router, which is what FLOPs/MFU accounting needs; for
    dense configs the two are identical."""
    d, h, kvh, hd, f, L, V = (
        cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.n_layers, cfg.vocab_size,
    )
    attn = d * h * hd + 2 * d * kvh * hd + h * hd * d
    if cfg.moe_experts and not active_only:
        mlp = cfg.moe_experts * 3 * d * f + d * cfg.moe_experts
    elif cfg.moe_experts:
        # k routed experts + router
        mlp = cfg.moe_top_k * 3 * d * f + d * cfg.moe_experts
    else:
        mlp = 3 * d * f
    per_layer = attn + mlp + 2 * d
    return V * d + L * per_layer + d + d * V


def flops_per_token(cfg: LlamaConfig, seq_len: int, causal_computed: bool = False) -> float:
    """Training FLOPs/token (fwd+bwd ≈ 6·params + attention term).

    The default counts the full 12·L·d·T attention term (the standard MFU
    convention). `causal_computed=True` halves it — the flash kernel skips
    blocks strictly above the causal diagonal, so that's the FLOPs the
    chip actually executes; useful as an honest companion number at long
    context where attention dominates."""
    attn = 12 * cfg.n_layers * cfg.d_model * seq_len  # qk^T + pv fwd+bwd
    if causal_computed:
        attn /= 2
    # MoE: a token's FLOPs touch k routed experts, not every expert
    return 6 * num_params(cfg, active_only=True) + attn


def moe_dispatch_flops_per_token(cfg: LlamaConfig, tokens_per_group: int,
                                 dispatch: Optional[str] = None) -> float:
    """Training FLOPs/token the MoE DISPATCH itself executes, summed over
    layers — add to flops_per_token() for a computed-FLOPs MFU that makes
    routing overhead visible.

    - "grouped": routing is argsort + gathers (byte moves, ~0 matmul
      FLOPs); only the combine weighting counts: k multiply-adds per
      feature, fwd+bwd → 6·k·d per layer. O(T·k·d) total.
    - "onehot": two [T,E,C]×[T,D] einsums at 2·E·C·d MACs/token each,
      fwd+bwd → 12·E·C·d per layer, with C = capacity(T) ∝ T/E — i.e.
      O(cf·T·d) per token, the term that swamped the expert FLOPs.

    `tokens_per_group` is the flattened token count the gate sees per
    routing group (B·T on one chip)."""
    from ray_tpu.parallel.moe import compute_capacity

    if not cfg.moe_experts:
        return 0.0
    dispatch = dispatch or cfg.moe_dispatch
    d, E, k, L = cfg.d_model, cfg.moe_experts, cfg.moe_top_k, cfg.n_layers
    if dispatch == "grouped":
        return float(6 * k * d * L)
    C = compute_capacity(tokens_per_group, E, cfg.moe_capacity_factor)
    return float((12 * E * C * d + 6 * k * d) * L)
