"""GSPMD training-step builder.

Produces the jitted SPMD train step that replaces the reference's
DDP/NCCL inner loop (reference: train/torch/train_loop_utils.py
prepare_model + loss.backward + allreduce): params/opt-state sharded per
the strategy's logical-axis rules, batch sharded on (dp, fsdp), gradient
reduction emitted by XLA as ICI collectives — no process groups.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.parallel.sharding import LogicalAxisRules


def make_train_state(params, tx):
    return {"params": params, "opt": tx.init(params), "step": jnp.zeros((), jnp.int32)}


def build_sharded_train_step(
    cfg,
    mesh,
    strategy: str = "fsdp",
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    grad_clip: float = 1.0,
    model=None,
    telemetry: bool = True,
    telemetry_name: str = "train_step",
) -> Tuple[Callable, Callable, Any, "LogicalAxisRules"]:
    """Returns (init_fn, step_fn, tx, rules).

    init_fn(rng, batch_shape) -> sharded train state on the mesh.
    step_fn(state, batch) -> (state, metrics) — fully jitted SPMD.

    `telemetry=True` (default) wraps step_fn with
    observability.instrument_step: per-step wall time, goodput, compile
    events and a live MFU estimate (FLOPs from the model's analytic
    `flops_per_token` at the batch's token shape) flow to the metrics
    pipeline and the unified trace at zero change to the compiled HLO.

    `model` offers `init_params`, `logical_axes`, `loss_fn` and (for the
    telemetry) `flops_per_token`. It MAY also offer `buffers(cfg)`, a twin
    tree of bools that is True at a leaf which is a buffer and no parameter
    (a router's choice bias): such a leaf takes no update, no weight decay
    and no optimizer state; and `loss_and_metrics`, `loss_fn` returning
    (loss, dict of scalars) whose scalars ride in the step's metrics beside
    the loss. A model with neither gets the step it always got.
    """
    from ray_tpu.models import llama as L

    model = model or L
    rules = LogicalAxisRules.for_strategy(strategy)
    axes = model.logical_axes(cfg)

    tx = optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(learning_rate, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )
    if hasattr(model, "buffers"):
        labels = jax.tree.map(lambda b: "buffer" if b else "param", model.buffers(cfg))
        tx = optax.multi_transform({"param": tx, "buffer": optax.set_to_zero()}, labels)

    param_shardings = jax.tree.map(
        lambda ax: rules.named_sharding(mesh, ax),
        axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(i, (str, type(None))) for i in x),
    )
    batch_sharding = rules.named_sharding(mesh, ("batch", None))
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())

    with_metrics = hasattr(model, "loss_and_metrics")

    def loss(params, batch):
        if with_metrics:
            return model.loss_and_metrics(params, batch, cfg, mesh, rules)
        return model.loss_fn(params, batch, cfg, mesh, rules)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step_fn(state, batch):
        l, grads = jax.value_and_grad(loss, has_aux=with_metrics)(state["params"], batch)
        l, extra = l if with_metrics else (l, {})
        with jax.named_scope("optimizer"):
            updates, opt = tx.update(grads, state["opt"], state["params"])
            params = optax.apply_updates(state["params"], updates)
            gnorm = optax.global_norm(grads)
        return (
            {"params": params, "opt": opt, "step": state["step"] + 1},
            {**extra, "loss": l, "grad_norm": gnorm, "step": state["step"] + 1},
        )

    if telemetry:
        from ray_tpu.observability import instrument_step

        flops_fn = getattr(model, "flops_per_token", None)
        _flops_cache: Dict[Tuple[int, ...], float] = {}

        def _step_flops(args, kwargs):
            # batch tokens are [B, T+1] (inputs+shifted targets); the
            # analytic FLOPs are per TRAINED token. Cached per shape —
            # the math is cheap but the hot path should not repeat it.
            if flops_fn is None:
                return None
            try:
                tokens = args[1]["tokens"]
                key = tuple(tokens.shape)
                if key not in _flops_cache:
                    b, t1 = tokens.shape
                    _flops_cache[key] = flops_fn(cfg, t1 - 1) * b * (t1 - 1)
                return _flops_cache[key]
            except Exception:
                return None

        step_fn = instrument_step(
            step_fn, name=telemetry_name, flops_per_call=_step_flops,
            kind="training",
        )

    def init_fn(rng):
        params = model.init_params(rng, cfg)
        params = jax.tree.map(
            lambda p, sh: jax.device_put(p, sh), params, param_shardings
        )
        # opt state init under jit so mu/nu inherit param shardings
        opt = jax.jit(tx.init)(params)
        return {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}

    def shard_batch(batch):
        return jax.tree.map(lambda x: jax.device_put(x, batch_sharding), batch)

    return init_fn, step_fn, shard_batch, rules


def default_mesh_for_strategy(strategy: str, n_devices: int) -> MeshSpec:
    """Lay a strategy string onto n devices: each model-parallel axis
    named in the strategy (tp/sp/ep/pp) gets degree 2; the data axis
    (fsdp if named, else dp) absorbs the remainder. Pass an explicit
    MeshSpec (ScalingConfig.mesh) for non-default degrees."""
    parts = set(strategy.split("+")) if strategy else set()
    degrees = {}
    for ax in ("tp", "sp", "ep", "pp"):
        if ax in parts:
            degrees[ax] = 2
    data_axis = "fsdp" if "fsdp" in parts else "dp"
    degrees[data_axis] = -1  # absorb
    return MeshSpec(**degrees).resolve(n_devices)


def setup_sharded_training(
    cfg,
    strategy: Optional[str] = None,
    mesh_spec=None,
    devices=None,
    model=None,
    **step_kwargs,
):
    """Worker-loop entry: resolve the parallelism strategy (argument >
    the trainer's ScalingConfig.strategy, which JaxTrainer exports as
    RAY_TPU_TRAIN_STRATEGY > "fsdp"), build the mesh over this worker's
    visible devices, and return (mesh, init_fn, step_fn, shard_batch,
    rules).

    Usage inside a JaxTrainer train loop::

        mesh, init_fn, step_fn, shard_batch, _ = setup_sharded_training(cfg)
        state = init_fn(jax.random.PRNGKey(0))
        state, metrics = step_fn(state, shard_batch(batch))
    """
    import os

    strategy = strategy or os.environ.get("RAY_TPU_TRAIN_STRATEGY") or "fsdp"
    if devices is None:
        devices = jax.devices()
    # "dcn_dp=N+<inner>" routes to the multislice path: N device islands
    # with <inner> laid out inside each, gradients crossing islands via
    # the host-mediated DCN allreduce (parallel/multislice.py). Same
    # 5-tuple contract; state/batch become per-slice lists.
    if "dcn_dp" in strategy:
        parts = strategy.split("+")
        dcn = next(p for p in parts if p.startswith("dcn_dp"))
        n_slices = int(dcn.split("=")[1]) if "=" in dcn else 2
        inner = "+".join(p for p in parts if not p.startswith("dcn_dp")) or "dp"
        from ray_tpu.parallel.multislice import setup_multislice_training

        ms = setup_multislice_training(
            cfg, n_slices, strategy=inner, devices=devices, model=model, **step_kwargs
        )
        return ms.meshes, ms.init_states, ms.step, ms.shard_batches, ms.rules
    if mesh_spec is None:
        mesh_spec = default_mesh_for_strategy(strategy, len(devices))
    elif isinstance(mesh_spec, dict):
        mesh_spec = MeshSpec(**mesh_spec)
    mesh = build_mesh(mesh_spec, devices)
    init_fn, step_fn, shard_batch, rules = build_sharded_train_step(
        cfg, mesh, strategy=strategy, model=model, **step_kwargs
    )
    return mesh, init_fn, step_fn, shard_batch, rules
