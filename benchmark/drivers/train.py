"""Driver `train`: one cell of a training configuration, through the normal
entry point: `JaxTrainer(ScalingConfig(num_workers=1, use_tpu=True,
tpu_chips_per_worker=1, strategy="dp")).fit()` around `setup_sharded_training`.

The parent never initialises a JAX backend; everything that touches the chip
is in `train_loop`, which the trainer runs in the worker granted the chip.
"""
from __future__ import annotations

import contextlib
import os
import tempfile
import time
import types
from typing import Any, Dict

import numpy as np

from benchmark import common
from benchmark.common import note, require

# steps of the window that a --trace 1 run puts under the profiler
TRACE_SKIP, TRACE_STEPS = 2, 4


def train_loop(config: Dict[str, Any]) -> None:
    import jax

    from benchmark import reference, trace_reduce, weights
    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.train.step import setup_sharded_training

    compiles = common.count_compilations()

    cfg, seed, job = config["cfg"], config["seed"], config["job"]
    B, T, seconds = job["batch"], job["seq_len"], config["seconds"]
    device0 = common.device_report()
    if device0["platform"] != config["platform"]:
        raise RuntimeError(f"the granted worker came up on {device0['platform']!r}")
    rng = np.random.default_rng([int(seed), 2])

    def next_batch():
        return rng.integers(0, cfg.vocab_size, (B, T + 1), dtype=np.int32)

    key = weights.seed_key(seed)
    first = next_batch()

    # the program's own step, optimizer and sharding; only the initialiser is
    # the benchmark's (one device program instead of dozens of eager ones)
    init_params = weights.init_params
    if config.get("lower_precision"):  # the control only: never set by a benchmark run
        def init_params(k, c):
            return weights.round_to_fewer_bits(weights.init_params(k, c), config["lower_precision"])
    model = types.SimpleNamespace(
        init_params=init_params, logical_axes=llama.logical_axes,
        loss_fn=llama.loss_fn, flops_per_token=llama.flops_per_token)
    mesh, init_fn, step_fn, shard_batch, rules = setup_sharded_training(
        cfg, strategy=job["strategy"], model=model)
    state = init_fn(key)
    jitted = step_fn.__wrapped__
    lowered = jitted.lower(state, shard_batch({"tokens": first})).as_text()
    pallas_calls = lowered.count("tpu_custom_call")
    if device0["platform"] == "tpu" and cfg.attn_impl == "auto" and not pallas_calls:
        raise RuntimeError("attn_impl='auto' gave way to the XLA path: no tpu_custom_call "
                           "in the lowered step")

    def step(tokens):
        nonlocal state
        state, metrics = step_fn(state, shard_batch({"tokens": tokens}))
        return float(metrics["loss"]), metrics  # the fetch is the device sync

    # the first two calls compile (the second sees donated buffers); a third
    # shows the steady time before the window opens
    warm = []
    loss0 = gnorm0 = None
    for i in range(3):
        t0 = time.perf_counter()
        loss, metrics = step(first if i == 0 else next_batch())
        warm.append(time.perf_counter() - t0)
        if i == 0:
            loss0, gnorm0 = loss, float(metrics["grad_norm"])
        if i == 1:
            first_step_s = common.clock() - config["t_fit"]
    compiles_before = len(compiles)
    cache_before = jitted._cache_size()

    trace_dir = os.path.join(common.RUN_DIR, "trace")
    tracing, reduced, traced_steps = False, None, 0
    profiler = contextlib.ExitStack()
    losses, ends, traced = [], [], []
    t_open = common.clock()
    setup_s = t_open - config["t_process_start"]
    while True:
        n = len(losses)
        if config["trace"] and n == TRACE_SKIP and not tracing:
            profiler.enter_context(common.traced_window(trace_dir))
            tracing, t_trace = True, common.clock()
        traced.append(tracing)
        loss, _ = step(next_batch())
        losses.append(loss)
        ends.append(common.clock())
        if tracing and len(losses) == TRACE_SKIP + TRACE_STEPS:
            trace_window = common.clock() - t_trace
            profiler.close()
            tracing, traced_steps = False, TRACE_STEPS
            reduced = trace_reduce.reduce_dir(trace_dir, trace_window)
        # the window closes at the first step boundary at or after --seconds:
        # all the work over all the time, no step cut in two
        if ends[-1] - t_open >= seconds and not tracing:
            break
    window_s = ends[-1] - t_open
    device_after = common.device_report()
    compiled_in_window = (len(compiles) - compiles_before) + (jitted._cache_size() - cache_before)

    # correctness, outside the window: the program's own loss and gradient
    # (the same loss_fn, remat and kernels the step differentiates; the step
    # itself returns no gradient) at the seed's initial weights on the first
    # batch, against the float32 reference. The trained state is dropped
    # first: both do not fit.
    t0 = time.perf_counter()
    del state
    params0 = init_params(key, cfg)
    batch0 = shard_batch({"tokens": first})
    sys_loss, sys_grads = jax.jit(jax.value_and_grad(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh, rules)))(params0, batch0)
    del params0
    ref_loss, ref_gnorm, grad_rel_err = reference.grad_check(key, first, cfg, sys_grads)
    sys_loss = float(sys_loss)
    del sys_grads
    reference_s = time.perf_counter() - t0
    report = {
        "device": device_after, "setup_s": setup_s, "window_s": window_s,
        "steps": len(losses), "tokens": len(losses) * B * T, "losses": losses,
        "step_s": [b - a for a, b in zip([t_open] + ends[:-1], ends)],
        # the step before the trace pays for starting it, the last traced one for stopping it
        "untraced": [not (t or u) for t, u in zip(traced, traced[1:] + [False])],
        "warm_step_s": warm, "first_step_s": first_step_s, "reference_s": reference_s,
        "loss0": loss0, "grad_norm0": gnorm0, "ref_loss0": ref_loss, "ref_grad_norm0": ref_gnorm,
        "sys_loss0": sys_loss, "grad_rel_err": grad_rel_err,
        "pallas_calls_in_lowered_step": pallas_calls,
        "compiled_in_window": compiled_in_window,
        "reduced": reduced, "traced_steps": traced_steps,
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
    }
    train.report({"step": len(losses), "loss": losses[-1], "bench": report})


def fit(cell: Dict[str, Any], cfg, seed: int, seconds: float, trace: bool,
        t_process_start: float, platform: str = "tpu", lower_precision=None):
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train import JaxTrainer

    job = {**cell["config_file"]["train"], **{
        k: cell["traffic_file"][k] for k in ("seq_len", "batch") if k in cell["traffic_file"]}}
    with tempfile.TemporaryDirectory(prefix="bench_train_") as storage:
        trainer = JaxTrainer(
            train_loop,
            train_loop_config={"cfg": cfg, "platform": platform,
                               "lower_precision": lower_precision, "seed": seed, "seconds": seconds, "trace": trace,
                               "job": job, "t_process_start": t_process_start,
                               "t_fit": common.clock()},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         tpu_chips_per_worker=cell["chips"],
                                         strategy=job["strategy"]),
            run_config=RunConfig(name="bench_train", storage_path=storage),
        )
        with common.deadline(1100, "JaxTrainer.fit"):
            result = trainer.fit()
    require(result.error is None, f"training failed: {result.error}")
    require("bench" in (result.metrics or {}),
            f"the last train.report did not reach the driver: {result.metrics}")
    return result.metrics["bench"], job


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_process_start: float) -> Dict[str, Any]:
    """One run of one train cell, in the shape run.py assembles a result from."""
    import ray_tpu

    with common.deadline(120, "ray_tpu.init"):
        ray_tpu.init()
    try:
        return measure(cell, seed, seconds, trace, t_process_start)
    finally:
        ray_tpu.shutdown()


def measure(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
            t_process_start: float, platform: str = "tpu",
            lower_precision=None) -> Dict[str, Any]:
    """`run` on a cluster that is already up (the tests bring their own)."""
    import math

    import ray_tpu

    cf = cell["config_file"]
    check = cf["check"]
    cfg = common.llama_config(cf)
    require(ray_tpu.cluster_resources().get("TPU", 0) >= cell["chips"],
            f"the cluster advertises TPU={ray_tpu.cluster_resources().get('TPU', 0)}, "
            f"the cell needs {cell['chips']}")
    r, job = fit(cell, cfg, seed, seconds, trace, t_process_start, platform, lower_precision)
    note(phase="setup", first_step_s=r["first_step_s"],
         warm_step_s=r["warm_step_s"], reference_s=r["reference_s"], mesh=r["mesh"],
         pallas_calls_in_lowered_step=r["pallas_calls_in_lowered_step"])
    note(phase="window", steps=r["steps"], window_s=r["window_s"], step_s=r["step_s"],
         losses=r["losses"])
    finite = all(math.isfinite(x) for x in r["losses"])
    # what the step itself reported before its first update, beside the
    # reference: printed, not judged (a mean over 8192 tokens and a norm
    # rounded to bfloat16 do not tell a lower precision; PERF.md section 2)
    note(phase="step0", loss=r["loss0"], grad_norm=r["grad_norm0"],
         program_loss=r["sys_loss0"], reference_loss=r["ref_loss0"],
         reference_grad_norm=r["ref_grad_norm0"])
    checks = [
        {"name": "grad_rel_err", "value": r["grad_rel_err"], "limit": check["grad_rel_err_limit"],
         "ok": r["grad_rel_err"] <= check["grad_rel_err_limit"]},
        {"name": "step_loss0_vs_program_loss", "value": abs(r["loss0"] - r["sys_loss0"]),
         "limit": 0, "ok": r["loss0"] == r["sys_loss0"]},
        {"name": "losses_not_finite", "value": 0 if finite else 1, "limit": 0, "ok": finite},
        {"name": "compilations_in_window", "value": r["compiled_in_window"], "limit": 0,
         "ok": r["compiled_in_window"] == 0},
    ]
    e2e = {"setup_s": r["setup_s"], "train_tok_s": r["tokens"] / r["window_s"]}
    quiet = [s for s, ok in zip(r["step_s"], r["untraced"]) if ok]
    facts = {"first_step_s": r["first_step_s"], "reduced": r["reduced"],
             # the rate over the steps the profiler did not touch, for MFU
             "train_tok_s_untraced": len(quiet) * job["batch"] * job["seq_len"] / sum(quiet),
             "traced_steps": r["traced_steps"], "job": job, "steps": r["steps"],
             "window_s": r["window_s"]}
    return {"e2e": e2e, "facts": facts, "checks": checks, "device": r["device"],
            "attempted": r["steps"], "failed": 0 if finite else 1}
