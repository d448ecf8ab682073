"""Driver `train_lfm2_moe`: one cell of the LFM2-MoE training configuration,
through the normal entry point: `JaxTrainer(ScalingConfig(num_workers=1,
use_tpu=True, tpu_chips_per_worker=1, strategy="dp")).fit()` around
`setup_sharded_training(cfg, strategy="dp", model=<models/lfm2_moe.py's
functions, the benchmark's initialiser>)`.

`drivers/train.py` with another model's files named in it (PR 57: that file
is bound to `LlamaConfig`, `reference` and `weights`, and an accepted
benchmark file is not this PR's to edit; folding the two is a `benchmark`
issue's). What differs: the configuration's builder, the model handed to the
step, the step's counters (`held_pairs`, `expert_rows_max`, `experts_hit`, `second_passes`)
kept beside each loss, the next step's batch made and sent while the device
works on this one, and what of the gradient comparison is judged
(`gradient_checks`: the whole tree, the experts' matrices alone, the routers
alone, the median row of the embedding's gradient).

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
# what of `reference_lfm2_moe.grad_check` is judged, each against the
# configuration's `check.<name>_limit`
GRADIENT_CHECKS = ("grad_rel_err", "grad_rel_err_experts", "grad_rel_err_router",
                   "grad_row_err_median")


def gradient_readings(ref: Dict[str, Any]) -> Dict[str, float]:
    """The judged numbers of one `reference_lfm2_moe.grad_check`."""
    return {"grad_rel_err": ref["grad_rel_err"],
            "grad_rel_err_experts": ref["parts"]["experts"]["rel_err"],
            "grad_rel_err_router": ref["parts"]["router"]["rel_err"],
            "grad_row_err_median": ref["grad_row_err_median"]}


def gradient_checks(readings: Dict[str, float], check: Dict[str, Any]):
    """The gradient's part of `correct`. The whole tree's error is the mixers'
    and the embedding's: the experts' matrices are 0.65 % of the gradient's
    squared norm and the routers 0.006 % (my chip runs, PR 57), so an expert
    gradient that is zero would pass under it; the experts' and the routers'
    own errors are judged beside it. The median row of the embedding's: a
    top-4 choice that flips on a near-tie between bfloat16 and float32 is
    ONE token's and moves the rows that token touches, a lower precision is
    every token's (PERF.md section 2 has every statistic's readings)."""
    return [{"name": name, "value": readings[name], "limit": check[name + "_limit"],
             "ok": readings[name] <= check[name + "_limit"]} for name in GRADIENT_CHECKS]


def lfm2_moe_config(config_file: Dict[str, Any], **overrides):
    """The program's config object for a configuration file of this family."""
    import jax.numpy as jnp

    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig

    c = config_file
    require(len(c["layer_types"]) == c["num_hidden_layers"], "layer_types names every layer")
    require(not c["conv_bias"] and c["norm_topk_prob"] and c["use_expert_bias"],
            "models/lfm2_moe.py has no convolution bias, normalises the chosen scores and "
            "takes a choice bias")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]]
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], layer_types=tuple(c["layer_types"]),
        n_dense_layers=c["num_dense_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        moe_d_ff=c["moe_intermediate_size"], n_experts=c["router_num_experts"],
        top_k=c["num_experts_per_tok"],
        held_experts=(c["held_experts_first"], c["num_experts"]), conv_taps=c["conv_L_cache"],
        rope_theta=float(c["rope_theta"]), route_scale=float(c["routed_scaling_factor"]),
        rms_eps=float(c["norm_eps"]), max_seq_len=c["max_position_embeddings"], dtype=dtype)
    train = c.get("train") or {}
    if "attn_impl" in train:
        kw["attn_impl"] = train["attn_impl"]
    if "remat" in train:
        kw["remat"] = bool(train["remat"])
    kw.update(overrides)
    return Lfm2MoeConfig(**kw)


def model_for_step(init_params):
    """What `setup_sharded_training(model=)` is handed: the program's own
    functions and the benchmark's initialiser (one device program instead of
    dozens of eager ones)."""
    from ray_tpu.models import lfm2_moe

    return types.SimpleNamespace(
        init_params=init_params, logical_axes=lfm2_moe.logical_axes, loss_fn=lfm2_moe.loss_fn,
        flops_per_token=lfm2_moe.flops_per_token, buffers=lfm2_moe.buffers,
        loss_and_metrics=lfm2_moe.loss_and_metrics)


def train_loop(config: Dict[str, Any]) -> None:
    import jax

    from benchmark import reference_lfm2_moe as reference, trace_reduce, weights_lfm2_moe as weights
    from ray_tpu import train
    from ray_tpu.models import lfm2_moe
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
    model = model_for_step(init_params)
    # the job's learning rate where the configuration states one (`assumed.optimizer`)
    step_kwargs = {"learning_rate": job["learning_rate"]} if "learning_rate" in job else {}
    mesh, init_fn, step_fn, shard_batch, rules = setup_sharded_training(
        cfg, strategy=job["strategy"], model=model, **step_kwargs)
    state = init_fn(key)
    jitted = step_fn.__wrapped__
    lowered = jitted.lower(state, shard_batch({"tokens": first})).as_text()
    pallas_calls = lowered.count("tpu_custom_call")
    if device0["platform"] == "tpu" and cfg.attn_impl == "auto" and not pallas_calls:
        raise RuntimeError("attn_impl='auto' gave way to the XLA path: no tpu_custom_call "
                           "in the lowered step")

    counters = []  # each step's, as the step returned them: read after the window

    ahead = None  # the next step's batch, already on the device

    def step(tokens=None):
        """One step on `tokens`, or on the batch made ahead. The NEXT step's
        fresh batch is made on the host and sent while the device works on
        this one, one deep, as an input pipeline does: the step stays
        device-bound whatever the shared host's cores are doing (made in
        line, the hop read 3-6 ms a step by the run and `train_tok_s` swung
        0.7 % between two runs of one tree: my chip runs, PR 57)."""
        nonlocal state, ahead
        state, metrics = step_fn(state, ahead if tokens is None else shard_batch({"tokens": tokens}))
        ahead = shard_batch({"tokens": next_batch()})
        counters.append({k: metrics[k] for k in lfm2_moe.COUNTERS})
        return float(metrics["loss"]), metrics  # the fetch is the device sync

    # the first two calls compile (the second sees donated buffers); a third
    # shows the steady time before the window opens
    warm = []
    loss0 = gnorm0 = None
    for i in range(3):
        t0 = time.perf_counter()
        loss, metrics = step(first if i == 0 else None)
        warm.append(time.perf_counter() - t0)
        if i == 0:
            loss0, gnorm0 = loss, float(metrics["grad_norm"])
        if i == 1:
            first_step_s = common.clock() - config["t_fit"]
    compiles_before = len(compiles)
    cache_before = jitted._cache_size()
    del counters[:]  # the window's own from here

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
        loss, _ = step()
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
        lambda p, b: lfm2_moe.loss_fn(p, b, cfg, mesh, rules)))(params0, batch0)
    del params0
    ref = reference.grad_check(key, first, cfg, sys_grads)
    sys_loss = float(sys_loss)
    del sys_grads
    reference_s = time.perf_counter() - t0
    report = {
        "device": device_after, "setup_s": setup_s, "window_s": window_s,
        "steps": len(losses), "tokens": len(losses) * B * T, "losses": losses,
        "step_s": [b - a for a, b in zip([t_open] + ends[:-1], ends)],
        # the step before the trace pays for starting it, the last traced one for stopping
        # it, and the one after it for the trace's reduction (it ends with that step's clock)
        "untraced": [not (p or t or u) for p, t, u in zip([False] + traced[:-1], traced,
                                                          traced[1:] + [False])],
        "warm_step_s": warm, "first_step_s": first_step_s, "reference_s": reference_s,
        "loss0": loss0, "grad_norm0": gnorm0, "ref_loss0": ref["loss"],
        "ref_grad_norm0": ref["grad_norm"], "sys_loss0": sys_loss,
        **gradient_readings(ref),
        "grad_rel_err_parts": {**ref["parts"], "row_quantiles": ref["grad_row_err_quantiles"],
                               "worst_leaves": ref["worst_leaves"]},
        "counters": [{k: int(v) for k, v in c.items()} for c in counters],
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
    with tempfile.TemporaryDirectory(prefix="bench_train_lfm2_moe_") as storage:
        trainer = JaxTrainer(
            train_loop,
            train_loop_config={"cfg": cfg, "platform": platform,
                               "lower_precision": lower_precision, "seed": seed, "seconds": seconds, "trace": trace,
                               "job": job, "t_process_start": t_process_start,
                               "t_fit": common.clock()},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         tpu_chips_per_worker=cell["chips"],
                                         strategy=job["strategy"]),
            run_config=RunConfig(name="bench_train_lfm2_moe", storage_path=storage),
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
    cfg = lfm2_moe_config(cf)
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
    # reference: printed, not judged (a mean over 16,384 tokens and a norm
    # rounded to bfloat16 do not tell a lower precision; PERF.md section 2)
    note(phase="step0", loss=r["loss0"], grad_norm=r["grad_norm0"],
         program_loss=r["sys_loss0"], reference_loss=r["ref_loss0"],
         reference_grad_norm=r["ref_grad_norm0"], grad_rel_err_parts=r["grad_rel_err_parts"])
    checks = gradient_checks(r, check) + [
        # two compilations of one forward (the whole step, and `loss_fn` alone): equal to rounding
        {"name": "step_loss0_vs_program_loss", "value": abs(r["loss0"] - r["sys_loss0"]),
         "limit": check["step_loss0_limit"],
         "ok": abs(r["loss0"] - r["sys_loss0"]) <= check["step_loss0_limit"]},
        {"name": "losses_not_finite", "value": 0 if finite else 1, "limit": 0, "ok": finite},
        {"name": "compilations_in_window", "value": r["compiled_in_window"], "limit": 0,
         "ok": r["compiled_in_window"] == 0},
    ]
    e2e = {"setup_s": r["setup_s"], "train_tok_s": r["tokens"] / r["window_s"]}
    quiet = [s for s, ok in zip(r["step_s"], r["untraced"]) if ok]
    held = [c["held_pairs"] for c in r["counters"]]
    note(phase="counters", held_pairs=held,
         **{k: [c[k] for c in r["counters"]] for k in r["counters"][0] if k != "held_pairs"})
    facts = {"first_step_s": r["first_step_s"], "reduced": r["reduced"],
             # the pairs the steps really held: over the steps the profiler did
             # not touch (for MFU) and over those it held whole (for the roofline)
             "held_pairs_untraced": sum(h for h, ok in zip(held, r["untraced"]) if ok),
             "untraced_s": sum(quiet), "untraced_steps": len(quiet),
             "held_pairs_traced": sum(held[TRACE_SKIP:TRACE_SKIP + r["traced_steps"]]),
             # the rate over the steps the profiler did not touch, for MFU
             "train_tok_s_untraced": len(quiet) * job["batch"] * job["seq_len"] / sum(quiet),
             "traced_steps": r["traced_steps"], "job": job, "steps": r["steps"],
             "window_s": r["window_s"]}
    return {"e2e": e2e, "facts": facts, "checks": checks, "device": r["device"],
            "attempted": r["steps"], "failed": 0 if finite else 1}
