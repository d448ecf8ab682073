#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once through the entry points a user would call,
each inside worker processes that were granted the chip through `resources`,
at Llama-3-8B widths with only the depth cut to fit one 16 GB chip:

  serve  serve.run(llm_deployment(continuous=True, ...TPU: 1)) answers
         concurrent requests of mixed prompt length through the paged
         macro-step engine; the same prompts then go through the static
         llama_decode.generate path (continuous=False) and the tokens are
         compared.
  train  JaxTrainer(ScalingConfig(use_tpu=True, tpu_chips_per_worker=1))
         takes a few setup_sharded_training steps at sequence 2048 with the
         Pallas flash kernel in the lowered step.

`--chips 4` runs instead, and only, what exists across chips: one trainer
worker granted four chips (fsdp+tp, 2x2) against the same steps on one of
its devices, and four one-chip replicas behind the router against one.

This process never initialises a JAX backend: a parent that has touched
JAX holds the chip, and the workers that were granted it then fail. There
is no mode in which a run without a chip ends in `"ok": true`.

One JSON object per line; the last line is
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import shutil
import signal
import statistics
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List

import numpy as np

import ray_tpu
from ray_tpu import serve
from ray_tpu._private.accelerator_detect import detect_tpu_chips
from ray_tpu._private.accelerators.tpu import compile_cache_dir
from ray_tpu.air.config import RunConfig, ScalingConfig
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve.llm import _LLMServer, llm_deployment
from ray_tpu.train import JaxTrainer

REPO = os.path.dirname(os.path.abspath(__file__))
PLATFORM = "tpu"

# Depth cuts, chosen from memory_analysis() of the programs compiled for a
# described v5e chip (the widths are Llama-3-8B's and are never changed):
# serving holds bf16 weights (2 x 1.05 GB embed/lm_head + 0.44 GB a layer)
# plus the paged KV pool and the prefill's score tiles; training holds
# weights, gradients and both Adam moments, 4x the weights.
SERVE_LAYERS = 8
TRAIN_LAYERS = 2

# serving traffic: mixed prompt lengths, each length twice so the static
# path (one compile per distinct length) stays inside the time limit; the
# longest goes first so the warm-up request compiles the widest bucket
PROMPT_LENS = (512, 32, 128, 512, 32, 128)
MAX_NEW_TOKENS = 32
N_SLOTS = 4  # fewer lanes than requests: admission has to queue and evict
# least share of tokens on which the static and the paged path must agree
# before their first difference (bf16 near-ties under random weights may
# flip an argmax, after which the two continuations are unrelated)
MIN_AGREEMENT = 0.25

TRAIN = {"seq": 2048, "batch": 1, "steps": 5, "require_kernel": True}
# four chips: the batch has to split over fsdp=2, and the one-device run it
# is compared with has to fit one chip beside its optimizer state
TRAIN_SHARDED = {**TRAIN, "seq": 512, "batch": 2}
# relative tolerance between the sharded and the one-device loss, per step
SHARDED_LOSS_RTOL = 2e-2

WATCHDOG_S = 1150


def model_config(n_layers: int) -> LlamaConfig:
    return LlamaConfig.llama3_8b(n_layers=n_layers)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class SmokeFailure(Exception):
    pass


def require(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


@contextlib.contextmanager
def deadline(seconds: int, what: str):
    """Every wait ends: a `TPU` request that pends must end the run."""

    def _expired(signum, frame):
        raise TimeoutError(f"{what} did not finish within {seconds}s")

    previous = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


# ------------------------------------------------------------ in the workers
def _device_report() -> Dict[str, Any]:
    """What the process that holds the chip sees (runs in the worker)."""
    import jax

    from ray_tpu._private.accelerator_detect import tpu_device_nodes

    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    return {
        "pid": os.getpid(),
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "host_device_nodes": len(tpu_device_nodes()),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
        "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
    }


class _SmokeLLMServer(_LLMServer):
    """The stock deployment callable plus the replica's own account of the
    device it runs on (serve/llm.py invites subclassing)."""

    def device_report(self) -> Dict[str, Any]:
        time.sleep(0.2)  # hold the slot so concurrent probes spread over replicas
        return _device_report()


def _train_loop(config: Dict[str, Any]) -> None:
    import jax

    from ray_tpu import train
    from ray_tpu.train.step import setup_sharded_training

    cfg = config["cfg"]
    tokens = np.random.default_rng(config["seed"]).integers(
        0, cfg.vocab_size, (config["batch"], config["seq"] + 1), dtype=np.int32)

    def run(**setup_kw):
        mesh, init_fn, step_fn, shard_batch, _ = setup_sharded_training(cfg, **setup_kw)
        state = init_fn(jax.random.PRNGKey(config["seed"]))
        batch = shard_batch({"tokens": tokens})
        kernel = "tpu_custom_call" in step_fn.__wrapped__.lower(state, batch).as_text()
        if config["require_kernel"] and not kernel:
            raise RuntimeError(
                "attn_impl='auto' gave way to the XLA path: no tpu_custom_call in the "
                f"lowered step at seq {config['seq']}, head_dim {cfg.head_dim}")
        per_device: Dict[int, int] = {}
        for leaf in jax.tree.leaves(state["params"]):
            for shard in leaf.addressable_shards:
                per_device[shard.device.id] = per_device.get(shard.device.id, 0) + shard.data.nbytes
        losses, seconds = [], []
        for _ in range(config["steps"]):
            t0 = time.perf_counter()
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))  # the fetch is the device sync
            seconds.append(time.perf_counter() - t0)
            train.report({"step": len(losses), "loss": losses[-1]})
        return {
            "mesh": {k: int(v) for k, v in mesh.shape.items()},
            "pallas_kernel": kernel,
            "losses": losses,
            "step_seconds": seconds,
            "param_bytes_per_device": [per_device[d] for d in sorted(per_device)],
            "param_bytes_total": sum(x.nbytes for x in jax.tree.leaves(state["params"])),
        }

    out = {}
    if config["compare_one_device"]:
        out["one_device"] = run(strategy="dp", devices=jax.devices()[:1])
    out["run"] = run()
    out["device"] = _device_report()
    train.report({"step": config["steps"], "loss": out["run"]["losses"][-1], "smoke": out})


# ------------------------------------------------------------- in the parent
def check_device(report: Dict[str, Any], granted: int) -> None:
    require(report["platform"] == PLATFORM,
            f"the granted worker came up on {report['platform']!r}, not {PLATFORM!r}: {report}")
    visible = [c for c in (report["visible_chips"] or "").split(",") if c]
    require(report["count"] == granted == len(visible),
            f"granted {granted} chip(s) but the worker sees jax.device_count()="
            f"{report['count']} with TPU_VISIBLE_CHIPS={report['visible_chips']!r}")
    require(report["host_device_nodes"] >= report["count"],
            f"the host shows {report['host_device_nodes']} TPU device node(s) but JAX "
            f"reports {report['count']} device(s)")


def cache_entries() -> int:
    d = compile_cache_dir()
    return len(os.listdir(d)) if os.path.isdir(d) else 0


def make_prompts(seed: int, vocab: int) -> List[List[int]]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).tolist() for n in PROMPT_LENS]


def check_tokens(outputs: List[List[int]], vocab: int) -> None:
    for out in outputs:
        require(len(out) == MAX_NEW_TOKENS and all(0 <= t < vocab for t in out),
                f"a request returned {len(out)} tokens (asked {MAX_NEW_TOKENS}) or one "
                f"outside the vocabulary: {out}")


def agreement(a: List[List[int]], b: List[List[int]]) -> Dict[str, Any]:
    """Share of tokens before the first difference, over all requests."""
    prefix = []
    for x, y in zip(a, b):
        same = [s == t for s, t in zip(x, y)]
        prefix.append(same.index(False) if False in same else len(same))
    return {"agreement": sum(prefix) / (len(a) * MAX_NEW_TOKENS),
            "identical_requests": sum(p == MAX_NEW_TOKENS for p in prefix),
            "requests": len(a), "common_prefix": prefix}


def llm_app(cfg: LlamaConfig, *, continuous: bool, num_replicas: int = 1):
    """llm_deployment's application, with the reporting subclass as callable."""
    app = llm_deployment(
        num_replicas=num_replicas, max_new_tokens=MAX_NEW_TOKENS, cfg=cfg,
        continuous=continuous, n_slots=N_SLOTS,
        ray_actor_options={"resources": {"TPU": 1}})
    stock = app.deployment
    return serve.deployment(
        _SmokeLLMServer, name=stock.name, num_replicas=stock.num_replicas,
        ray_actor_options=stock.ray_actor_options, fault_config=stock.fault_config,
    ).bind(*app.init_args, **app.init_kwargs)


def deploy(cfg: LlamaConfig, *, continuous: bool, num_replicas: int = 1):
    t0 = time.perf_counter()
    with deadline(420, f"serve.run (continuous={continuous}, replicas={num_replicas})"):
        handle = serve.run(llm_app(cfg, continuous=continuous, num_replicas=num_replicas),
                           name="smoke")
    return handle, time.perf_counter() - t0


def replica_reports(handle, num_replicas: int) -> List[Dict[str, Any]]:
    """One device report per replica process, through the routed handle."""
    probe = handle.options(method_name="device_report")
    seen: Dict[int, Dict[str, Any]] = {}
    for _ in range(10):
        for response in [probe.remote() for _ in range(2 * num_replicas)]:
            report = response.result(timeout=120)
            seen[report["pid"]] = report
        if len(seen) >= num_replicas:
            break
    require(len(seen) == num_replicas,
            f"{num_replicas} replica(s) deployed but {len(seen)} process(es) answered")
    return list(seen.values())


def generate(handle, prompts: List[List[int]], *, concurrent: bool):
    """(outputs, seconds): all requests in flight at once, or one by one."""
    t0 = time.perf_counter()
    if concurrent:
        outputs = [r.result(timeout=600) for r in [handle.remote(p) for p in prompts]]
    else:
        outputs = [handle.remote(p).result(timeout=600) for p in prompts]
    return outputs, time.perf_counter() - t0


def phase_serve(seed: int) -> Dict[str, Any]:
    cfg = model_config(SERVE_LAYERS)
    emit(phase="serve", model="llama3_8b widths", d_model=cfg.d_model, n_heads=cfg.n_heads,
         n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
         depth_cut=f"{cfg.n_layers} of 32 layers", prompt_lens=PROMPT_LENS,
         max_new_tokens=MAX_NEW_TOKENS, n_slots=N_SLOTS)
    prompts, fresh = make_prompts(seed, cfg.vocab_size), make_prompts(seed + 1, cfg.vocab_size)
    entries0 = cache_entries()

    handle, deploy_s = deploy(cfg, continuous=True)
    (device,) = replica_reports(handle, 1)
    check_device(device, granted=1)
    _, first_s = generate(handle, prompts[:1], concurrent=True)
    # six requests on four lanes: two wait for an eviction, and the first
    # prompt, seen in the warm-up, is admitted from the radix prefix cache
    paged, cold_s = generate(handle, prompts, concurrent=True)
    steady, steady_s = generate(handle, fresh, concurrent=True)
    check_tokens(paged + steady, cfg.vocab_size)
    metrics = handle.options(method_name="metrics").remote().result(timeout=60)
    (after,) = replica_reports(handle, 1)
    emit(phase="serve", path="continuous paged macro-step", device=after,
         detected_chips=detect_tpu_chips(), deploy_seconds=deploy_s,
         first_request_seconds_with_compile=first_s, first_round_seconds_with_compile=cold_s,
         steady_round_seconds=steady_s, tokens_per_request=MAX_NEW_TOKENS,
         requests_per_round=len(prompts),
         engine={k: metrics.get(k) for k in (
             "dispatches", "tokens_out", "lane_occupancy_pct", "kv_blocks_total",
             "kv_blocks_peak_in_use", "ttft_ms_p50", "tpot_ms_p50")})
    serve.delete("smoke")

    handle, deploy_static_s = deploy(cfg, continuous=False)
    (device_static,) = replica_reports(handle, 1)
    check_device(device_static, granted=1)
    require(device_static["pid"] != device["pid"], "the static replica reused the paged replica's process")
    static, static_cold_s = generate(handle, prompts, concurrent=False)
    _, static_steady_s = generate(handle, prompts, concurrent=False)
    check_tokens(static, cfg.vocab_size)
    agree = agreement(paged, static)
    emit(phase="serve", path="static llama_decode.generate", deploy_seconds=deploy_static_s,
         round_seconds_with_compile=static_cold_s, steady_round_seconds=static_steady_s,
         compared="paged engine vs static path, greedy, same prompts and weights",
         threshold=MIN_AGREEMENT, **agree,
         compile_cache={"dir": compile_cache_dir(), "entries_gained": cache_entries() - entries0})
    require(agree["agreement"] >= MIN_AGREEMENT,
            f"paged and static paths agree on {agree['agreement']:.3f} of the tokens, "
            f"under the threshold {MIN_AGREEMENT}")
    serve.shutdown()
    return after


def fit(cfg: LlamaConfig, job: Dict[str, Any], *, chips: int, strategy: str, seed: int,
        compare_one_device: bool, storage: str) -> Dict[str, Any]:
    trainer = JaxTrainer(
        _train_loop,
        train_loop_config={"cfg": cfg, "seed": seed, "compare_one_device": compare_one_device,
                           **job},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True, tpu_chips_per_worker=chips,
                                     strategy=strategy),
        run_config=RunConfig(name="chip_smoke", storage_path=storage),
    )
    with deadline(900, f"JaxTrainer.fit ({strategy}, {chips} chip(s))"):
        result = trainer.fit()
    require(result.error is None, f"training failed: {result.error}")
    require(result.metrics.get("step") == job["steps"] and "smoke" in result.metrics,
            f"the last train.report did not reach the driver: {result.metrics}")
    return result.metrics["smoke"]


def check_losses(losses: List[float]) -> None:
    require(len(losses) == TRAIN["steps"] and all(np.isfinite(losses)),
            f"losses are not finite: {losses}")
    require(losses[-1] < losses[0], f"the loss did not fall over {len(losses)} steps: {losses}")


def emit_train(out: Dict[str, Any], cfg: LlamaConfig, job: Dict[str, Any], entries0: int,
               **extra) -> None:
    run = out["run"]
    emit(phase="train", device=out["device"], detected_chips=detect_tpu_chips(),
         depth_cut=f"{cfg.n_layers} of 32 layers", seq=job["seq"], batch=job["batch"],
         mesh=run["mesh"], pallas_kernel_in_lowered_step=run["pallas_kernel"],
         losses=run["losses"],
         # the first two calls each compile (the second sees donated buffers)
         compile_step_seconds=run["step_seconds"][:2],
         steady_step_seconds=statistics.median(run["step_seconds"][2:]),
         param_bytes_per_device=run["param_bytes_per_device"],
         param_bytes_total=run["param_bytes_total"],
         compile_cache={"dir": compile_cache_dir(), "entries_gained": cache_entries() - entries0},
         **extra)


def phase_train(seed: int, storage: str) -> Dict[str, Any]:
    cfg = model_config(TRAIN_LAYERS)
    entries0 = cache_entries()
    out = fit(cfg, TRAIN, chips=1, strategy="dp", seed=seed, compare_one_device=False,
              storage=storage)
    check_device(out["device"], granted=1)
    emit_train(out, cfg, TRAIN, entries0)
    check_losses(out["run"]["losses"])
    return out["device"]


def phase_train_sharded(seed: int, storage: str) -> Dict[str, Any]:
    cfg = model_config(TRAIN_LAYERS)
    entries0 = cache_entries()
    out = fit(cfg, TRAIN_SHARDED, chips=4, strategy="fsdp+tp", seed=seed,
              compare_one_device=True, storage=storage)
    check_device(out["device"], granted=4)
    one, run = out["one_device"], out["run"]
    rel = [abs(a - b) / abs(b) for a, b in zip(run["losses"], one["losses"])]
    emit_train(out, cfg, TRAIN_SHARDED, entries0, one_device_losses=one["losses"],
               one_device_step_seconds=one["step_seconds"], loss_rel_diff=rel,
               loss_rtol=SHARDED_LOSS_RTOL)
    check_losses(run["losses"])
    check_losses(one["losses"])
    require(run["mesh"].get("fsdp") == 2 and run["mesh"].get("tp") == 2,
            f"expected a 2x2 fsdp+tp mesh, got {run['mesh']}")
    require(max(rel) <= SHARDED_LOSS_RTOL,
            f"sharded and one-device losses differ by {max(rel):.4f} (rtol {SHARDED_LOSS_RTOL})")
    per_device, total = run["param_bytes_per_device"], run["param_bytes_total"]
    require(len(per_device) == 4 and max(per_device) <= 0.3 * total,
            f"parameters are not spread over four devices: {per_device} of {total} bytes")
    return out["device"]


def phase_replicas(seed: int) -> None:
    cfg = model_config(SERVE_LAYERS)
    prompts = make_prompts(seed, cfg.vocab_size)
    handle, _ = deploy(cfg, continuous=True)
    (single,) = replica_reports(handle, 1)
    check_device(single, granted=1)
    generate(handle, prompts[:1], concurrent=True)
    one, _ = generate(handle, prompts, concurrent=True)
    check_tokens(one, cfg.vocab_size)
    serve.delete("smoke")

    handle, deploy_s = deploy(cfg, continuous=True, num_replicas=4)
    reports = replica_reports(handle, 4)
    for report in reports:
        check_device(report, granted=1)
    chips = sorted(r["visible_chips"] for r in reports)
    require(len(set(chips)) == 4, f"four replicas do not hold four distinct chips: {chips}")
    # several rounds, so that every replica answers every kind of prompt
    rounds = [generate(handle, prompts, concurrent=True) for _ in range(4)]
    agrees = [agreement(one, outputs) for outputs, _ in rounds]
    for outputs, _ in rounds:
        check_tokens(outputs, cfg.vocab_size)
    emit(phase="replicas", replicas=[{k: r[k] for k in ("pid", "visible_chips", "kind", "count")}
                                     for r in reports],
         deploy_seconds=deploy_s, round_seconds=[s for _, s in rounds],
         compared="four routed replicas vs one replica, greedy, same prompts and weights",
         threshold=MIN_AGREEMENT, agreement=[a["agreement"] for a in agrees],
         identical_requests=[a["identical_requests"] for a in agrees])
    require(min(a["agreement"] for a in agrees) >= MIN_AGREEMENT,
            f"four replicas and one replica agree on {[a['agreement'] for a in agrees]} of "
            f"the tokens, under the threshold {MIN_AGREEMENT}")
    serve.shutdown()


# ------------------------------------------------------------------- driving
def preflight(chips: int) -> int:
    """Refuse to start where the run could not be a chip run."""
    require(not os.environ.get("RAY_TPU_WORKER_JAX_PLATFORMS"),
            "RAY_TPU_WORKER_JAX_PLATFORMS pins every worker's platform (the test suites' "
            "setting); unset it to run on the chip")
    platforms = os.environ.get("JAX_PLATFORMS")
    require(not platforms or PLATFORM in platforms.split(","),
            f"JAX_PLATFORMS={platforms!r} holds JAX off the {PLATFORM} backend")
    found = detect_tpu_chips()
    require(found >= chips,
            f"this run needs {chips} chip(s); the host exposes {found} "
            "(/dev/accel*, /dev/vfio/<n>)")
    return found


def dump_logs(log_dir: str) -> None:
    """Only the end of stdout and one directory come back from the machine."""
    if not os.path.isdir(log_dir):
        return
    shutil.copytree(log_dir, os.path.join(REPO, "chiprun_out", "smoke_logs"), dirs_exist_ok=True)
    paths = sorted(glob.glob(os.path.join(log_dir, "raylet-*.log"))
                   + glob.glob(os.path.join(log_dir, "worker-*.log")), key=os.path.getmtime)
    for path in paths[-6:]:
        with open(path, errors="replace") as f:
            tail = f.readlines()[-40:]
        print(f"----- tail of {os.path.basename(path)}\n" + "".join(tail), flush=True)


@contextlib.contextmanager
def cluster(need_chips: int):
    with deadline(120, "ray_tpu.init"):
        ray_tpu.init()
    log_dir = os.path.join(os.path.realpath("/tmp/ray_tpu/session_latest"), "logs")
    try:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        require(have >= need_chips, f"the cluster advertises TPU={have}, the phase needs {need_chips}")
        yield
    except BaseException:
        dump_logs(log_dir)
        raise
    finally:
        ray_tpu.shutdown()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1: serve and train on one chip (default); 4: only the sharded "
                             "trainer and the four-replica deployment, and their references")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    def _watchdog():
        print(f"chip_smoke: no end after {WATCHDOG_S}s, giving up", flush=True)
        os._exit(1)

    timer = threading.Timer(WATCHDOG_S, _watchdog)
    timer.daemon = True
    timer.start()
    try:
        found = preflight(args.chips)
        emit(phase="preflight", detected_chips=found, chips_used=args.chips, seed=args.seed,
             compile_cache_dir=compile_cache_dir(), compile_cache_entries=cache_entries())
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as storage:
            if args.chips == 1:
                with cluster(1):
                    device = phase_serve(args.seed)
                with cluster(1):
                    trained_on = phase_train(args.seed, storage)
                require(trained_on["kind"] == device["kind"], "the phases ran on different devices")
            else:
                with cluster(4):
                    device = phase_train_sharded(args.seed, storage)
                with cluster(4):
                    phase_replicas(args.seed)
    except Exception as e:  # the one boundary: say why, exit non-zero, print no result
        import traceback

        traceback.print_exc(file=sys.stdout)
        emit(phase="failed", error=f"{type(e).__name__}: {e}")
        return 1
    emit(ok=True, device={"platform": device["platform"], "kind": device["kind"],
                          "count": device["count"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
