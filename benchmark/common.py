"""What every part of the benchmark shares: where its files are, how a cell
is looked up by name, and how a configuration file becomes the program's
`LlamaConfig`. Importing this module touches no JAX backend."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import signal
import sys
import time
from typing import Any, Dict

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
# what a run leaves behind (traces, logs); listed in .gitignore
RUN_DIR = os.path.join(BENCH_DIR, "out", "run")

if REPO not in sys.path:
    sys.path.insert(0, REPO)


class BenchFailure(Exception):
    """The run cannot give a result: exit non-zero, print no result line."""


def clock() -> float:
    """Seconds on the host's monotonic clock. Every duration the benchmark
    reports is a difference of two readings of it: CLOCK_MONOTONIC is one clock
    for every process of the machine (a worker can be timed from a stamp its
    parent took) and is never stepped, where `time.time()` is set from outside
    and a step inside a window shortens or lengthens everything timed across it."""
    return time.clock_gettime(time.CLOCK_MONOTONIC)


def require(cond: bool, message: str) -> None:
    if not cond:
        raise BenchFailure(message)


def note(**fields) -> None:
    """One JSON object on an earlier line of stdout."""
    print(json.dumps(fields, default=str), flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    return load_json(os.path.join(REPO, "BENCHMARK.json"))


def load_cell(name: str) -> Dict[str, Any]:
    """The cell, with its configuration and traffic files read in."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    require(name in cells, f"no workload {name!r} in BENCHMARK.json (has {sorted(cells)})")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in bench["configs"]}
    cell["config_entry"] = configs[cell["config"]]
    cell["config_file"] = load_json(os.path.join(REPO, configs[cell["config"]]["file"]))
    cell["traffic_file"] = load_json(
        os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    cell["end_to_end"] = [m for m in bench["end_to_end"]
                          if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if name in m.get("workloads", [name]) and m["moves"] in e2e_names]
    return cell


def apply_env(config_file: Dict[str, Any]) -> None:
    """The program's own environment knobs a configuration names, set before
    the cluster starts so that they reach its workers."""
    for k, v in (config_file.get("env") or {}).items():
        if k != "why":
            os.environ[k] = str(v)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by the name BENCHMARK.json or a
    configuration file gives: a new driver or per-layer metric is a new file."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    require(os.path.isfile(path), f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def llama_config(config_file: Dict[str, Any], **overrides):
    """The program's config object for a configuration file. With no sliding
    window Mistral-7B-v0.3 is exactly what `LlamaConfig` computes."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    c = config_file
    require(c.get("sliding_window") is None, "LlamaConfig has no sliding window")
    require(c["hidden_size"] // c["num_attention_heads"] == c["head_dim"],
            "LlamaConfig derives head_dim from hidden_size / heads")
    require(not c.get("tie_word_embeddings"), "LlamaConfig keeps an untied lm_head")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]]
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]), dtype=dtype)
    train = c.get("train") or {}
    if "attn_impl" in train:
        kw["attn_impl"] = train["attn_impl"]
    if "remat" in train:
        kw["remat"] = bool(train["remat"])
    kw.update(overrides)
    return LlamaConfig(**kw)


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    require(device_kind in table,
            f"device kind {device_kind!r} is not in benchmark/peaks.json: add it with its source")
    return table[device_kind]


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


def count_compilations() -> list:
    """A list that grows by one entry for every program this process hands to
    the backend compiler from now on (a persistent-cache hit counts: the jit
    cache missed). Its length before and after a window is the number of
    compilations inside it."""
    import jax

    events: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: events.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    return events


def start_trace(trace_dir: str) -> None:
    """The profiler on, with the Python tracer, so that an idle gap on the
    device can be named by what the host was doing (traced runs only)."""
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 1
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


@contextlib.contextmanager
def traced_window(trace_dir: str):
    """The profiler on around the body, and the body marked in the trace by an
    annotation of its own: the profiler records before `start_trace` returns
    and after `stop_trace` is called, so the reducer takes the window from the
    mark (`trace_reduce.WINDOW_MARKER`), not from a clock beside it."""
    import jax

    from benchmark.trace_reduce import WINDOW_MARKER

    start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_MARKER):
            yield
    finally:
        jax.profiler.stop_trace()


def device_report() -> Dict[str, Any]:
    """What the process that holds the chip sees (runs in the worker)."""
    import jax

    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return {
        "pid": os.getpid(),
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max([p for p in peaks if p is not None], default=None),
        "bytes_limit": (devices[0].memory_stats() or {}).get("bytes_limit"),
        "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
    }
