#!/usr/bin/env python3
"""Compile each cell's programs for a described v5e and print memory_analysis().

The record of how each depth was chosen. Nothing runs: there is no chip here,
so every figure this prints is COMPILED ONLY, never a chip run.

    JAX_PLATFORMS=cpu python benchmark/rehearse.py --config mistral-7b-v0.3.serve --layers 8 12 16
    JAX_PLATFORMS=cpu python benchmark/rehearse.py --config mistral-7b-v0.3.train --layers 3 4 5

Results are appended to benchmark/out/rehearse.json.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import common  # noqa: E402


def _analysis(compiled):
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")}
    # donated arguments are aliased to outputs: count them once
    out["total_bytes"] = (out["argument_size_in_bytes"] + out["output_size_in_bytes"]
                          - out["alias_size_in_bytes"] + out["temp_size_in_bytes"]
                          + out["generated_code_size_in_bytes"])
    return out


def rehearse_serve(config_file, layers, variants, one_chip):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import weights
    from ray_tpu.models import llama_decode as D
    from ray_tpu.serve._internal.sampling import MAX_STOP_TOKENS

    s = config_file["serve"]
    cfg = common.llama_config({**config_file, "num_hidden_layers": layers})
    B, bs, K, chunk = s["n_slots"], s["block_size"], 8, 8
    MB = -(-cfg.max_seq_len // bs)
    n_blocks = B * MB + 1  # the engine's default pool

    def shaped(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(lambda: weights._init(jax.random.PRNGKey(0), cfg)))
    cache = shaped(jax.eval_shape(lambda: D.init_paged_cache(cfg, B, n_blocks, bs)))

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = D.jitted_macro_step_slots_paged(cfg, chunk, sampled=False)
    rows = []
    for A, P in variants:
        t0 = time.time()
        compiled = fn.lower(
            params, cache, arr((B,), jnp.int32),
            arr((K,), jnp.int32), arr((K,), jnp.bool_), arr((K, A, P), jnp.int32),
            arr((K, A), jnp.int32), arr((K, A), jnp.int32), arr((K, A), jnp.int32),
            arr((K, A), jnp.int32), arr((K, A), jnp.uint32), arr((K, B, MB), jnp.int32),
            arr((K, B), jnp.float32), arr((K, B), jnp.int32), arr((K, B), jnp.float32),
            arr((K, B, MAX_STOP_TOKENS), jnp.int32)).compile()
        row = {"program": "macro_step_slots_paged", "layers": layers, "A": A, "P": P,
               "span": cfg.max_seq_len, "n_slots": B, "n_blocks": n_blocks,
               "compile_s": round(time.time() - t0, 1), **_analysis(compiled)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    weights_b = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(params))
    pool_b = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(cache))
    print(json.dumps({"layers": layers, "weight_bytes": weights_b, "kv_pool_bytes": pool_b}))
    return rows


def rehearse_train(config_file, layers, one_device):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmark import weights
    from ray_tpu.ops import flash_attention as FA
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.step import build_sharded_train_step, default_mesh_for_strategy

    # the backend query names the CPU here; on the chip `auto` takes the kernel
    FA._on_tpu = lambda: True
    t = config_file["train"]
    cfg = common.llama_config({**config_file, "num_hidden_layers": layers})
    mesh = build_mesh(default_mesh_for_strategy(t["strategy"], 1), [one_device])
    init_fn, step_fn, shard_batch, rules = build_sharded_train_step(
        cfg, mesh, strategy=t["strategy"], telemetry=False)
    # the optimizer as train/step.py sets it, for the shapes of its state only
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1))
    rep = NamedSharding(mesh, PartitionSpec())

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), tree)

    params = jax.eval_shape(lambda: weights._init(jax.random.PRNGKey(0), cfg))
    state = shaped({"params": params, "opt": jax.eval_shape(tx.init, params),
                    "step": jax.ShapeDtypeStruct((), jnp.int32)})
    batch = {"tokens": jax.ShapeDtypeStruct((t["batch"], t["seq_len"] + 1), jnp.int32, sharding=rep)}
    t0 = time.time()
    lowered = step_fn.lower(state, batch)
    kernels = lowered.as_text().count("tpu_custom_call")
    compiled = lowered.compile()
    row = {"program": "train_step", "layers": layers, "seq_len": t["seq_len"], "batch": t["batch"],
           "pallas_calls_in_lowered_step": kernels, "compile_s": round(time.time() - t0, 1),
           **_analysis(compiled)}
    print(json.dumps(row), flush=True)
    return [row]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="*", default=None,
                    help="serve only: A,P pairs such as 4,1024 1,16 (default: the widest)")
    args = ap.parse_args()
    config_file = common.load_json(os.path.join(common.BENCH_DIR, "configs", args.config + ".json"))

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    rows = []
    for layers in args.layers:
        try:
            if config_file["driver"] == "serve":
                variants = [tuple(int(x) for x in v.split(",")) for v in (args.variants or ["4,1024"])]
                rows += rehearse_serve(config_file, layers, variants, one_chip)
            else:
                rows += rehearse_train(config_file, layers, topo.devices[0])
        except Exception as e:  # the compiler's refusal is the finding
            row = {"config": args.config, "layers": layers, "refused": f"{type(e).__name__}: {str(e)[:600]}"}
            print(json.dumps(row), flush=True)
            rows.append(row)
    path = os.path.join(common.BENCH_DIR, "out", "rehearse.json")
    record = common.load_json(path) if os.path.exists(path) else {
        "what": "memory_analysis() of each cell's programs compiled for a described v5e:2x2 chip; "
                "COMPILED ONLY, not a chip run", "rows": []}
    record["rows"] += [{"config": args.config, **r} for r in rows]
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
