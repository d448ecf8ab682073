#!/usr/bin/env python3
"""Compile the `pretrain-moe-8k` train step for a described v5e at each depth
the cut's rule tries, and print memory_analysis(): the record of how the depth
of `lfm2-8b-a1b.train` was chosen (`rehearse.py`'s train half is bound to
`LlamaConfig`). Nothing runs: every figure this prints is COMPILED ONLY.

    JAX_PLATFORMS=cpu python benchmark/rehearse_lfm2_moe.py --upto 12 10 8 6

`--upto N` keeps the published layers 0 and 2..N. Results are appended to
benchmark/out/rehearse.lfm2-8b-a1b.train.json.
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
from benchmark.rehearse import _analysis  # noqa: E402

CONFIG, TRAFFIC = "lfm2-8b-a1b.train", "tokens-8k.steady"


def kept_config(config_file, upto: int):
    """The configuration with the published layers 0 and 2..upto kept."""
    kept = [0] + list(range(2, upto + 1))
    types = config_file["published"]["layer_types"]
    return {**config_file, "kept_layers": kept, "num_hidden_layers": len(kept),
            "layer_types": [types[i] for i in kept]}


def step_and_shapes(config_file, job, one_device):
    """(the jitted train step for `one_device`, its state and batch as shapes
    placed there, the program's config): what the rehearsal and
    `tests/test_tpu_compile.py` lower and compile."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmark import weights_lfm2_moe as weights
    from benchmark.drivers.train_lfm2_moe import lfm2_moe_config, model_for_step
    from ray_tpu.models import lfm2_moe
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.step import build_sharded_train_step, default_mesh_for_strategy

    cfg = lfm2_moe_config(config_file)
    mesh = build_mesh(default_mesh_for_strategy(job["strategy"], 1), [one_device])
    _, step_fn, _, _ = build_sharded_train_step(
        cfg, mesh, strategy=job["strategy"], model=model_for_step(weights.init_params),
        telemetry=False)
    rep = NamedSharding(mesh, PartitionSpec())

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), tree)

    params = jax.eval_shape(lambda: weights._init(jax.random.PRNGKey(0), cfg))
    # the optimizer as train/step.py sets it, for the shapes of its state only
    tx = optax.multi_transform(
        {"param": optax.chain(optax.clip_by_global_norm(1.0),
                              optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)),
         "buffer": optax.set_to_zero()},
        jax.tree.map(lambda b: "buffer" if b else "param", lfm2_moe.buffers(cfg)))
    state = shaped({"params": params, "opt": jax.eval_shape(tx.init, params),
                    "step": jax.ShapeDtypeStruct((), jnp.int32)})
    batch = shaped({"tokens": jax.ShapeDtypeStruct((job["batch"], job["seq_len"] + 1), jnp.int32)})
    return step_fn, state, batch, cfg


def rehearse(config_file, job, one_device):
    import jax

    from ray_tpu.ops import flash_attention as FA

    # the backend query names the CPU here; on the chip `auto` takes the kernel
    FA._on_tpu = lambda: True
    step_fn, state, batch, cfg = step_and_shapes(config_file, job, one_device)
    t0 = time.time()
    lowered = step_fn.lower(state, batch)
    kernels = lowered.as_text().count("tpu_custom_call")
    compiled = lowered.compile()
    n_params = sum(int(x.size) for x in jax.tree.leaves(state["params"]))
    return {"program": "train_step", "layers": cfg.n_layers, "kept_layers": config_file["kept_layers"],
            "params": n_params, "seq_len": job["seq_len"], "batch": job["batch"],
            "pallas_calls_in_lowered_step": kernels, "compile_s": round(time.time() - t0, 1),
            **_analysis(compiled)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--upto", type=int, nargs="+", required=True)
    args = ap.parse_args()
    config_file = common.load_json(os.path.join(common.BENCH_DIR, "configs", CONFIG + ".json"))
    traffic = common.load_json(os.path.join(common.BENCH_DIR, "traffic", TRAFFIC + ".json"))
    job = {**config_file["train"], "seq_len": traffic["seq_len"], "batch": traffic["batch"]}

    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    rows = []
    for upto in args.upto:
        try:
            row = rehearse(kept_config(config_file, upto), job, topo.devices[0])
        except Exception as e:  # the compiler's refusal is the finding
            row = {"upto": upto, "refused": f"{type(e).__name__}: {str(e)[:600]}"}
        row = {"config": CONFIG, "upto": upto, **row}
        print(json.dumps(row), flush=True)
        rows.append(row)
    path = os.path.join(common.BENCH_DIR, "out", f"rehearse.{CONFIG}.json")
    record = common.load_json(path) if os.path.exists(path) else {
        "what": "memory_analysis() of each cell's programs compiled for a described v5e:2x2 chip; "
                "COMPILED ONLY, not a chip run", "rows": []}
    record["rows"] += rows
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
