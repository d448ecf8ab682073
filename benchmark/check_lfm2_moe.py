#!/usr/bin/env python3
"""What `correct` can and cannot see in `pretrain-moe-8k`, shown at the cell's
own size on the chip (REVIEW 57). The comparison is the harness's own: the
program's `loss_fn` differentiated at the seed's initial weights on the
seed's first batch, `reference_lfm2_moe.grad_check`, and the verdicts of
`drivers.train_lfm2_moe.gradient_checks` under the configuration's committed
limits. One process, no cluster, no train step:

    python benchmark/check_lfm2_moe.py --seed 2200000411 --out chiprun_out/check.json

- `sound`: the program as it is. Comes out correct.
- `experts_zero`: the system's gradient with every expert matrix's set to
  zero. The whole tree's error hardly moves (the experts are 0.65 % of its
  squared norm); `grad_rel_err_experts` reads 1 and the run is not correct.
- `d_rhs_halved`: a fault IN the program: the ragged product's backward
  (`ops/grouped_matmul.py`) hands back half its d rhs, the gradient of the
  experts' matrices, and everything else as it is. Not correct, by the
  experts' own error alone.
- `second_pass`: the first pass of `afmoe.expert_ffn_train` cut to half of a
  layer's held pairs, so that EVERY expert layer takes its second pass too,
  forward and backward (the cell's own steps take none): the same readings
  as `sound` to rounding, and `second_passes` = the expert layers.
- `flips`: the (token, expert layer) pairs whose top-4 choice differs
  between the program (bfloat16 activations) and the reference (float32,
  its OWN choice), counted; and the error of the last layer's output and of
  the embedding's rows over the tokens no flip touched against the rest.

`--precision int8` gives the system the control's weights, for the control's
own count of flips (`--phases sound flips`). No run of the benchmark calls
this; PERF.md section 2 has what it read on the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "pretrain-moe-8k"
PHASES = ("sound", "experts_zero", "d_rhs_halved", "second_pass", "flips")


def program_grads(params, batch, cfg):
    """(loss, counters, gradients) of the program's own loss, freshly traced."""
    import jax

    from ray_tpu.models import lfm2_moe

    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p, b: lfm2_moe.loss_and_metrics(p, b, cfg), has_aux=True))(params, batch)
    return float(loss), {k: int(v) for k, v in counters.items()}, grads


def program_forward_with_choices(params, inputs, cfg):
    """The program's own forward (`lfm2_moe.hidden`, no remat so that what
    `afmoe.route` chose can leave the trace): (the last layer's output,
    chosen [expert layers, B * T, top_k])."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import afmoe, lfm2_moe

    seen, route = [], afmoe.route

    def spy(*args, **kw):
        chosen, w = route(*args, **kw)
        seen.append(chosen)
        return chosen, w

    def forward(params, inputs):
        del seen[:]
        x, _ = lfm2_moe.hidden(params, inputs, dataclasses.replace(cfg, remat=False))
        return x, jnp.stack(seen)

    afmoe.route = spy
    try:
        return jax.jit(forward)(params, inputs)
    finally:
        afmoe.route = route


def flips(params, key, first, cfg, row_err):
    """Counts of differing choices, and errors over the tokens they touch."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_lfm2_moe as reference

    inputs, targets = first[:, :-1], first[:, 1:]
    x_sys, chosen = program_forward_with_choices(params, jnp.asarray(inputs), cfg)
    x_ref, ref_mask = reference.forward_with_choices(key, inputs, cfg)
    x_sys, x_ref = np.asarray(x_sys, np.float32), np.asarray(x_ref)
    B, T = inputs.shape
    chosen = np.asarray(chosen).reshape(-1, B, T, cfg.top_k)
    sys_mask = np.zeros(chosen.shape[:3] + (cfg.n_experts,), bool)
    np.put_along_axis(sys_mask, chosen, True, axis=-1)
    ref_mask = np.asarray(ref_mask)
    lo, n = cfg.held_experts
    differs = (sys_mask != ref_mask).any(-1)                           # [layers, B, T]
    differs_held = (sys_mask != ref_mask)[..., lo:lo + n].any(-1)      # a held expert gained or lost
    tok_err = np.linalg.norm(x_sys - x_ref, axis=-1) / np.linalg.norm(x_ref, axis=-1)   # [B, T]
    touched, touched_held = differs.any(0), differs_held.any(0)
    # a token is behind a flip where its own or an EARLIER token's choice differed in its
    # sequence (the attention layers carry it forward)
    behind = np.maximum.accumulate(touched_held, axis=1)
    # rows of the embedding: a row's gradient is its tokens' (those that carry its id, those
    # whose target it is); clean where none of them has a held flip of its own
    dirty_row = np.zeros(cfg.vocab_size, bool)
    dirty_row[inputs[touched_held]] = True
    dirty_row[targets[touched_held]] = True
    carried = np.zeros(cfg.vocab_size, bool)
    carried[inputs.reshape(-1)] = True
    q = lambda a: [float(v) for v in np.quantile(a, (0.25, 0.5, 0.75))] if a.size else None  # noqa: E731
    return {
        "pairs": int(differs.size), "pairs_differ": int(differs.sum()),
        "pairs_differ_in_a_held_expert": int(differs_held.sum()),
        "pairs_differ_by_layer": [int(v) for v in differs.sum((1, 2))],
        "tokens": int(touched.size), "tokens_with_a_flip": int(touched.sum()),
        "tokens_with_a_held_flip": int(touched_held.sum()),
        "tokens_at_or_behind_a_held_flip": int(behind.sum()),
        "last_layer_err_quartiles": {
            "all": q(tok_err), "no_flip_of_its_own": q(tok_err[~touched]),
            "no_held_flip_of_its_own": q(tok_err[~touched_held]),
            "a_held_flip_of_its_own": q(tok_err[touched_held]),
            "before_every_held_flip": q(tok_err[~behind])},
        "embedding_row_err_quartiles": {
            "carried_rows_no_held_flip": q(row_err[carried & ~dirty_row]),
            "carried_rows_a_held_flip": q(row_err[carried & dirty_row]),
            "rows_no_token_carries": q(row_err[~carried])},
    }


def run(cell, seed: int, phases=PHASES, lower_precision=None):
    """{phase: what it read} for one seed of `cell`, on the device JAX has.
    `lower_precision` gives the SYSTEM the control's weights (`int8`), as
    benchmark/control.py does: for the control's count of flips."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common, reference_lfm2_moe as reference, weights_lfm2_moe as weights
    from benchmark.drivers import train_lfm2_moe as driver
    from ray_tpu.models import lfm2_moe
    from ray_tpu.ops import grouped_matmul as GM

    cf = cell["config_file"]
    cfg = driver.lfm2_moe_config(cf)
    B, T = cell["traffic_file"]["batch"], cell["traffic_file"]["seq_len"]
    # the seed's first batch and initial weights, as the driver makes them
    first = np.random.default_rng([seed, 2]).integers(0, cfg.vocab_size, (B, T + 1), dtype=np.int32)
    key = weights.seed_key(seed)
    batch = {"tokens": jnp.asarray(first)}
    expert_layers = sum(1 for _, ffn in cfg.kinds if ffn == lfm2_moe.MOE)
    rows = {"seed": seed, "device": common.device_report(), "limits": cf["check"],
            "lower_precision": lower_precision}

    def weights_at_the_seed():
        params = weights.init_params(key, cfg)
        return weights.round_to_fewer_bits(params, lower_precision) if lower_precision else params

    def grads_at_the_seed():
        """The weights are made for the program's pass and dropped before the
        reference's, as the driver drops them: both do not fit."""
        return program_grads(weights_at_the_seed(), batch, cfg)

    def judged(name, loss, counters, grads, **more):
        t0 = time.perf_counter()
        ref = reference.grad_check(key, first, cfg, grads)
        readings = driver.gradient_readings(ref)
        checks = driver.gradient_checks(readings, cf["check"])
        rows[name] = {"correct": all(c["ok"] for c in checks), "checks": checks, "program_loss": loss,
                      "reference_loss": ref["loss"], "counters": counters, "parts": ref["parts"],
                      "worst_leaves": ref["worst_leaves"], "row_quantiles": ref["grad_row_err_quantiles"],
                      "reference_s": time.perf_counter() - t0, **more}
        common.note(phase="check_lfm2_moe", what=name, **rows[name])
        return ref

    sound = None
    if {"sound", "experts_zero", "flips"} & set(phases):
        loss, counters, grads = grads_at_the_seed()
        sound = judged("sound", loss, counters, grads)
        leaves = sorted(sound["leaves"].values())
        rows["sound"]["leaf_err_quantiles"] = {str(p): leaves[int(p * (len(leaves) - 1))]
                                               for p in (0.0, 0.5, 0.9, 0.99, 1.0)}
        if "experts_zero" in phases:
            for layer in grads["layers"]:
                if "experts" in layer["ffn"]:
                    layer["ffn"]["experts"] = jax.tree.map(jnp.zeros_like, layer["ffn"]["experts"])
            judged("experts_zero", loss, counters, grads)
        del grads
    if "d_rhs_halved" in phases:
        bwd = GM._ragged_dot_safe_bwd

        def halved(res, dout):
            dlhs, drhs, dsizes = bwd(res, dout)
            return dlhs, 0.5 * drhs, dsizes

        GM._ragged_dot_safe.defvjp(GM._ragged_dot_safe_fwd, halved)
        try:
            loss, counters, grads = grads_at_the_seed()
        finally:
            GM._ragged_dot_safe.defvjp(GM._ragged_dot_safe_fwd, bwd)
        judged("d_rhs_halved", loss, counters, grads)
        del grads
    if "second_pass" in phases:
        sized = lfm2_moe.pair_chunk
        half = B * T * cfg.top_k * cfg.held_experts[1] // cfg.n_experts // 2   # of a router in balance's share
        lfm2_moe.pair_chunk = lambda cfg, rows: half
        try:
            loss, counters, grads = grads_at_the_seed()
        finally:
            lfm2_moe.pair_chunk = sized
        judged("second_pass", loss, counters, grads, first_pass_pairs=half, expert_layers=expert_layers)
        del grads
    if "flips" in phases:
        rows["flips"] = flips(weights_at_the_seed(), key, first, cfg, sound["row_err"])
        common.note(phase="check_lfm2_moe", what="flips", **rows["flips"])
    return rows


def main() -> int:
    from benchmark import common

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--phases", nargs="+", default=list(PHASES), choices=PHASES)
    ap.add_argument("--precision", choices=("int8", "none"), default="none")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    precision = None if args.precision == "none" else args.precision
    rows = run(common.load_cell(CELL), args.seed, args.phases, precision)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    sound = precision is None
    want = {"sound": sound, "second_pass": sound, "experts_zero": False, "d_rhs_halved": False}
    wrong = [k for k, v in want.items() if k in rows and rows[k]["correct"] != v]
    if wrong:
        print("NOT AS EXPECTED: " + ", ".join(wrong))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
