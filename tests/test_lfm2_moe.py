"""LFM2-MoE under training (models/lfm2_moe.py, afmoe.expert_ffn_train,
train/step.py's buffers) at tiny widths on the CPU, against the plain
float32 reference the benchmark keeps (benchmark/reference_lfm2_moe.py: three
explicit taps, whole score matrices, every held expert applied to every row
under a 0/1 mask: no sort, no ragged product).

What is held here: the loss and EVERY leaf's gradient; the short convolution
alone; the expert layer's backward at more pairs than `afmoe.PAIR_CHUNK`
(where the serving form would enter its data-dependent loop, which has no
transpose); a router skewed onto one held expert (both passes run, nothing is
dropped); the four shares of a layer adding up to the uncut layer, output and
router gradient alike; a train step that leaves the choice bias bit for bit
and moves every other leaf; and Mistral's step lowering to the text it
lowered to before `train/step.py` learned of buffers and counters.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import reference_lfm2_moe as R
from benchmark import weights_lfm2_moe as W
from ray_tpu.models import afmoe
from ray_tpu.models import lfm2_moe as M
from ray_tpu.models import llama
from ray_tpu.train.step import build_sharded_train_step, setup_sharded_training

F32 = jnp.float32
HIGHEST = functools.partial(jax.default_matmul_precision, "highest")


def _cfg(**kw):
    return M.Lfm2MoeConfig.tiny(dtype=F32, **kw)


def _tokens(cfg, batch=2, seq=33, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)


def _rel(a, b):
    return float(jnp.linalg.norm(a.astype(F32) - b.astype(F32)) / (jnp.linalg.norm(b.astype(F32)) + 1e-30))


@pytest.fixture(scope="module")
def tiny():
    """(cfg, params, tokens, program's (loss, counters, grads), reference's (loss, grads))."""
    cfg = _cfg()
    params = W.init_params(W.seed_key(3_000_000_019), cfg)
    tokens = _tokens(cfg)
    with HIGHEST():
        (loss, counters), grads = jax.jit(jax.value_and_grad(
            lambda p: M.loss_and_metrics(p, {"tokens": tokens}, cfg), has_aux=True))(params)
    ref = jax.jit(jax.value_and_grad(lambda p: R.plain_loss(p, tokens, cfg)))(params)
    return cfg, params, tokens, (loss, counters, grads), ref


# ------------------------------------------------------- loss and gradients
def test_the_benchmarks_initialiser_makes_the_programs_tree(tiny):
    cfg, params, *_ = tiny
    stock = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(stock) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(stock)] == [a.shape for a in jax.tree.leaves(params)]
    assert jax.tree.structure(M.logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(params)
    assert M.num_params(cfg) == sum(a.size for a in jax.tree.leaves(params))


def test_loss_equals_the_plain_references(tiny):
    _, _, _, (loss, _, _), (ref_loss, _) = tiny
    assert abs(float(loss) - float(ref_loss)) < 2e-6 * float(ref_loss)


def _leaf_paths():
    cfg = _cfg()
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    return [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]


@pytest.mark.parametrize("path", _leaf_paths())
def test_every_leafs_gradient_equals_the_plain_references(tiny, path):
    """Leaf by leaf; the choice bias takes a gradient of exactly zero from
    both (it enters a choice)."""
    _, _, _, (_, _, grads), (_, ref_grads) = tiny
    got = dict((jax.tree_util.keystr(p), g) for p, g in jax.tree_util.tree_flatten_with_path(grads)[0])[path]
    want = dict((jax.tree_util.keystr(p), g) for p, g in jax.tree_util.tree_flatten_with_path(ref_grads)[0])[path]
    if path.endswith("['bias']"):
        assert not np.any(np.asarray(got)) and not np.any(np.asarray(want))
    else:
        assert float(jnp.linalg.norm(want)) > 0 and _rel(got, want) < 2e-5


def test_the_references_layer_by_layer_check_reads_the_same_error(tiny):
    """`grad_check` (from the seed, one layer's weights at a time) against the
    whole-tree gradient of `plain_loss`: zero error for the reference's own
    gradient, the planted error for a scaled one, and its parts tell the
    experts' leaves from the rest."""
    cfg, params, tokens, (_, _, grads), (ref_loss, ref_grads) = tiny
    key = W.seed_key(3_000_000_019)
    own = R.grad_check(key, tokens, cfg, ref_grads)
    assert own["grad_rel_err"] < 1e-6 and abs(own["loss"] - float(ref_loss)) < 1e-5
    assert R.grad_check(key, tokens, cfg, grads)["grad_rel_err"] < 2e-5
    off = jax.tree.map(lambda g: g, ref_grads)
    for layer in off["layers"]:
        if "experts" in layer["ffn"]:
            layer["ffn"]["experts"] = jax.tree.map(lambda g: 1.5 * g, layer["ffn"]["experts"])
    planted = R.grad_check(key, tokens, cfg, off)
    assert abs(planted["parts"]["experts"]["rel_err"] - 0.5) < 1e-5
    assert planted["parts"]["outside"]["rel_err"] < 1e-6 < planted["grad_rel_err"]
    # the embedding's rows: an error planted in ONE row moves the whole-tree norm and not the median
    one_row = {**ref_grads, "embed": ref_grads["embed"].at[7].multiply(30.0)}
    rows = R.grad_check(key, tokens, cfg, one_row)
    assert rows["grad_row_err_median"] < 1e-6 and rows["grad_rel_err"] > 0.1
    every_row = R.grad_check(key, tokens, cfg, {**ref_grads, "embed": 1.25 * ref_grads["embed"]})
    assert abs(every_row["grad_row_err_median"] - 0.25) < 1e-5


def test_the_references_check_names_the_one_leaf_that_is_wrong(tiny):
    """ONE expert's one matrix with a gradient of zero: its part's error
    rises by that leaf's share, and the worst leaf is that expert's, by
    name, at exactly 1."""
    cfg, _, tokens, _, (_, ref_grads) = tiny
    key = W.seed_key(3_000_000_019)
    at = next(i for i, (_, ffn) in enumerate(cfg.kinds) if ffn == "moe")
    off = jax.tree.map(lambda g: g, ref_grads)
    off["layers"][at]["ffn"]["experts"]["w_down"] = off["layers"][at]["ffn"]["experts"]["w_down"].at[2].set(0.0)
    got = R.grad_check(key, tokens, cfg, off)
    name = f"layers.{at}.ffn.experts.w_down[2]"
    assert next(iter(got["worst_leaves"])) == name and abs(got["leaves"][name] - 1.0) < 1e-6
    assert 0 < got["parts"]["experts"]["rel_err"] < 1 and got["parts"]["router"]["rel_err"] < 1e-6
    assert not any(k.endswith("bias") for k in got["leaves"])  # no gradient, no ratio


def test_counters_count_the_held_pairs(tiny, monkeypatch):
    """Against the router's own choices, recorded layer by layer in an eager pass."""
    cfg, params, tokens, (_, counters, _), _ = tiny
    first, count = cfg.held_experts
    chosen, route = [], afmoe.route
    monkeypatch.setattr(afmoe, "route", lambda *a: (chosen.append(route(*a)[0]), route(*a))[1])
    with HIGHEST():
        M.loss_and_metrics(params, {"tokens": tokens}, dataclasses.replace(cfg, remat=False))
    per = np.stack([np.bincount(np.asarray(c).ravel(), minlength=cfg.n_experts)[first:first + count]
                    for c in chosen])
    assert len(chosen) == cfg.n_layers - cfg.n_dense_layers
    assert (int(counters["held_pairs"]), int(counters["expert_rows_max"]),
            int(counters["experts_hit"])) == (per.sum(), per.max(), (per > 0).sum())
    # 64 rows: a first pass of 128 pairs; this router sends ~64 to the held quarter
    assert M.pair_chunk(cfg, 64) == 128 and int(counters["second_passes"]) == 0


# ------------------------------------------------------ the short convolution
def test_short_convolution_is_three_explicit_taps_and_causal():
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.standard_normal((2, 17, 8)), F32)
    taps = jnp.asarray(rng.standard_normal((3, 8)), F32)
    got = np.asarray(M.short_conv(u, taps))
    un, k = np.asarray(u), np.asarray(taps)
    want = np.zeros_like(un)
    for t in range(17):
        want[:, t] = k[2] * un[:, t]
        if t >= 1:
            want[:, t] += k[1] * un[:, t - 1]
        if t >= 2:
            want[:, t] += k[0] * un[:, t - 2]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # causal: what comes after position 9 moves nothing at or before it
    later = u.at[:, 10:].add(5.0)
    np.testing.assert_array_equal(np.asarray(M.short_conv(later, taps))[:, :10], got[:, :10])
    assert np.abs(np.asarray(M.short_conv(later, taps))[:, 10:] - got[:, 10:]).min() > 0


def test_conv_mixer_equals_the_references_and_has_no_activation():
    cfg = _cfg()
    p = W.make_op(jax.random.PRNGKey(2), W.CONV, cfg)
    a = jnp.asarray(np.random.default_rng(2).standard_normal((2, 21, cfg.d_model)), F32)
    with HIGHEST():
        got = M.conv_op(p, a, cfg)
        want = jnp.stack([R._conv_op(a[i], p, cfg) for i in range(2)])
        # no activation anywhere: the mixer is odd in its input's gates taken together
        assert _rel(M.conv_op(p, -a, cfg), -got) < 1e-5
    assert _rel(got, want) < 1e-5


# -------------------------------------------------------- the expert layer
def _expert_layer(cfg, seed=4, rows=64):
    k = jax.random.PRNGKey(seed)
    p = W.make_ffn(k, W.MOE, cfg)
    m = jax.random.normal(jax.random.fold_in(k, 99), (rows, cfg.d_model), F32)
    return p, m


def _program_layer(p, m, cfg, chunk):
    chosen, w = afmoe.route(m, p["router"], p["bias"], cfg)
    return afmoe.expert_ffn_train(m, chosen, w, p["experts"], cfg, chunk)


@pytest.mark.parametrize("chunk", [64, 256, 10 ** 6])
def test_expert_layer_and_its_backward_equal_the_reference_at_any_pass_size(chunk):
    """One pass, two passes, and a first pass as long as all the pairs."""
    cfg = _cfg()
    p, m = _expert_layer(cfg)
    ct = jax.random.normal(jax.random.PRNGKey(7), m.shape, F32)
    with HIGHEST():
        (out, sizes), vjp = jax.vjp(lambda p, m: _program_layer(p, m, cfg, chunk), p, m)
        gp, gm = vjp((ct, np.zeros(sizes.shape, jax.dtypes.float0)))
        want, ref_vjp = jax.vjp(lambda p, m: R._moe_ffn(m, p, cfg), p, m)
        rp, rm = ref_vjp(ct)
    assert _rel(out, want) < 1e-5 and _rel(gm, rm) < 1e-5
    assert _rel(gp["router"], rp["router"]) < 1e-5
    for name in ("w_gate", "w_up", "w_down"):
        assert _rel(gp["experts"][name], rp["experts"][name]) < 1e-5


def test_gradients_exist_and_agree_at_more_pairs_than_the_serving_chunk():
    """More than `afmoe.PAIR_CHUNK` pairs in one expert layer: `expert_ffn`
    would enter `_expert_ffn_in_chunks`, whose `fori_loop` has a trip count
    that is data and no transpose; the training form differentiates."""
    cfg = _cfg(layer_types=(M.CONV, M.FULL), n_dense_layers=1)
    tokens = _tokens(cfg, batch=2, seq=641)
    assert tokens[:, :-1].size * cfg.top_k > afmoe.PAIR_CHUNK
    params = W.init_params(W.seed_key(11), cfg)
    with HIGHEST():
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: M.loss_fn(p, {"tokens": tokens}, cfg)))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: R.plain_loss(p, tokens, cfg)))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    worst = max(_rel(g, r) for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads))
                if float(jnp.linalg.norm(r)) > 0)
    assert worst < 5e-5
    p, m = _expert_layer(cfg, rows=1280)
    with pytest.raises(Exception, match="[Rr]everse-mode|while_loop|fori_loop"):
        jax.jit(jax.grad(lambda m: afmoe.expert_ffn(  # jitted: the trip count is then a tracer
            m, *afmoe.route(m, p["router"], p["bias"], cfg),
            {k: v[None] for k, v in p["experts"].items()}, 0, cfg)[0].sum()))(m)


@pytest.mark.parametrize("chunk", [64, 10 ** 6])
def test_a_router_skewed_onto_one_held_expert_drops_nothing(chunk):
    """Every row's first choice is held expert 2: it takes all 64 rows, four
    times the first pass's share at chunk 64, and both passes run."""
    cfg = _cfg()
    p, m = _expert_layer(cfg)
    p = {**p, "bias": p["bias"].at[cfg.held_experts[0] + 2].set(10.0)}
    with HIGHEST():
        out, sizes = _program_layer(p, m, cfg, chunk)
        want = R._moe_ffn(m, p, cfg)
        chosen, _ = afmoe.route(m, p["router"], p["bias"], cfg)
        gm = jax.grad(lambda m: (_program_layer(p, m, cfg, chunk)[0] ** 2).sum())(m)
        rm = jax.grad(lambda m: (R._moe_ffn(m, p, cfg) ** 2).sum())(m)
    first, count = cfg.held_experts
    held = int(((np.asarray(chosen) >= first) & (np.asarray(chosen) < first + count)).sum())
    assert int(sizes[2]) == m.shape[0] and int(sizes.sum()) == held > 64  # more than a 64-pair pass
    if chunk == 64:  # and the whole model under such a router counts its second passes
        params = W.init_params(W.seed_key(21), cfg)
        for layer in params["layers"][1:]:
            layer["ffn"]["bias"] = layer["ffn"]["bias"].at[:cfg.held_experts[1]].set(10.0)  # all four held
        _, counters = M.loss_and_metrics(params, {"tokens": _tokens(cfg, seq=65)}, cfg)
        assert (int(counters["second_passes"]), int(counters["expert_rows_max"]),
                int(counters["held_pairs"])) == (3, 128, 3 * 128 * 4)
    assert _rel(out, want) < 1e-5 and _rel(gm, rm) < 1e-5


def _as_the_chip_leaves_unwritten_rows(real):
    """`grouped_matmul` as the TPU's ragged kernels behave (my chip run, PR
    57: the first traced run of `pretrain-moe-8k` read NaN from its second
    step on): rows past the last group are never written, in the result or,
    backward, in the left operand's gradient. NaN stands for what lies there."""
    def in_group(n, sizes):
        return (jnp.arange(n) < sizes.sum())[:, None]

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return jnp.where(in_group(lhs.shape[0], sizes), real(lhs, rhs, sizes), jnp.nan)

    def fwd(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, dout):
        lhs, rhs, sizes = res
        keep = in_group(lhs.shape[0], sizes)
        _, vjp = jax.vjp(lambda l, r: real(l, r, sizes), lhs, rhs)
        dl, dr = vjp(jnp.where(keep, dout, 0))     # the matrices' gradient sums over the groups only
        return jnp.where(keep, dl, jnp.nan), dr, np.zeros(sizes.shape, jax.dtypes.float0)

    poisoned.defvjp(fwd, bwd)
    return poisoned


@pytest.mark.parametrize("chunk", [64, 256])
def test_rows_a_ragged_product_leaves_unwritten_reach_no_gradient(monkeypatch, chunk):
    """On the CPU a ragged product writes zeros past its last group; on the
    chip it writes nothing. With NaN there, forward and backward, the layer's
    output and every gradient stay finite and equal the reference's."""
    monkeypatch.setattr(afmoe, "grouped_matmul", _as_the_chip_leaves_unwritten_rows(afmoe.grouped_matmul))
    cfg = _cfg()
    p, m = _expert_layer(cfg)
    ct = jax.random.normal(jax.random.PRNGKey(7), m.shape, F32)
    with HIGHEST():
        (out, sizes), vjp = jax.vjp(lambda p, m: _program_layer(p, m, cfg, chunk), p, m)
        gp, gm = vjp((ct, np.zeros(sizes.shape, jax.dtypes.float0)))
    monkeypatch.undo()
    with HIGHEST():
        want, ref_vjp = jax.vjp(lambda p, m: R._moe_ffn(m, p, cfg), p, m)
        rp, rm = ref_vjp(ct)
    assert int(sizes.sum()) < m.shape[0] * cfg.top_k      # some pairs ARE in no group
    for got, ref in ((out, want), (gm, rm), (gp["router"], rp["router"]),
                     *((gp["experts"][n], rp["experts"][n]) for n in ("w_gate", "w_up", "w_down"))):
        assert np.all(np.isfinite(np.asarray(got))) and _rel(got, ref) < 1e-5


def test_the_four_shares_add_up_to_the_uncut_layer_output_and_router_gradient():
    """The deployment's four chips hold experts 0-3, 4-7, 8-11, 12-15 of the
    tiny router's 16: their outputs, and their router gradients under one
    fixed cotangent, add up to what the reference gives with all 16 held."""
    whole = _cfg(held_experts=(0, 16))
    p_whole, m = _expert_layer(whole)
    ct = jax.random.normal(jax.random.PRNGKey(8), m.shape, F32)
    with HIGHEST():
        want, ref_vjp = jax.vjp(lambda r, m: R._moe_ffn(m, {**p_whole, "router": r}, whole),
                                p_whole["router"], m)
        want_r, want_m = ref_vjp(ct)
        out, g_r, g_m = 0.0, 0.0, 0.0
        for first in (0, 4, 8, 12):
            cfg = _cfg(held_experts=(first, 4))
            p, _ = _expert_layer(cfg)  # the same key: this share's experts of the same layer
            for name in ("w_gate", "w_up", "w_down"):
                np.testing.assert_array_equal(np.asarray(p["experts"][name]),
                                              np.asarray(p_whole["experts"][name][first:first + 4]))
            o, vjp = jax.vjp(lambda r, m: _program_layer({**p, "router": r}, m, cfg, 64)[0],
                             p["router"], m)
            d_r, d_m = vjp(ct)
            out, g_r, g_m = out + o, g_r + d_r, g_m + d_m
    assert _rel(out, want) < 1e-5 and _rel(g_r, want_r) < 1e-5 and _rel(g_m, want_m) < 1e-5


# ------------------------------------------------------------ the train step
def test_two_steps_leave_the_choice_bias_bit_for_bit_and_move_every_other_leaf():
    cfg = _cfg()
    mesh, init_fn, step_fn, shard_batch, _ = setup_sharded_training(
        cfg, strategy="dp", model=M, devices=jax.devices()[:1])
    state = init_fn(jax.random.PRNGKey(5))
    before = jax.tree.map(np.asarray, state["params"])
    for i in range(2):
        state, metrics = step_fn(state, shard_batch({"tokens": _tokens(cfg, seed=i)}))
        assert set(M.COUNTERS) <= set(metrics) and np.isfinite(float(metrics["loss"]))
        assert 0 < int(metrics["held_pairs"]) and int(metrics["expert_rows_max"]) <= 64
    flags = jax.tree.leaves(M.buffers(cfg))
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(before)[0]]
    assert sum(flags) == cfg.n_layers - cfg.n_dense_layers
    for path, flag, a, b in zip(paths, flags, jax.tree.leaves(before), jax.tree.leaves(state["params"])):
        assert path.endswith("['bias']") == flag
        if flag:
            np.testing.assert_array_equal(a, np.asarray(b))
        else:
            assert np.any(a != np.asarray(b)), path
    # and the optimizer keeps no moments for a buffer
    moments = [x for x in jax.tree.leaves(state["opt"]) if getattr(x, "ndim", 0) >= 1]
    assert len(moments) == 2 * (len(flags) - sum(flags))


def _old_step_builder(cfg, mesh, rules, model, learning_rate=3e-4, weight_decay=0.1, grad_clip=1.0):
    """`build_sharded_train_step`'s step as it stood before PR 57, kept here
    word for word: what Mistral's step must still lower to."""
    tx = optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(learning_rate, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )

    def loss(params, batch):
        return model.loss_fn(params, batch, cfg, mesh, rules)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step_fn(state, batch):
        l, grads = jax.value_and_grad(loss)(state["params"], batch)
        updates, opt = tx.update(grads, state["opt"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        gnorm = optax.global_norm(grads)
        return (
            {"params": params, "opt": opt, "step": state["step"] + 1},
            {"loss": l, "grad_norm": gnorm, "step": state["step"] + 1},
        )

    return step_fn, tx


def test_mistrals_step_lowers_to_the_text_it_lowered_to_before():
    """A model with neither `buffers` nor `loss_and_metrics` gets the step it
    always got: the lowered text of the dense decoder's step, built by this
    PR's `train/step.py` and by the builder as it stood, is the same text."""
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.step import default_mesh_for_strategy

    cfg = llama.LlamaConfig.tiny(attn_impl="blockwise")
    mesh = build_mesh(default_mesh_for_strategy("dp", 1), jax.devices()[:1])
    init_fn, step_fn, shard_batch, rules = build_sharded_train_step(
        cfg, mesh, strategy="dp", telemetry=False)
    old_fn, tx = _old_step_builder(cfg, mesh, rules, llama)
    params = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    state = {"params": params, "opt": jax.eval_shape(tx.init, params),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}
    new_text, old_text = step_fn.lower(state, batch).as_text(), old_fn.lower(state, batch).as_text()
    assert new_text == old_text and "dot_general" in new_text


def test_config_says_what_it_cannot_run():
    with pytest.raises(ValueError, match="layer types"):
        M.Lfm2MoeConfig(layer_types=("conv", "sliding_attention"))
    with pytest.raises(ValueError, match="held_experts"):
        M.Lfm2MoeConfig(held_experts=(28, 8))
    cfg = M.Lfm2MoeConfig()
    assert (cfg.n_layers, cfg.layer_types.count(M.FULL), cfg.head_dim) == (24, 6, 64)
    assert M.pair_chunk(dataclasses.replace(cfg, held_experts=(0, 8)), 16384) == 20480
