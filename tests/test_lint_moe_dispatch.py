"""Lint: the grouped MoE dispatch must never materialize a [T, E, C]
(or [T·k, E, C]) tensor — that rank-3 intermediate IS the one-hot
routing formulation whose einsums cost O(T·E·C·D) FLOPs and cratered
MoE MFU to 25% of dense. Walks the full fwd+bwd jaxpr (including
sub-jaxprs) and, via XLA cost analysis, bounds the grouped path's
non-expert FLOPs to O(T·k·D) — CPU-checkable proxies for the TPU win.
"""
import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np

from ray_tpu.parallel.moe import (
    compute_capacity,
    moe_layer_dense,
    moe_layer_grouped,
)

# dims chosen pairwise-distinct so a shape match is unambiguous
T, D, E, F = 96, 16, 4, 32
CF = 1.0
C = compute_capacity(T, E, CF)
K = 2
S = T * K


def _expert_fn(pe, t):
    g = jax.nn.silu((t @ pe["w_gate"]).astype(jnp.float32)).astype(t.dtype)
    return (g * (t @ pe["w_up"])) @ pe["w_down"]


def _expert_gemms(pe, sorted_tokens, group_sizes):
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    g = grouped_matmul(sorted_tokens, pe["w_gate"], group_sizes)
    u = grouped_matmul(sorted_tokens, pe["w_up"], group_sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(sorted_tokens.dtype) * u
    return grouped_matmul(h, pe["w_down"], group_sizes)


def _args(k):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (T, D)) * 0.1
    gate_w = jax.random.normal(ks[1], (D, E)) * 0.1
    params = {
        "w_gate": jax.random.normal(ks[2], (E, D, F)) * 0.1,
        "w_up": jax.random.normal(ks[3], (E, D, F)) * 0.1,
        "w_down": jax.random.normal(ks[4], (E, F, D)) * 0.1,
    }
    return x, gate_w, params


def _loss(dispatch, k):
    def f(x, gw, ps):
        if dispatch == "ragged":
            out, aux = moe_layer_grouped(x, gw, _expert_gemms, ps,
                                         capacity_factor=CF, top_k=k)
        else:
            out, aux = moe_layer_dense(x, gw, _expert_fn, ps,
                                       capacity_factor=CF, top_k=k,
                                       dispatch=dispatch)
        return (out ** 2).sum() + aux
    return f


def _walk_avals(jaxpr):
    """Every intermediate aval in a jaxpr, recursing into sub-jaxprs
    (pjit / custom_jvp / scan / cond bodies)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v, "aval"):
                yield v.aval
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                yield from _walk_avals(sub)


def _sub_jaxprs(p):
    if isinstance(p, jax.extend.core.ClosedJaxpr):
        yield p.jaxpr
    elif isinstance(p, jax.extend.core.Jaxpr):
        yield p
    elif isinstance(p, (list, tuple)):
        for item in p:
            yield from _sub_jaxprs(item)


def _rank3_tec_avals(fn, *args):
    jaxpr = jax.make_jaxpr(jax.value_and_grad(fn, argnums=(0, 1, 2)))(*args)
    bad = []
    for aval in _walk_avals(jaxpr.jaxpr):
        shape = tuple(getattr(aval, "shape", ()))
        if len(shape) == 3 and shape[0] in (T, S) and shape[1:] == (E, C):
            bad.append(shape)
    return bad


def test_grouped_dispatch_has_no_tec_intermediate():
    for dispatch in ("grouped", "ragged"):
        for k in (1, K):
            bad = _rank3_tec_avals(_loss(dispatch, k), *_args(k))
            assert not bad, f"{dispatch} k={k} materializes {bad}"


def test_lint_detects_onehot_path():
    # detector sanity: the reference einsum path MUST trip the lint
    bad = _rank3_tec_avals(_loss("onehot", 1), *_args(1))
    assert bad, "lint failed to flag the one-hot [T, E, C] tensors"


def _flops(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    analysis = compiled.cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0]
    return float(analysis.get("flops", 0.0)) if analysis else 0.0


def test_grouped_dispatch_flops_bounded():
    """Counted dispatch FLOPs of the grouped path ≤ O(T·k·D): total
    forward FLOPs minus the expert GEMMs + router must fit in a small
    multiple of S·D (gather/weighting/softmax), nowhere near the
    12·E·C·D/token the one-hot einsums burn."""
    for k in (1, K):
        args = _args(k)
        s = T * k
        fwd = lambda x, gw, ps: moe_layer_dense(  # noqa: E731
            x, gw, _expert_fn, ps, capacity_factor=CF, top_k=k,
            dispatch="grouped")[0]
        total = _flops(fwd, *args)
        expert = 2 * 3 * D * F * E * C     # padded queues: E·C rows
        router = 2 * T * E * D
        overhead = total - expert - router
        budget = 32 * s * D + 16 * T * E + 4096  # gathers + softmax + sort
        assert overhead <= budget, (
            f"k={k}: dispatch overhead {overhead:.0f} FLOPs exceeds "
            f"O(T·k·D) budget {budget}")

    # and the one-hot path pays the einsum tax the grouped path skips
    onehot = _flops(lambda x, gw, ps: moe_layer_dense(
        x, gw, _expert_fn, ps, capacity_factor=CF, top_k=1,
        dispatch="onehot")[0], *_args(1))
    grouped = _flops(lambda x, gw, ps: moe_layer_dense(
        x, gw, _expert_fn, ps, capacity_factor=CF, top_k=1,
        dispatch="grouped")[0], *_args(1))
    assert onehot >= grouped + 2 * 2 * T * E * C * D  # the two einsums


def test_ragged_path_skips_capacity_padding():
    """The ragged grouped-GEMM path runs the expert matmuls through
    `ragged_dot` on S sorted rows and never builds an [E, C, D] padded
    queue. (FLOPs can't prove this on CPU — XLA's CPU lowering of
    ragged_dot is a dense per-group loop — so the check is structural.)"""
    k = 1
    x, gw, ps = _args(k)
    fn = lambda x, gw, ps: moe_layer_grouped(  # noqa: E731
        x, gw, _expert_gemms, ps, capacity_factor=CF, top_k=k)[0]
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2)))(x, gw, ps)

    prims = []
    padded = []

    def walk(j):
        for eqn in j.eqns:
            prims.append(eqn.primitive.name)
            for v in eqn.outvars:
                shape = tuple(getattr(getattr(v, "aval", None), "shape", ()))
                if shape == (E, C, D):
                    padded.append(shape)
            for p in eqn.params.values():
                for sub in _sub_jaxprs(p):
                    walk(sub)

    walk(jaxpr.jaxpr)
    assert prims.count("ragged_dot_general") >= 3  # fwd gate/up/down
    assert not padded, "ragged path built a capacity-padded [E, C, D] queue"
