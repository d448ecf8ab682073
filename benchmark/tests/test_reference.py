"""The plain reference agrees with models/llama.py at a tiny size in float32,
and a lower-precision system fails both comparisons at that size: the control
of `correct`, kept as a test (its twin at the cells' own size is
benchmark/control.py, run on the chip)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, weights
from ray_tpu.models import llama

CFG = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise", remat=False,
                             rope_theta=1e6, n_layers=3)
SEEDS = [3, 2**31 + 9, 77]
# tiny-size limits, set as the cells' are (PERF.md section 2): above what the
# sound system reads here, below what the controls read
GAP_LIMIT, GRAD_LIMIT = 1e-4, 1e-3


T, N_OUT, ROWS = 64, 16, 24


@jax.jit
def _forward(params, tokens):
    with jax.default_matmul_precision("highest"):
        return llama.forward(params, tokens, CFG)


def _gap_mean(seed, params):
    """Greedy decoding through the program's forward pass (padded to one
    shape: the mask is causal, so the padding cannot be seen), then the
    reference over prompt + emitted tokens."""
    rng = np.random.default_rng(seed)
    first = rng.integers(8, T - N_OUT, ROWS)
    toks = np.zeros((ROWS, T), np.int32)
    for i, n in enumerate(first):
        toks[i, :n] = rng.integers(0, CFG.vocab_size, n)
    for step in range(N_OUT):
        logits = _forward(params, jnp.asarray(toks))
        at = first + step - 1
        toks[np.arange(ROWS), at + 1] = np.asarray(
            jnp.argmax(logits[np.arange(ROWS), at], axis=-1))
    gaps, _ = reference.logit_gaps(weights.seed_key(seed), jnp.asarray(toks),
                                   jnp.asarray(first, jnp.int32),
                                   jnp.full((ROWS,), N_OUT, jnp.int32), CFG, N_OUT)
    return reference.summarize_gaps(gaps)


def _grad_err(seed, params):
    toks = np.random.default_rng(seed).integers(0, CFG.vocab_size, (2, 65)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(p, {"tokens": jnp.asarray(toks)}, CFG))(params)
    ref_loss, ref_gnorm, rel = reference.grad_check(weights.seed_key(seed), toks, CFG, grads)
    return float(loss), ref_loss, rel


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_system_agrees_with_the_reference(seed):
    params = weights.init_params(weights.seed_key(seed), CFG)
    g = _gap_mean(seed, params)
    assert g["tokens_checked"] == ROWS * N_OUT and g["gap_mean"] <= GAP_LIMIT
    loss, ref_loss, rel = _grad_err(seed, params)
    assert abs(loss - ref_loss) < 1e-5 and rel <= GRAD_LIMIT


@pytest.mark.parametrize("kind", ["int8", "fp8"])
@pytest.mark.parametrize("seed", SEEDS)
def test_lower_precision_fails_both_comparisons(seed, kind):
    params = weights.round_to_fewer_bits(weights.init_params(weights.seed_key(seed), CFG), kind)
    assert _gap_mean(seed, params)["gap_mean"] > 3 * GAP_LIMIT
    assert _grad_err(seed, params)[2] > 3 * GRAD_LIMIT


def test_weights_are_the_seeds_own_and_in_one_program():
    a = weights.init_params(weights.seed_key(2**31 + 9), CFG)
    b = weights.init_params(weights.seed_key(2**31 + 9), CFG)
    c = weights.init_params(weights.seed_key(2**31 + 10), CFG)
    assert all(bool((x == y).all()) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool((a["lm_head"] == c["lm_head"]).all())
    stock = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), CFG))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), a) == jax.tree.map(
        lambda x: (x.shape, x.dtype), stock)
    assert float(jnp.std(a["layers"]["wq"])) == pytest.approx(CFG.d_model ** -0.5, rel=0.05)
