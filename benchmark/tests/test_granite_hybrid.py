"""The hybrid configuration's benchmark files on the CPU: the configuration
held to ITS published widths against the catalog's row, the model arithmetic,
the reference against a second spelling of one Mamba layer (a NumPy float64
loop), the driver end to end at a tiny size, and the three readers on a small
recorded list of operations. No timing is asserted or reported."""
import json
import os

import numpy as np
import pytest

from benchmark import common, hybrid_spans
from benchmark import model_math_granite_hybrid as mm

CONFIG = common.load_json(f"{common.BENCH_DIR}/configs/granite-4.0-h-micro.serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config`, as this PR read it: kept here so that the test
# holds where the guide is not installed
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192,
    "layer_types": ["attention" if i % 10 == 5 else "mamba" for i in range(40)],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


def _catalog_row():
    if not os.path.exists(CATALOG):
        return None
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "granite-4.0-h-micro")


# ------------------------------------------------------- the configuration
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_key(key):
    """Key by key: as published, or listed in `reduced` with the published
    value under `published`; only the table span is reduced."""
    assert CONFIG["reduced"] == ["max_position_embeddings"]
    if key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == PUBLISHED[key] and CONFIG[key] == 4096
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_published_block_is_the_catalog_row():
    row = _catalog_row()
    if row is None:
        pytest.skip("the model-configs catalog is not installed here")
    assert row["config"] == PUBLISHED
    entry = next(c for c in common.load_benchmark()["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == row["source_url"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]


def test_configuration_says_what_it_assumes_and_deploys():
    assert {"time_step_limit", "ssm_state_dtype", "weights_distribution"} <= set(CONFIG["assumed"])
    assert CONFIG["driver"] == "serve_hybrid" and CONFIG["torch_dtype"] == "bfloat16"
    s = CONFIG["serve"]
    assert s["prefix_cache"] is False and s["max_new_tokens"] == 256 and s["block_size"] == 16
    assert s["n_slots"] in (16, 32) and s["why_n_slots"]
    for key in ("deployment", "weights", "departures", "why_reduced"):
        assert CONFIG[key]


def test_program_config_from_the_file():
    from benchmark.drivers.serve_hybrid import hybrid_config

    cfg = hybrid_config(CONFIG)
    assert (cfg.n_layers, cfg.n_mamba_layers, cfg.n_attn_layers) == (40, 36, 4)
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.max_seq_len) == (2048, 8192, 100352, 4096)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.conv_dim) == (
        64, 64, 128, 4352)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 1 / 64, 0.22, 8.0)
    with pytest.raises(common.BenchFailure):
        hybrid_config({**CONFIG, "position_embedding_type": "rope"})


# ------------------------------------------------------ the model arithmetic
def test_model_arithmetic_from_the_shapes():
    assert mm.mamba_layer_params(CONFIG) == 76_182_976       # 76.2 M
    assert mm.attn_layer_params(CONFIG) == 60_821_504        # 60.8 M
    assert mm.num_params(CONFIG) == 3_191_396_096            # 3.19 B
    assert mm.weight_bytes(CONFIG) == mm.decode_read_bytes(CONFIG) == 6_382_792_192
    assert mm.state_bytes_per_lane(CONFIG) == 36 * (3 * 4352 * 2 + 64 * 64 * 128 * 4)
    assert mm.update_bytes_per_lane_step(CONFIG) == 2 * mm.state_bytes_per_lane(CONFIG)
    assert mm.kv_bytes_per_token(CONFIG) == 8192
    per_layer = 2 * 256 * 128 + 2 * 256 * 4096 + 2 * 2 * 128 * 4096
    assert mm.scan_flops_per_token(CONFIG) == 36 * per_layer
    assert mm.scan_bytes_per_token(CONFIG) == 36 * (4352 * 2 + 64 * 4 + 4096 * 2)
    assert mm.forward_flops_per_token(CONFIG, 100) == (
        2.0 * mm.matmul_params(CONFIG) + 36 * per_layer + 4.0 * 4 * 32 * 64 * 100)


def test_arithmetic_agrees_with_the_program():
    """The counts above are the yardstick's own; the program's parameter tree
    and its engine's per-lane constant come to the same numbers."""
    from benchmark.drivers.serve_hybrid import hybrid_config
    from ray_tpu.models import granite_hybrid, granite_hybrid_decode

    cfg = hybrid_config(CONFIG)
    assert granite_hybrid.num_params(cfg) == mm.num_params(CONFIG)
    assert granite_hybrid_decode.state_bytes_per_lane(cfg) == mm.state_bytes_per_lane(CONFIG)


# ----------------------------------- the reference, spelled a second time
def test_reference_mamba_layer_against_a_numpy_loop():
    """One Mamba mixer over 6 positions in NumPy float64, one Python loop a
    position, from the equations of ISSUE 29 section 1."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_granite_hybrid as R
    from benchmark import weights_granite_hybrid as W
    from ray_tpu.models.granite_hybrid import GraniteHybridConfig

    cfg = GraniteHybridConfig.tiny(dtype=jnp.float32)
    w = jax.tree.map(lambda a: np.asarray(a, np.float64),
                     W.make_mamba_layer(jax.random.PRNGKey(3), cfg))
    T, H, P, N = 6, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    di = H * P
    a = np.random.default_rng(0).normal(size=(T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(R.mamba_mixer(jnp.asarray(a[None], jnp.float32),
                                       jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), w), cfg))[0]

    silu = lambda v: v / (1.0 + np.exp(-v))  # noqa: E731
    zxbcdt = a @ np.concatenate([w["in_proj"], w["dt_proj"]], axis=1)
    z, xBC, dt = zxbcdt[:, :di], zxbcdt[:, di:di + di + 2 * N], zxbcdt[:, di + di + 2 * N:]
    conv = np.zeros_like(xBC)
    for t in range(T):
        acc = w["conv_b"].copy()
        for j in range(4):  # tap j sees position t - 3 + j; zeros before the start
            if t - 3 + j >= 0:
                acc += w["conv_w"][j] * xBC[t - 3 + j]
        conv[t] = silu(acc)
    h = np.zeros((H, P, N))
    y = np.zeros((T, H, P))
    for t in range(T):
        x_t, B_t, C_t = conv[t, :di].reshape(H, P), conv[t, di:di + N], conv[t, di + N:]
        delta = np.log1p(np.exp(dt[t] + w["dt_bias"]))
        A = -np.exp(w["A_log"])
        for i in range(H):
            h[i] = np.exp(delta[i] * A[i]) * h[i] + delta[i] * np.outer(x_t[i], B_t)
            y[t, i] = h[i] @ C_t + w["D"][i] * x_t[i]
    g = y.reshape(T, di) * silu(z)
    g = g / np.sqrt((g * g).mean(-1, keepdims=True) + cfg.rms_eps) * w["gate_norm"]
    want = g @ w["out_proj"]
    # float32 against float64: 1e-5 of the largest value
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


# -------------------------------------------------- the driver's CPU path
@pytest.fixture(scope="module")
def cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=1)
    yield
    ray_tpu.shutdown()


CLOSED = {"kind": "serve_closed", "clients": 6, "max_requests": 64,
          "prompt_len": {"dist": "uniform", "min": 33, "max": 64},
          "output_len": {"dist": "uniform", "min": 2, "max": 6}}


def _cell():
    cfg = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.hybrid.json")
    return {"name": "test", "chips": 1, "config": "tiny.hybrid", "traffic": "closed",
            "config_file": cfg, "traffic_file": CLOSED}


def test_hybrid_driver_end_to_end(cluster):
    from benchmark.drivers import serve_hybrid

    out = serve_hybrid.measure(_cell(), seed=2**31 + 29, seconds=3.0, trace=False,
                               t_process_start=common.clock())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    engine = out["facts"]["engine"]
    assert engine["tokens_out"] > 0
    assert engine["state_lane_steps"] == engine["useful_slot_steps"] > 0
    assert out["facts"]["state_bytes"] > 0 and out["facts"]["lanes"] == 4
    assert out["device"]["platform"] == "cpu"  # never reported as a device metric


def test_hybrid_control_comes_out_incorrect(cluster):
    """The int8 control at the tiny size: the comparison that decides
    `correct` tells rounded weights from sound ones."""
    from benchmark.drivers import serve_hybrid

    out = serve_hybrid.measure(_cell(), seed=2**31 + 30, seconds=2.0, trace=False,
                               t_process_start=common.clock(), lower_precision="int8")
    gap = next(c for c in out["checks"] if c["name"] == "logit_gap_mean")
    assert not gap["ok"] and gap["value"] > 5 * gap["limit"]


# ------------------------------------------- the readers on a recorded list
def test_scope_of_takes_the_innermost():
    base = "jit(macro_step_slots_paged)/while/body/cond/branch_1_fun/"
    assert hybrid_spans.scope_of(base + "decode_chunk/while/body/ssm_update/mul") == "ssm_update"
    assert hybrid_spans.scope_of(base + "admit_prefill/while/body/ssm_scan/exp") == "ssm_scan"
    assert hybrid_spans.scope_of(base + "admit_prefill/ssm_proj/dot_general") == "ssm_proj"
    assert hybrid_spans.scope_of(base + "decode_chunk/attn_mix/dot_general") == "attn_mix"
    assert hybrid_spans.scope_of(base + "decode_chunk/dot_general") == ""
    # the program's scope names are these, and none of them holds a macro-step half's
    from ray_tpu.models import granite_hybrid as G

    assert (G.SCOPE_SCAN, G.SCOPE_UPDATE, G.SCOPE_PROJ, G.SCOPE_ATTN) == hybrid_spans.SCOPES
    assert not any(half in s for s in hybrid_spans.SCOPES
                   for half in ("admit_prefill", "decode_chunk"))


def _recorded():
    """Two macro-step executions inside a 1 s window (one before it, and one
    after it that the trace's end cuts), each paired with a dispatch;
    operations of 10 ms each."""
    dispatch = lambda t, seq, **kw: ("engine.dispatch", t, 0.001, {"seq": seq, **kw})  # noqa: E731
    trace = {
        "window": (1.0, 2.0),
        "spans": [dispatch(0.40, 0, steps=8, state_lanes=100, prompt_tokens=300),
                  dispatch(1.05, 1, steps=10, state_lanes=300, prompt_tokens=500),
                  dispatch(1.50, 2, steps=12, state_lanes=340, prompt_tokens=0),
                  dispatch(1.90, 3, steps=64, state_lanes=2048, prompt_tokens=0)],
        "modules": [("jit_macro_step_slots_paged", 0.5, 0.2),
                    ("jit_macro_step_slots_paged", 1.1, 0.3),
                    ("jit_other", 1.45, 0.01),
                    ("jit_macro_step_slots_paged", 1.6, 0.2),
                    ("jit_macro_step_slots_paged", 1.95, 0.2)],
    }
    ops = sorted([(0.55, 0.01, "ssm_update"),                       # before the window
                  (1.10, 0.01, "ssm_scan"), (1.12, 0.01, "ssm_scan"), (1.14, 0.01, "ssm_proj"),
                  (1.20, 0.01, "ssm_update"), (1.22, 0.01, "attn_mix"), (1.24, 0.01, ""),
                  (1.455, 0.01, "ssm_update"),                      # not in a macro-step
                  (1.60, 0.01, "ssm_update"), (1.62, 0.01, "ssm_update"),
                  (1.64, 0.01, "ssm_proj"),
                  (1.96, 0.01, "ssm_update")])                     # after the window
    return trace, ops


def test_view_sums_scopes_over_the_windows_executions():
    trace, ops = _recorded()
    v = hybrid_spans.view(trace, ops)
    assert v["executions"] == v["paired_executions"] == 2
    assert v["macro_step_s"] == pytest.approx(0.5)
    assert v["window"] == pytest.approx({"ssm_scan": 0.02, "ssm_update": 0.03,
                                         "ssm_proj": 0.02, "attn_mix": 0.01})
    assert v["paired"] == v["window"]
    assert (v["paired_state_lanes"], v["paired_steps"], v["paired_prompt_tokens"]) == (640, 22, 500)
    assert hybrid_spans.view({**trace, "window": None}, ops) is None
    # the trace's last execution is cut by the profiler's stop: its dispatch's
    # 12 steps and 340 lane-steps are not counted over the operations it shows (B2)
    cut = hybrid_spans.view({**trace, "modules": trace["modules"][:-1]}, ops)
    assert (cut["executions"], cut["paired_executions"]) == (2, 1)
    assert (cut["paired_state_lanes"], cut["paired_steps"]) == (300, 10)
    assert cut["paired"]["ssm_update"] == pytest.approx(0.01) and cut["window"] == v["window"]


@pytest.mark.parametrize("metric", ["programs.ssm_share_pct", "kernels.ssm_update_roofline_pct",
                                    "kernels.ssm_scan_roofline_pct"])
def test_readers_on_the_recorded_list(metric, monkeypatch):
    trace, ops = _recorded()
    recorded = hybrid_spans.view(trace, ops)
    monkeypatch.setattr(hybrid_spans, "hybrid_view", lambda facts: recorded)
    peaks = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"facts": {"state_bytes": mm.state_bytes_per_lane(CONFIG)}, "config": CONFIG,
           "peaks": peaks}
    got = common.load_module("layer_metrics", metric).read(ctx)
    if metric == "programs.ssm_share_pct":
        assert got["value"] == pytest.approx(100.0 * 0.07 / 0.5)
        assert got["attn_mix_s"] == pytest.approx(0.01)
    elif metric == "kernels.ssm_update_roofline_pct":
        least = 640 * 2 * mm.state_bytes_per_lane(CONFIG) / 819e9
        assert got["value"] == pytest.approx(100.0 * least / 0.03) and got["bound"] == "memory"
    else:
        least = max(500 * mm.scan_flops_per_token(CONFIG) / 197e12,
                    500 * mm.scan_bytes_per_token(CONFIG) / 819e9)
        assert got["value"] == pytest.approx(100.0 * least / 0.02)
    # a program without the scopes, or an untraced run: nothing to read
    empty = hybrid_spans.view(trace, [(s, d, "") for s, d, _ in ops])
    monkeypatch.setattr(hybrid_spans, "hybrid_view", lambda facts: empty)
    assert common.load_module("layer_metrics", metric).read(ctx) is None
    monkeypatch.setattr(hybrid_spans, "hybrid_view", lambda facts: None)
    assert common.load_module("layer_metrics", metric).read(ctx) is None
