"""The `qwen3_next` configuration's benchmark files on the CPU: the
configuration held to ITS published widths against the catalog's row, the
model arithmetic against the figures of ISSUE 43 and against the program's
parameter tree, the reference against the program, the driver end to end at a
tiny size, and the new readers on a small hand-built trace. No timing is
asserted or reported."""
import json
import os

import numpy as np
import pytest

from benchmark import common, qwen3_next_spans as S
from benchmark import model_math_qwen3_next as mm

CONFIG = common.load_json(f"{common.BENCH_DIR}/configs/qwen3-next-80b-a3b.serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config`, as this PR read it: kept here so that the test
# holds where the guide is not installed
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 8, "num_experts": 128, "vocab_size": 37984,
           "max_position_embeddings": 8192}
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}


# ------------------------------------------------------- the configuration
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_key(key):
    """Key by key: as published, or listed in `reduced` with the published
    value under `published`; depth, the held experts, the vocabulary's slice
    and the table span are all that is reduced, and no width."""
    assert sorted(CONFIG["reduced"]) == sorted(REDUCED)
    if key in REDUCED:
        assert CONFIG["published"][key] == PUBLISHED[key] and CONFIG[key] == REDUCED[key]
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_published_block_is_the_catalog_row_and_the_file_says_what_it_assumes():
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["config"] == PUBLISHED and row["source_url"] == CONFIG["source"]
    bench = common.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/qwen3-next-80b-a3b.serve.json"
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert (CONFIG["router_num_experts"], CONFIG["held_experts_first"],
            CONFIG["linear_chunk_size"]) == (512, 0, 64)
    assert {"layer", "linear_attention", "linear_chunk_size", "state_precision", "full_attention",
            "rope", "moe", "layouts", "small_parameters", "embedding_and_head", "mtp",
            "torch_dtype", "weights_distribution"} <= set(CONFIG["assumed"])
    assert "four chips of one v5e host share each layer" in CONFIG["deployment"]
    assert "four pipeline stages of 12 layers" in CONFIG["deployment"]
    assert CONFIG["departures"]["program"] and CONFIG["departures"]["reference"]
    assert sorted(CONFIG["why_reduced"]) == sorted(REDUCED)
    s = CONFIG["serve"]
    assert (s["n_slots"], s["block_size"], s["max_new_tokens"], s["prefix_cache"],
            s["continuous"]) == (8, 16, 192, False, True)
    assert s["why_prefix_cache"] and CONFIG["check"]["why"] and CONFIG["weights"]
    assert (CONFIG["check"]["gap_mean_limit"], CONFIG["check"]["gap_p90_limit"]) == (0.058, 0.215)
    assert len(bench["configs"]) == 6 and len(bench["workloads"]) == 8
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_the_cell_and_its_traffic_are_the_issues():
    from benchmark import traffic
    from benchmark.drivers.serve import macro_variants

    cell = common.load_cell("longdoc-summarize")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b.serve", "longdoc-summarize.closed", 1)
    t = cell["traffic_file"]
    assert (t["kind"], t["clients"], t["stagger_s"], t["think_s"], t["profile_seed"],
            t["sampling"]) == ("serve_closed", 16, 0.13, 0.05, 43, "greedy")
    longdoc = common.load_json(f"{common.BENCH_DIR}/traffic/longdoc.closed.json")
    assert t["prompt_len"] == longdoc["prompt_len"] == {
        "dist": "lognormal", "median": 2560, "sigma": 0.4, "min": 1025, "max": 4096}
    assert t["output_len"] == {"dist": "uniform", "min": 64, "max": 192}
    # `tok_s` is what is judged; the latencies are listed because over six seeds on the chip they
    # spread by 0.38 and 0.21 % of their medians, under the halves of their bounds (0.75, 0.625)
    # that a new cell is admitted under (my chip runs, PR 43): so a decode step and a macro-step
    # are read under the accepted names that move `latency_p50_ms`, not under the `.tok_s` pair
    assert {m["name"] for m in cell["end_to_end"]} == {"tok_s", "latency_p50_ms", "latency_p90_ms",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {"programs.gdn_share_pct", "kernels.gdn_update_roofline_pct",
                     "kernels.gdn_scan_roofline_pct", "kernels.moe512_decode_roofline_pct",
                     "programs.moe_share_pct", "programs.prefill_share_pct",
                     "programs.decode_step_ms", "programs.macro_step_ms",
                     "engine.lane_occupancy_pct", "engine.starved_idle_pct",
                     "engine.vacant_lane_pct", "engine.blocked_lane_pct", "engine.admit_real_pct",
                     "device.idle_pct.serve", "entry.deploy_s"}
    assert all(m["moves"] in {e["name"] for e in cell["end_to_end"]} for m in cell["per_layer"])
    assert "programs.serve_roofline_pct" not in names  # Llama's arithmetic
    for m in cell["per_layer"]:
        assert os.path.isfile(f"{common.BENCH_DIR}/layer_metrics/{m['name']}.py")
    plan = traffic.plan(t, 2**31 + 5, 40.0, CONFIG["vocab_size"])
    p = np.array([len(r["prompt"]) for r in plan["requests"]])
    o = np.array([r["max_new_tokens"] for r in plan["requests"]])
    assert 1025 <= p.min() < p.max() <= 4096 and 64 <= o.min() < o.max() <= 192
    assert 2300 < np.median(p) < 2800
    assert max(max(r["prompt"]) for r in plan["requests"][:64]) < 37984  # inside the slice
    variants = macro_variants(t, CONFIG["serve"], CONFIG["max_position_embeddings"])
    assert len(variants) == 9 and variants[0] == [8, 4096] and variants[-1] == [1, 16]


def test_program_config_from_the_file():
    from benchmark.drivers.serve_qwen3_next import qwen3_next_config
    from ray_tpu.models import qwen3_next_decode as D

    cfg = qwen3_next_config(CONFIG)
    assert (cfg.n_layers, cfg.n_linear_layers, cfg.n_full_layers) == (8, 6, 2)
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",) + (
        "linear_attention",) * 3 + ("full_attention",)
    # every published width
    assert (cfg.d_model, cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim, cfg.lin_v_dim,
            cfg.lin_conv, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rotary_dim, cfg.n_experts,
            cfg.top_k, cfg.moe_d_ff, cfg.shared_d_ff) == (
        2048, 16, 32, 128, 128, 4, 16, 2, 256, 64, 512, 10, 512, 512)
    assert (cfg.held_experts, cfg.vocab_size, cfg.max_seq_len, cfg.lin_chunk) == (
        (0, 128), 37984, 8192, 64)
    assert (cfg.route_scoring, cfg.route_norm, cfg.route_scale, cfg.rope_theta, cfg.rms_eps) == (
        "softmax", True, 1.0, 1e7, 1e-6)
    # a lane: six linear layers' float32 states and conv tails
    assert D.state_bytes_per_lane(cfg) == mm.state_bytes_per_lane(CONFIG) == 6 * (2_097_152 + 49_152)
    with pytest.raises(common.BenchFailure):
        qwen3_next_config({**CONFIG, "mlp_only_layers": [0]})
    with pytest.raises(common.BenchFailure):
        qwen3_next_config({**CONFIG, "rope_scaling": {"type": "yarn"}})


# ------------------------------------------------------ the model arithmetic
def test_model_arithmetic_is_the_issues():
    """ISSUE 43's figures: 33.72 M a linear mixer, 27.26 M an attention
    mixer, 3.146 M an expert, 3.667 B held, 79.67 B whole."""
    assert mm.linear_mixer_matmul_params(CONFIG) == 25_165_824 + 131_072 + 32_768 + 8_388_608
    assert round(mm.linear_mixer_matmul_params(CONFIG) / 1e6, 2) == 33.72
    assert mm.attn_mixer_matmul_params(CONFIG) == 16_777_216 + 2 * 1_048_576 + 8_388_608
    assert round(mm.attn_mixer_matmul_params(CONFIG) / 1e6, 2) == 27.26
    assert mm.expert_params(CONFIG) == 3 * 2048 * 512 and round(mm.expert_params(CONFIG) / 1e6, 3) == 3.146
    assert mm.router_params(CONFIG) == 2048 * 512
    assert round(mm.num_params(CONFIG) / 1e9, 3) == 3.667
    assert round(mm.published_params(CONFIG) / 1e9, 2) == 79.67
    assert round(mm.weight_bytes(CONFIG) / 1e9, 2) == 7.33
    s = mm.shapes(CONFIG)
    assert (s["Ll"], s["La"], s["conv_dim"], s["di"], s["E"], s["Er"]) == (6, 2, 8192, 4096, 128, 512)
    assert mm.update_bytes_per_lane_step(CONFIG) == 2 * 6 * (2_097_152 + 49_152)
    assert mm.kv_bytes_per_token(CONFIG) == 4096
    # the chunked rule: 6 C K + 4 C V + 6 K V a value head, 5.77 MFLOP a layer and token
    assert mm.scan_flops_per_token(CONFIG) == 6 * 32 * (6 * 64 * 128 + 4 * 64 * 128 + 6 * 128 * 128)
    assert round(mm.scan_flops_per_token(CONFIG) / 6 / 1e6, 2) == 5.77
    assert mm.expert_bytes(CONFIG) == 6_291_456
    assert mm.expert_decode_bytes(CONFIG, 10, 20) == 10 * 6_291_456 + 20 * 2 * 2048 * 2
    assert mm.expected_held_hit(CONFIG, 8) == pytest.approx(18.7, abs=0.1)


def test_arithmetic_agrees_with_the_program():
    from benchmark.drivers.serve_qwen3_next import qwen3_next_config
    from ray_tpu.models import qwen3_next as M

    assert M.num_params(qwen3_next_config(CONFIG)) == mm.num_params(CONFIG)
    assert M.num_params(M.Qwen3NextConfig()) == mm.published_params(CONFIG)
    tiny = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.qwen3_next.json")
    assert M.num_params(qwen3_next_config(tiny)) == mm.num_params(tiny)


# ----------------------------------------------- the reference and the program
def test_reference_agrees_with_the_program_and_a_share_is_its_held_experts():
    import jax
    import jax.numpy as jnp

    from benchmark import reference_qwen3_next as R, weights_qwen3_next as W
    from benchmark.drivers.serve_qwen3_next import qwen3_next_config
    from ray_tpu.models import qwen3_next as M

    tiny = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.qwen3_next.json")
    cfg = qwen3_next_config(tiny)
    assert cfg == M.Qwen3NextConfig.tiny(dtype=jnp.float32)
    key = W.seed_key(2**31 + 43)
    params = W.init_params(key, cfg)
    tokens = np.random.default_rng(0).integers(0, 512, (3, 26)).astype(np.int32)
    want = np.asarray(R.logits(key, jnp.asarray(tokens), cfg))
    got = np.asarray(M.forward(params, jnp.asarray(tokens), cfg))
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    # logit_gaps reads those logits: 0 where the emitted token is the argmax
    first, count = np.array([20, 9, 1], np.int32), np.array([6, 17, 0], np.int32)
    emitted = tokens.copy()
    for b in range(2):
        for i in range(count[b]):
            emitted[b, first[b] + i] = want[b, first[b] + i - 1].argmax()
    # the reference is causal: positions before a changed token keep their logits
    again = np.asarray(R.logits(key, jnp.asarray(emitted), cfg))
    gaps, spread = R.logit_gaps(key, jnp.asarray(emitted), jnp.asarray(first), jnp.asarray(count), cfg, 17)
    gaps = np.asarray(gaps)
    assert gaps.shape == (3, 17) and (gaps[2] == -1).all() and (gaps[0, 6:] == -1).all()
    assert gaps[0, 0] == 0.0 and gaps[1, 0] == 0.0  # the first emitted token is the argmax
    for b in range(2):
        for i in range(count[b]):
            lg = again[b, first[b] + i - 1]
            assert gaps[b, i] == pytest.approx(lg.max() - lg[emitted[b, first[b] + i]], abs=1e-4)
    assert np.asarray(spread)[0, 0] == pytest.approx(again[0, 19].std(), rel=1e-3)
    # the held share's experts are the router's experts 4..7, by key
    moe = params[W.MOE]
    _, k_e, _, _ = W.moe_keys(W.part_keys(key, cfg)[4][1], cfg)
    np.testing.assert_array_equal(np.asarray(moe["experts"]["w_up"][1, 2]),
                                  np.asarray(W.make_expert(k_e[cfg.held_first + 2], cfg)["w_up"]))
    assert moe["experts"]["w_up"].shape[:2] == (5, 4) and moe["router"].shape == (5, 64, 16)
    # the control's rounding touches the matrices and nothing else
    rounded = W.round_to_fewer_bits(jax.tree.map(jnp.copy, params), "int8")
    for name in ("dt_bias", "A_log", "conv_w", "norm", "head_norm"):
        np.testing.assert_array_equal(np.asarray(rounded[W.LINEAR][name]), np.asarray(params[W.LINEAR][name]))
    np.testing.assert_array_equal(np.asarray(rounded[W.MOE]["shared_gate"]), np.asarray(moe["shared_gate"]))
    assert np.abs(np.asarray(rounded[W.LINEAR]["in_proj"]) - np.asarray(params[W.LINEAR]["in_proj"])).max() > 0


# -------------------------------------------------- the driver's CPU path
@pytest.fixture(scope="module")
def cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=1)
    yield
    ray_tpu.shutdown()


CLOSED = {"kind": "serve_closed", "clients": 6, "max_requests": 64,
          "prompt_len": {"dist": "uniform", "min": 33, "max": 64},
          "output_len": {"dist": "uniform", "min": 8, "max": 24}}


def _cell():
    cfg = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.qwen3_next.json")
    return {"name": "test", "chips": 1, "config": "tiny.qwen3_next", "traffic": "closed",
            "config_file": cfg, "traffic_file": CLOSED}


def test_driver_end_to_end(cluster):
    from benchmark.drivers import serve_qwen3_next

    out = serve_qwen3_next.measure(_cell(), seed=2**31 + 43, seconds=3.0, trace=False,
                                   t_process_start=common.clock())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert {"logit_gap_mean", "logit_gap_p90", "tokens_checked"} <= {c["name"] for c in out["checks"]}
    engine = out["facts"]["engine"]
    assert engine["tokens_out"] > 0 and engine["state_lane_steps"] == engine["useful_slot_steps"] > 0
    # held experts only: a quarter of the router's, so fewer than top-4 pairs a row and layer
    assert 0 < engine["expert_rows"] < engine["useful_slot_steps"] * 4 * 5
    assert engine["expert_rows"] >= engine["experts_hit"] >= engine["expert_rows_max"] > 0
    assert out["facts"]["state_bytes"] == 4 * (4 * 8 * 8 * 4 + 3 * 64 * 4) and out["facts"]["lanes"] == 4
    assert out["device"]["platform"] == "cpu"  # never reported as a device metric


def test_control_comes_out_incorrect(cluster):
    """The int8 control at the tiny size: the comparison that decides
    `correct` tells rounded weights from sound ones."""
    from benchmark.drivers import serve_qwen3_next

    out = serve_qwen3_next.measure(_cell(), seed=2**31 + 44, seconds=2.0, trace=False,
                                   t_process_start=common.clock(), lower_precision="int8")
    gap = next(c for c in out["checks"] if c["name"] == "logit_gap_mean")
    assert not gap["ok"] and gap["value"] > 5 * gap["limit"]


# ------------------------------------------- the marks in a device trace
STACK = "jit(macro_step_slots_paged)/while/body/cond/branch_1_fun/decode_chunk/while/body/"


def test_scope_of_takes_the_innermost_and_our_kernels_are_known_by_name():
    assert S.scope_of(STACK + "gdn_proj/dot_general:") == "gdn_proj"
    assert S.scope_of(STACK + "gdn_update/mul:") == "gdn_update"
    assert S.scope_of(STACK + "attn_full/while/body/dynamic_slice:") == "attn_full"
    assert S.scope_of(STACK + "moe_experts/sort:") == "moe_experts" and S.scope_of(STACK) == ""
    admit = STACK.replace("decode_chunk", "admit_prefill")
    raw = [(0.0, 0.01, "%fusion.1 = bf16[8,64]", admit + "gdn_proj/dot_general:"),
           (0.01, 0.01, "%fusion.9 = bf16[8,64]", admit + "closed_call/gdn_scan/while/body/dot_general:"),
           (0.02, 0.01, "%flash_fwd.8 = (bf16[128,4096,256]) custom-call(...)", ""),
           (0.04, 0.01, "%fusion.2 = bf16[8,64]", admit + "moe_experts/sort:"),
           (0.06, 0.01, "%ragged-dot.3 = bf16[8,64]", ""),
           (0.07, 0.01, "%fusion.3 = bf16[8,64]", STACK + "gdn_proj/dot_general:"),
           (0.08, 0.01, "%gdn_update.8 = (f32[8,32,128]) custom-call(...)", ""),
           (0.09, 0.01, "%copy.4 = bf16[8,64]", "")]
    assert [(half, scope) for _, _, half, scope in S.scoped(raw)] == [
        ("admit_prefill", "gdn_proj"), ("admit_prefill", "gdn_scan"), ("admit_prefill", "attn_full"),
        ("admit_prefill", "moe_experts"), ("admit_prefill", "moe_experts"),
        ("decode_chunk", "gdn_proj"), ("decode_chunk", "gdn_update"), ("", "")]


def _recorded():
    """A 1 s window that opens inside execution seq 4, two whole executions
    (seq 5, whose dispatch lies before the trace, and seq 6), a last one (seq
    7) that the trace's end cuts; operations of 10 ms as (start, duration,
    half, scope)."""
    plan = lambda seq, steps, lanes, tokens, **dev: {  # noqa: E731
        "seq": seq, "steps": steps, "lane_steps": lanes, "state_lanes": lanes,
        "prompt_tokens": tokens, **dev}
    dev = lambda rows, hit, most: {"expert_rows": rows, "experts_hit": hit,  # noqa: E731
                                   "expert_rows_max": most}
    spans = [("engine.resolve", 1.15, 0.01, plan(4, 8, 60, 300, **dev(600, 400, 25))),
             ("engine.dispatch", 1.16, 0.001, plan(6, 12, 90, 0)),
             ("engine.resolve", 1.45, 0.02, plan(5, 10, 80, 5000, **dev(1600, 1500, 30))),
             ("engine.dispatch", 1.48, 0.001, plan(7, 8, 64, 2000)),
             ("engine.resolve", 1.75, 0.01, plan(6, 12, 90, 0, **dev(1800, 1700, 28)))]
    modules = [("jit_macro_step_slots_paged(1)", 0.85, 0.30), ("jit_macro_step_slots_paged(1)", 1.15, 0.30),
               ("jit_macro_step_slots_paged(1)", 1.45, 0.30), ("jit_macro_step_slots_paged(1)", 1.75, 0.30)]
    a, d = "admit_prefill", "decode_chunk"
    ops = [(1.05, 0.01, d, "gdn_update"),                                   # seq 4 (not counted)
           (1.16, 0.01, a, "gdn_proj"), (1.17, 0.01, a, "gdn_scan"), (1.18, 0.01, a, "gdn_scan"),
           (1.19, 0.01, a, "attn_full"), (1.20, 0.01, a, "moe_experts"), (1.21, 0.01, a, ""),
           (1.30, 0.01, d, "gdn_proj"), (1.31, 0.01, d, "gdn_update"), (1.32, 0.01, d, "moe_experts"),
           (1.33, 0.01, d, "moe_route"), (1.34, 0.01, d, ""),              # seq 5
           (1.50, 0.01, d, "gdn_update"), (1.51, 0.01, d, "attn_full"), (1.52, 0.01, d, "moe_experts"),
           (1.53, 0.01, d, "moe_shared"),                                   # seq 6
           (1.80, 0.01, d, "gdn_update"),                                   # seq 7 (cut)
           (2.20, 0.01, d, "gdn_update")]                                   # outside a macro-step
    return {"window": (1.0, 2.0), "spans": spans, "modules": modules}, sorted(ops)


def test_view_sums_scopes_by_half_and_counts_the_whole_executions_by_their_resolve():
    trace, ops = _recorded()
    v = S.view(trace, ops)
    assert v["executions"] == 4 and v["counted_executions"] == 2
    assert v["macro_step_s"] == pytest.approx(1.2)
    w, c = v["window"], v["counted"]
    assert w["admit_prefill/gdn_scan"] == c["admit_prefill/gdn_scan"] == pytest.approx(0.02)
    assert w["decode_chunk/gdn_update"] == pytest.approx(0.04)   # not the one outside a macro-step
    assert c["decode_chunk/gdn_update"] == pytest.approx(0.02)   # nor seq 4's, nor seq 7's
    assert c["decode_chunk/moe_experts"] == pytest.approx(0.02)
    assert w["admit_prefill/all"] == pytest.approx(0.06) and c["decode_chunk/all"] == pytest.approx(0.09)
    assert (v["counted_steps"], v["counted_lane_steps"], v["counted_state_lanes"],
            v["counted_prompt_tokens"]) == (22, 170, 170, 5000)
    assert (v["counted_experts_hit"], v["counted_expert_rows"], v["counted_expert_rows_max"]) == (
        3200, 3400, 58)
    assert S.view({**trace, "window": None}, ops) is None


NEW_METRICS = ["programs.gdn_share_pct", "kernels.gdn_update_roofline_pct",
               "kernels.gdn_scan_roofline_pct", "kernels.moe512_decode_roofline_pct"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_on_the_recorded_trace(metric, monkeypatch):
    trace, ops = _recorded()
    recorded = S.view(trace, ops)
    monkeypatch.setattr(S, "qwen3_next_view", lambda facts: recorded)
    ctx = {"facts": {}, "config": CONFIG, "peaks": PEAKS}
    got = common.load_module("layer_metrics", metric).read(ctx)
    if metric == "programs.gdn_share_pct":
        assert got["value"] == pytest.approx(100.0 * 0.08 / 1.2)
        assert got["attn_full_pct"] == pytest.approx(100.0 * 0.02 / 1.2)
        assert got["moe_share_pct"] == pytest.approx(100.0 * 0.05 / 1.2)
        assert got["rest_pct"] == pytest.approx(100.0 * (1.2 - 0.15) / 1.2)
    elif metric == "kernels.gdn_update_roofline_pct":
        least = 170 * 2 * 6 * (2_097_152 + 49_152) / 819e9
        assert got["value"] == pytest.approx(100.0 * least / 0.02) and got["bound"] == "memory"
    elif metric == "kernels.gdn_scan_roofline_pct":
        least = 5000 * 6 * 32 * 180_224 / 197e12
        assert got["value"] == pytest.approx(100.0 * least / 0.02) and got["bound"] == "compute"
    else:
        least = (3200 * 6_291_456 + 3400 * 2 * 2048 * 2) / 819e9
        assert got["value"] == pytest.approx(100.0 * least / 0.02) and got["bound"] == "memory"
        assert got["held_hit_a_layer_step"] == pytest.approx(3200 / (22 * 8))
        assert got["live_rows_a_step"] == pytest.approx(3400 / 2.5 / (22 * 8))
        assert got["uniform_held_hit"] == pytest.approx(18.7, abs=0.1)
    assert 0 < got["value"] < 100 or metric == "kernels.moe512_decode_roofline_pct"
    # a program without the scopes (the parent, another model), or an untraced run: nothing to read
    empty = S.view(trace, [(s, d, half, "") for s, d, half, _ in ops])
    monkeypatch.setattr(S, "qwen3_next_view", lambda facts: empty)
    assert common.load_module("layer_metrics", metric).read(ctx) is None
    monkeypatch.setattr(S, "qwen3_next_view", lambda facts: None)
    assert common.load_module("layer_metrics", metric).read(ctx) is None


def test_the_accepted_expert_share_reader_reads_this_cells_trace_unedited(monkeypatch):
    """`programs.moe_share_pct` (PR 33's reader over `afmoe_spans`) on this
    model's operations: the expert layer's three scopes are the shared
    `moe_ffn`'s and `attn_full` is Trinity's name, so the cell joins its
    `workloads`."""
    from benchmark import afmoe_spans

    trace, ops = _recorded()
    theirs = afmoe_spans.view(trace, [(s, d, half, scope if scope in afmoe_spans.SCOPES else "")
                                      for s, d, half, scope in ops])
    monkeypatch.setattr(afmoe_spans, "afmoe_view", lambda facts: theirs)
    got = common.load_module("layer_metrics", "programs.moe_share_pct").read({"facts": {}})
    assert got["value"] == pytest.approx(100.0 * 0.05 / 1.2)
    assert got["prefill_share_pct"] == pytest.approx(100.0 * 0.06 / 1.2)
