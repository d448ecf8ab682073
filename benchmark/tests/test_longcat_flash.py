"""The LongCat-Flash configuration's benchmark files on the CPU: the
configuration held to ITS published widths against the catalog's row, the
model arithmetic against the figures of ISSUE 45 and against the program's
parameter tree, the reference against the program, the driver end to end at a
tiny size, and the new readers on a small hand-built trace. No timing is
asserted or reported."""
import json
import os

import numpy as np
import pytest

from benchmark import common, longcat_flash_spans as S
from benchmark import model_math_longcat_flash as mm

CONFIG = common.load_json(f"{common.BENCH_DIR}/configs/longcat-flash-chat.serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config`, as this PR read it: kept here so that the test
# holds where the guide is not installed
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144, "ffn_hidden_size": 12288,
    "expert_ffn_hidden_size": 2048, "num_layers": 28, "num_attention_heads": 64,
    "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12}
REDUCED = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384,
           "max_position_embeddings": 1024}
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "agent-fanout-generate"


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
            row = next(r for r in map(json.loads, f) if r["name"] == "LongCat-Flash-Chat")
        assert row["config"] == PUBLISHED and row["source_url"] == CONFIG["source"]
    bench = common.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/longcat-flash-chat.serve.json"
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert (CONFIG["router_num_experts"], CONFIG["held_experts_first"]) == (512, 0)
    assert {"layer", "mla_scales", "routing", "zero_experts", "rope", "torch_dtype",
            "weights_distribution"} <= set(CONFIG["assumed"])
    assert "32 chips share each layer" in CONFIG["deployment"]
    assert "identity term whole on every chip" in CONFIG["deployment"]
    assert CONFIG["departures"]["program"] and CONFIG["departures"]["reference"]
    assert sorted(CONFIG["why_reduced"]) == sorted(REDUCED)
    s = CONFIG["serve"]
    assert (s["n_slots"], s["block_size"], s["max_new_tokens"], s["prefix_cache"],
            s["continuous"]) == (32, 16, 256, False, True)
    assert s["why_prefix_cache"] and CONFIG["check"]["why"] and CONFIG["weights"]
    check = CONFIG["check"]
    assert (check["gap_mean_limit"], check["gap_p90_limit"], check["gap_p99_limit"]) == (0.0025, 0.005, 0.063)
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert bench["configs"][-1]["name"] == CONFIG["name"] and bench["workloads"][-1]["name"] == CELL


def test_the_cell_and_its_traffic_are_the_issues():
    from benchmark import traffic
    from benchmark.drivers.serve import macro_variants

    cell = common.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat-flash-chat.serve", "fanout-generate.closed", 1)
    t = cell["traffic_file"]
    assert (t["kind"], t["clients"], t["stagger_s"], t["think_s"], t["profile_seed"],
            t["sampling"]) == ("serve_closed", 64, 0.05, 0.05, 45, "greedy")
    wide = common.load_json(f"{common.BENCH_DIR}/traffic/generate-wide.closed.json")
    # generate-wide.closed's lengths on purpose: the two 32-lane cells differ by architecture
    assert t["prompt_len"] == wide["prompt_len"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.45, "min": 129, "max": 512}
    assert t["output_len"] == wide["output_len"] == {"dist": "uniform", "min": 64, "max": 256}
    assert {"tok_s", "setup_s"} <= {m["name"] for m in cell["end_to_end"]}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"kernels.mla_pair_decode_roofline_pct", "kernels.ffn_dense_decode_roofline_pct",
            "kernels.moe_zero_decode_roofline_pct", "programs.shortcut_share_pct",
            "programs.prefill_share_pct", "programs.decode_step_ms.tok_s",
            "programs.macro_step_ms.tok_s", "engine.lane_occupancy_pct", "engine.starved_idle_pct",
            "engine.vacant_lane_pct", "engine.blocked_lane_pct", "engine.admit_real_pct",
            "device.idle_pct.serve", "entry.deploy_s"} <= names
    assert all(m["moves"] in {e["name"] for e in cell["end_to_end"]} for m in cell["per_layer"])
    for m in cell["per_layer"]:
        assert os.path.isfile(f"{common.BENCH_DIR}/layer_metrics/{m['name']}.py")
    plan = traffic.plan(t, 2**31 + 5, 40.0, CONFIG["vocab_size"])
    p = np.array([len(r["prompt"]) for r in plan["requests"]])
    o = np.array([r["max_new_tokens"] for r in plan["requests"]])
    assert 129 <= p.min() < p.max() <= 512 and 64 <= o.min() < o.max() <= 256
    assert 230 < np.median(p) < 290
    assert max(max(r["prompt"]) for r in plan["requests"][:64]) < 16384  # inside the slice
    variants = macro_variants(t, CONFIG["serve"], CONFIG["max_position_embeddings"])
    assert len(variants) == 13 and variants[0] == [32, 512] and variants[-1] == [1, 16]


def test_program_config_from_the_file():
    from benchmark.drivers.serve_longcat_flash import longcat_flash_config

    cfg = longcat_flash_config(CONFIG)
    # every published width
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.d_ff, cfg.moe_d_ff, cfg.n_experts, cfg.top_k,
            cfg.route_scale) == (6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 768, 12, 6.0)
    assert (cfg.n_layers, cfg.n_sublayers, cfg.n_routed_experts, cfg.n_zero_experts,
            cfg.held_experts, cfg.vocab_size, cfg.max_seq_len) == (4, 8, 512, 256, (0, 16), 16384, 1024)
    assert (cfg.mla_q_scale, round(cfg.mla_kv_scale, 4), cfg.rope_theta, cfg.rms_eps) == (
        2.0, 3.4641, 1e7, 1e-5)
    with pytest.raises(common.BenchFailure):
        longcat_flash_config({**CONFIG, "zero_expert_type": "copy"})
    with pytest.raises(common.BenchFailure):
        longcat_flash_config({**CONFIG, "q_lora_rank": None})


# ------------------------------------------------------ the model arithmetic
def test_model_arithmetic_is_the_issues():
    """ISSUE 45's figures: 90.57 M an attention, 226.49 M a dense FFN, 4.72 M
    the router, 37.75 M an expert; a double layer without experts 638.9 M,
    with 16 held 1,242.9 M = 2.486 GB, with all 512 19.97 B = 39.93 GB (ISSUE 45
    writes that count as GB); 10.35 GB of
    weights; 560.66 B whole; 10,240 B a token in the padded pool."""
    assert mm.attn_matmul_params(CONFIG) == (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576
                                             + 512 * 64 * 256 + 8192 * 6144) == 90_570_752
    assert mm.dense_ffn_params(CONFIG) == 226_492_416 and mm.expert_params(CONFIG) == 37_748_736
    assert mm.router_params(CONFIG) == 6144 * 768 and mm.router_width(CONFIG) == 768
    assert round(mm.layer_params(CONFIG, 0) / 1e6, 1) == 638.9
    assert round(mm.layer_params(CONFIG) * 2 / 1e9, 3) == 2.486
    assert round(mm.layer_params(CONFIG, 512) / 1e9, 2) == 19.97  # parameters: 39.93 GB
    assert mm.num_params(CONFIG) == 5_172_749_312 and round(mm.weight_bytes(CONFIG) / 1e9, 2) == 10.35
    whole = {**CONFIG, **CONFIG["published"], "router_num_experts": 512}
    assert round(mm.num_params(whole) / 1e9, 2) == 560.66
    assert mm.latent_bytes_per_token(CONFIG) == 9216  # the model's; 8 x 640 x 2 = 10,240 padded
    assert mm.mla_pair_decode_bytes(CONFIG, 1000, 3) == 1000 * 9216 + 3 * 8 * 512 * 64 * 256 * 2
    assert mm.ffn_dense_decode_bytes(CONFIG, 3) == 3 * 8 * 226_492_416 * 2
    assert mm.moe_zero_decode_bytes(CONFIG, 10, 20, 3, 90) == (
        10 * 75_497_472 + 20 * 2 * 6144 * 2 + 3 * 4 * 6144 * 768 * 2 + 90 * 4 * 2 * 6144 * 2)
    assert mm.expected_held_hit(CONFIG, 32) == pytest.approx(6.33, abs=0.01)
    assert mm.real_experts_per_token(CONFIG, 2, 1) == pytest.approx(8.0)
    # a decode step's least bytes: 7.2 GB, of which the eight dense FFNs 3.6 and the attentions 1.4
    assert round(mm.decode_step_bytes(CONFIG, 6.33) / 1e9, 1) == 7.2


def test_arithmetic_agrees_with_the_program():
    from benchmark.drivers.serve_longcat_flash import longcat_flash_config
    from ray_tpu.models import longcat_flash as M

    assert M.num_params(longcat_flash_config(CONFIG)) == mm.num_params(CONFIG)
    whole = {**CONFIG, **CONFIG["published"], "router_num_experts": 512}
    assert M.num_params(M.LongcatFlashConfig()) == mm.num_params(whole)
    tiny = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.longcat_flash.json")
    assert M.num_params(longcat_flash_config(tiny)) == mm.num_params(tiny)


# ----------------------------------------------- the reference and the program
def test_reference_agrees_with_the_program_and_a_share_is_its_held_experts():
    import jax
    import jax.numpy as jnp

    from benchmark import reference_longcat_flash as R, weights_longcat_flash as W
    from benchmark.drivers.serve_longcat_flash import longcat_flash_config
    from ray_tpu.models import longcat_flash as M

    tiny = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.longcat_flash.json")
    cfg = longcat_flash_config(tiny)
    assert cfg == M.LongcatFlashConfig.tiny(dtype=jnp.float32)
    key = W.seed_key(2**31 + 45)
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
    # the held share's experts are the REAL experts 4..7, by key; an identity expert has no weights
    moe = params[W.MOE]
    _, _, k_e = W.moe_keys(W.part_keys(key, cfg)[4][1], cfg)
    assert k_e.shape[0] == cfg.n_routed_experts == 8
    np.testing.assert_array_equal(np.asarray(moe["experts"]["w_up"][1, 2]),
                                  np.asarray(W.make_expert(k_e[cfg.held_first + 2], cfg)["w_up"]))
    assert moe["experts"]["w_up"].shape[:2] == (2, 4) and moe["router"].shape == (2, 64, 12)
    # the up-projections out of the compressed spaces are drawn at d_model^-0.5
    assert np.asarray(params["layers"]["w_qb"]).std() == pytest.approx(64 ** -0.5, rel=0.05)
    assert np.asarray(params["layers"]["w_uk"]).std() == pytest.approx(64 ** -0.5, rel=0.05)
    assert np.asarray(params["layers"]["w_qa"]).std() == pytest.approx(64 ** -0.5, rel=0.05)
    assert np.asarray(params["layers"]["wo"]).std() == pytest.approx(64 ** -0.5, rel=0.05)  # 4 x 16
    # the control's rounding touches the matrices and nothing else
    rounded = W.round_to_fewer_bits(jax.tree.map(jnp.copy, params), "int8")
    for name in ("attn_norm", "ffn_norm", "q_a_norm", "kv_norm"):
        np.testing.assert_array_equal(np.asarray(rounded["layers"][name]), np.asarray(params["layers"][name]))
    np.testing.assert_array_equal(np.asarray(rounded[W.MOE]["bias"]), np.asarray(moe["bias"]))
    assert np.abs(np.asarray(rounded["layers"]["w_qb"]) - np.asarray(params["layers"]["w_qb"])).max() > 0


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
    cfg = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.longcat_flash.json")
    return {"name": "test", "chips": 1, "config": "tiny.longcat_flash", "traffic": "closed",
            "config_file": cfg, "traffic_file": CLOSED}


def test_driver_end_to_end(cluster):
    from benchmark.drivers import serve_longcat_flash

    out = serve_longcat_flash.measure(_cell(), seed=2**31 + 45, seconds=3.0, trace=False,
                                      t_process_start=common.clock())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert {"logit_gap_mean", "logit_gap_p90", "tokens_checked"} <= {c["name"] for c in out["checks"]}
    engine = out["facts"]["engine"]
    assert engine["tokens_out"] > 0 and engine["ctx_tokens"] > 0
    # a live row chooses top-3 of 12 outputs in each of 2 layers, real or identity
    assert engine["real_choices"] + engine["zero_choices"] == engine["useful_slot_steps"] * 3 * 2
    assert 0.2 < engine["zero_choices"] / (engine["useful_slot_steps"] * 6) < 0.5  # 4 of 12
    # held experts only: half of the real ones
    assert 0 < engine["expert_rows"] < engine["real_choices"]
    assert engine["expert_rows"] >= engine["experts_hit"] >= engine["expert_rows_max"] > 0
    assert out["facts"]["state_bytes"] == 0 and out["facts"]["lanes"] == 4
    assert out["device"]["platform"] == "cpu"  # never reported as a device metric


def test_control_comes_out_incorrect(cluster):
    """The int8 control at the tiny size: the comparison that decides
    `correct` tells rounded weights from sound ones."""
    from benchmark.drivers import serve_longcat_flash

    out = serve_longcat_flash.measure(_cell(), seed=2**31 + 46, seconds=2.0, trace=False,
                                      t_process_start=common.clock(), lower_precision="int8")
    gap = next(c for c in out["checks"] if c["name"] == "logit_gap_mean")
    assert not gap["ok"] and gap["value"] > 5 * gap["limit"]


# ------------------------------------------- the marks in a device trace
STACK = "jit(macro_step_slots_paged)/while/body/cond/branch_1_fun/decode_chunk/while/body/"


def test_scope_of_takes_the_innermost_and_the_flash_kernel_is_known_by_name():
    assert S.scope_of(STACK + "mla_proj/dot_general:") == "mla_proj"
    assert S.scope_of(STACK + "mla_proj/mla_absorb/dot_general:") == "mla_absorb"
    assert S.scope_of(STACK + "mla_ctx/while/body/dynamic_slice:") == "mla_ctx"
    assert S.scope_of(STACK + "ffn_dense/dot_general:") == "ffn_dense"
    assert S.scope_of(STACK + "moe_zero/mul:") == "moe_zero" and S.scope_of(STACK) == ""
    admit = STACK.replace("decode_chunk", "admit_prefill")
    raw = [(0.0, 0.01, "%fusion.1 = bf16[8,64]", admit + "mla_proj/dot_general:"),
           (0.02, 0.01, "%flash_fwd.8 = (bf16[1024,512,128]) custom-call(...)", ""),
           (0.03, 0.01, "%fusion.7 = bf16[8,64]", admit + "ffn_dense/dot_general:"),
           (0.04, 0.01, "%fusion.2 = bf16[8,64]", admit + "moe_experts/sort:"),
           (0.06, 0.01, "%ragged-dot.3 = bf16[8,64]", ""),
           (0.07, 0.01, "%fusion.3 = bf16[8,64]", STACK + "moe_zero/mul:"),
           (0.08, 0.01, "%ragged-dot.4 = bf16[8,64]", ""),
           (0.09, 0.01, "%copy.4 = bf16[8,64]", "")]
    assert [(half, scope) for _, _, half, scope in S.scoped(raw)] == [
        ("admit_prefill", "mla_proj"), ("admit_prefill", "mla_ctx"), ("admit_prefill", "ffn_dense"),
        ("admit_prefill", "moe_experts"), ("admit_prefill", "moe_experts"),
        ("decode_chunk", "moe_zero"), ("decode_chunk", "moe_experts"), ("", "")]


def _recorded():
    """A 1 s window that opens inside execution seq 4, two whole executions
    (seq 5, whose dispatch lies before the trace, and seq 6), a last one (seq
    7) that the trace's end cuts; operations of 10 ms as (start, duration,
    half, scope)."""
    plan = lambda seq, steps, lanes, tokens, ctx, **dev: {  # noqa: E731
        "seq": seq, "steps": steps, "lane_steps": lanes, "prompt_tokens": tokens,
        "ctx_tokens": ctx, "prompt_pairs": tokens * 100, **dev}
    dev = lambda rows, hit, most, real, zero: {  # noqa: E731
        "expert_rows": rows, "experts_hit": hit, "expert_rows_max": most,
        "real_choices": real, "zero_choices": zero}
    spans = [("engine.resolve", 1.15, 0.01, plan(4, 8, 60, 300, 9000, **dev(60, 40, 25, 1900, 980))),
             ("engine.dispatch", 1.16, 0.001, plan(6, 12, 90, 0, 40000)),
             ("engine.resolve", 1.45, 0.02, plan(5, 10, 80, 5000, 30000, **dev(80, 70, 30, 2560, 1280))),
             ("engine.dispatch", 1.48, 0.001, plan(7, 8, 64, 2000, 20000)),
             ("engine.resolve", 1.75, 0.01, plan(6, 12, 90, 0, 40000, **dev(90, 80, 28, 2880, 1440)))]
    modules = [("jit_macro_step_slots_paged(1)", 0.85, 0.30), ("jit_macro_step_slots_paged(1)", 1.15, 0.30),
               ("jit_macro_step_slots_paged(1)", 1.45, 0.30), ("jit_macro_step_slots_paged(1)", 1.75, 0.30)]
    a, d = "admit_prefill", "decode_chunk"
    ops = [(1.05, 0.01, d, "ffn_dense"),                                    # seq 4 (not counted)
           (1.16, 0.01, a, "mla_proj"), (1.17, 0.01, a, "mla_ctx"), (1.18, 0.01, a, "ffn_dense"),
           (1.19, 0.01, a, "moe_experts"), (1.20, 0.01, a, "moe_zero"), (1.21, 0.01, a, ""),
           (1.30, 0.01, d, "mla_proj"), (1.31, 0.01, d, "mla_absorb"), (1.32, 0.01, d, "mla_ctx"),
           (1.33, 0.01, d, "ffn_dense"), (1.34, 0.01, d, "ffn_dense"), (1.35, 0.01, d, "moe_route"),
           (1.36, 0.01, d, "moe_experts"), (1.37, 0.01, d, "moe_zero"), (1.38, 0.01, d, ""),  # seq 5
           (1.50, 0.01, d, "mla_ctx"), (1.51, 0.01, d, "ffn_dense"), (1.52, 0.01, d, "moe_experts"),
           (1.53, 0.01, d, "moe_zero"),                                     # seq 6
           (1.80, 0.01, d, "ffn_dense"),                                    # seq 7 (cut)
           (2.20, 0.01, d, "ffn_dense")]                                    # outside a macro-step
    return {"window": (1.0, 2.0), "spans": spans, "modules": modules}, sorted(ops)


def test_view_sums_scopes_by_half_and_counts_the_whole_executions_by_their_resolve():
    trace, ops = _recorded()
    v = S.view(trace, ops)
    assert v["executions"] == 4 and v["counted_executions"] == 2
    assert v["macro_step_s"] == pytest.approx(1.2)
    w, c = v["window"], v["counted"]
    assert w["decode_chunk/ffn_dense"] == pytest.approx(0.05)   # not the one outside a macro-step
    assert c["decode_chunk/ffn_dense"] == pytest.approx(0.03)   # nor seq 4's, nor seq 7's
    assert c["decode_chunk/mla_ctx"] == pytest.approx(0.02) and c["decode_chunk/mla_absorb"] == pytest.approx(0.01)
    assert c["decode_chunk/moe_zero"] == pytest.approx(0.02)
    assert w["admit_prefill/all"] == pytest.approx(0.06) and c["decode_chunk/all"] == pytest.approx(0.13)
    assert (v["counted_steps"], v["counted_lane_steps"], v["counted_prompt_tokens"],
            v["counted_ctx_tokens"]) == (22, 170, 5000, 70000)
    assert (v["counted_experts_hit"], v["counted_expert_rows"], v["counted_expert_rows_max"],
            v["counted_real_choices"], v["counted_zero_choices"]) == (150, 170, 58, 5440, 2720)
    assert S.view({**trace, "window": None}, ops) is None


NEW_METRICS = ["kernels.mla_pair_decode_roofline_pct", "kernels.ffn_dense_decode_roofline_pct",
               "kernels.moe_zero_decode_roofline_pct", "programs.shortcut_share_pct"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_on_the_recorded_trace(metric, monkeypatch):
    trace, ops = _recorded()
    recorded = S.view(trace, ops)
    monkeypatch.setattr(S, "longcat_flash_view", lambda facts: recorded)
    ctx = {"facts": {}, "config": CONFIG, "peaks": PEAKS}
    got = common.load_module("layer_metrics", metric).read(ctx)
    if metric == "kernels.mla_pair_decode_roofline_pct":
        least = (70000 * 9216 + 22 * 8 * 512 * 64 * 256 * 2) / 819e9
        assert got["value"] == pytest.approx(100.0 * least / 0.03) and got["bound"] == "memory"
        assert got["ctx_tokens_a_lane_step"] == pytest.approx(70000 / 170)
    elif metric == "kernels.ffn_dense_decode_roofline_pct":
        least = 22 * 8 * 226_492_416 * 2 / 819e9
        assert got["value"] == pytest.approx(100.0 * least / 0.03) and got["bound"] == "memory"
    elif metric == "kernels.moe_zero_decode_roofline_pct":
        least = (150 * 75_497_472 + 170 * 2 * 6144 * 2 + 22 * 4 * 6144 * 768 * 2
                 + 170 * 4 * 2 * 6144 * 2) / 819e9
        assert got["value"] == pytest.approx(100.0 * least / 0.05) and got["bound"] == "memory"
        assert got["held_hit_a_layer_step"] == pytest.approx(150 / 88)
        assert got["real_experts_a_token"] == pytest.approx(8.0)
    else:
        assert got["value"] == pytest.approx(100.0 * 0.05 / 0.15)   # the window's executions
        assert got["mla_share_pct"] == pytest.approx(100.0 * 0.04 / 0.15)
        assert got["ffn_dense_share_pct"] == pytest.approx(100.0 * 0.05 / 0.15)
        assert got["admit_share_of_macro_steps_pct"] == pytest.approx(100.0 * 0.06 / 1.2)
        assert got["real_choice_share"] == pytest.approx(2 / 3)
        assert got["real_experts_a_token"] == pytest.approx(8.0)
    # a program without the scopes (the parent, another model), or an untraced run: nothing to read
    empty = S.view(trace, [(s, d, half, "") for s, d, half, _ in ops])
    monkeypatch.setattr(S, "longcat_flash_view", lambda facts: empty)
    assert common.load_module("layer_metrics", metric).read(ctx) is None
    monkeypatch.setattr(S, "longcat_flash_view", lambda facts: None)
    assert common.load_module("layer_metrics", metric).read(ctx) is None


def test_the_accepted_step_readers_read_this_cells_trace_unedited(monkeypatch):
    """`programs.decode_step_ms.tok_s` (PR 39's reader over `sarvam_mla_spans`)
    needs the two halves and the resolve spans' `steps` and no scope of its
    own model's, so the cell joins its `workloads`."""
    from benchmark import sarvam_mla_spans

    trace, ops = _recorded()
    theirs = sarvam_mla_spans.view(trace, [(s, d, half, scope if scope in sarvam_mla_spans.SCOPES else "")
                                           for s, d, half, scope in ops])
    monkeypatch.setattr(sarvam_mla_spans, "mla_view", lambda facts: theirs)
    got = common.load_module("layer_metrics", "programs.decode_step_ms.tok_s").read({"facts": {}})
    assert got["value"] == pytest.approx(1e3 * 0.13 / 22) and got["steps"] == 22
