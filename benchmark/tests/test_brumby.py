"""The Brumby configuration's benchmark files on the CPU: the configuration
held to ITS published widths against the catalog's row, the model arithmetic
against hand arithmetic at the published sizes and against the program's
parameter tree, the driver end to end at a tiny size, and the new readers on a
small hand-built trace. No timing is asserted or reported, and nothing pins
the benchmark's SIZE (how many configurations, cells or metrics it has) or its
LAST entries: a later PR appends."""
import json
import os

import numpy as np
import pytest

from benchmark import brumby_spans as S, common
from benchmark import model_math_brumby as mm

CONFIG = common.load_json(f"{common.BENCH_DIR}/configs/brumby-14b-base.serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config`, as this PR read it: kept here so that the test
# holds where the guide is not installed
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
    "intermediate_size": 17408, "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 6, "max_position_embeddings": 4096}
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "longform-generate"


# ------------------------------------------------------- the configuration
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_key(key):
    """Key by key: as published, or listed in `reduced` with the published
    value under `published`; depth and the table span are all that is reduced:
    no width, head count or vocabulary size differs from the row."""
    assert sorted(CONFIG["reduced"]) == sorted(REDUCED)
    if key in REDUCED:
        assert CONFIG["published"][key] == PUBLISHED[key] and CONFIG[key] == REDUCED[key]
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_the_file_is_the_catalog_row_and_says_what_it_assumes():
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Brumby-14B-Base")
        assert row["config"] == PUBLISHED and row["source_url"] == CONFIG["source"]
    bench = common.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/brumby-14b-base.serve.json"
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    # every point the row cannot confirm, each with its origin
    assert {"note", "layer", "degree", "scale", "gate", "normaliser", "head_norms_and_rope",
            "state_precision", "retention_chunk_size", "window_keys", "torch_dtype",
            "small_parameters", "embedding_and_head", "weights_distribution"} <= set(CONFIG["assumed"])
    assert "not checked against the source's modeling file" in CONFIG["assumed"]["note"]
    assert (CONFIG["retention_chunk_size"], CONFIG["retention_eps"], CONFIG["torch_dtype"]) == (
        2048, 1e-6, "bfloat16")
    assert "four pipeline stages of ten layers" in CONFIG["deployment"]
    assert CONFIG["departures"]["program"] and CONFIG["departures"]["reference"]
    assert "STATE form from position 0" in CONFIG["departures"]["program"]
    assert sorted(CONFIG["why_reduced"]) == sorted(REDUCED)
    s = CONFIG["serve"]
    assert (s["n_slots"], s["block_size"], s["max_new_tokens"], s["prefix_cache"], s["continuous"]) == (
        16, 16, 512, False, True)
    assert s["why_n_slots"] and s["why_prefix_cache"]
    assert CONFIG["check"]["why"] and CONFIG["weights"] and CONFIG["driver"] == "serve_brumby"
    assert (CONFIG["check"]["gap_mean_limit"], CONFIG["check"]["gap_p99_limit"]) == (0.003, 0.067)
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG["name"]]
    assert [(w["name"], w["chips"]) for w in cells] == [(CELL, 1)]


def test_the_cell_and_its_traffic_are_the_issues():
    from benchmark import traffic
    from benchmark.drivers.serve import macro_variants

    cell = common.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b-base.serve", "longform-generate.closed", 1)
    assert len(cell["why"]) <= 200
    t = cell["traffic_file"]
    lanes = CONFIG["serve"]["n_slots"]
    assert (t["kind"], t["clients"], t["stagger_s"], t["think_s"], t["profile_seed"], t["sampling"],
            t["trace_seconds"], t["max_requests"]) == (
        "serve_closed", 2 * lanes, 0.05, 0.05, 52, "greedy", 8.0, 1024)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 513,
                               "max": 2048}
    assert t["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
    e2e = {m["name"] for m in cell["end_to_end"]}
    # no latency: three of thirteen runs hold a stall that moves p90 by 3-6 % (PERF.md section 2)
    assert e2e == {"tok_s", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"programs.retention_share_pct", "kernels.retention_update_roofline_pct",
            "kernels.retention_scan_roofline_pct", "programs.prefill_share_pct",
            "engine.lane_occupancy_pct", "engine.starved_idle_pct", "engine.vacant_lane_pct",
            "engine.blocked_lane_pct", "engine.admit_real_pct", "device.idle_pct.serve",
            "entry.deploy_s", "programs.decode_step_ms.tok_s", "programs.macro_step_ms.tok_s"} <= names
    assert all(m["moves"] in e2e for m in cell["per_layer"])
    bench = common.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"].split(".")[1].startswith("retention_"):
            assert (m["workloads"], m["moves"], m["unit"], m["source"]) == (
                [CELL], "tok_s", "%", "device_trace")
    for m in cell["per_layer"]:
        assert os.path.isfile(f"{common.BENCH_DIR}/layer_metrics/{m['name']}.py")
    plan = traffic.plan(t, 2**31 + 5, 40.0, CONFIG["vocab_size"])
    p = np.array([len(r["prompt"]) for r in plan["requests"]])
    o = np.array([r["max_new_tokens"] for r in plan["requests"]])
    assert 513 <= p.min() < p.max() <= 2048 and 128 <= o.min() < o.max() <= 512
    assert 950 < np.median(p) < 1250 and 290 < o.mean() < 350
    assert p.max() + o.max() <= CONFIG["max_position_embeddings"]  # the table span holds the longest
    variants = macro_variants(t, CONFIG["serve"], CONFIG["max_position_embeddings"])
    assert variants[0] == [lanes, 2048] and variants[-1] == [1, 16]
    assert {P for _, P in variants} == {2048, 1024, 16}


def test_program_config_from_the_file():
    from benchmark.drivers.serve_brumby import brumby_config

    cfg = brumby_config(CONFIG)
    # every published width
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.n_layers,
            cfg.vocab_size, cfg.rope_theta, cfg.rms_eps) == (
        5120, 40, 8, 128, 17408, 6, 151936, 1e6, 1e-6)
    assert (cfg.ret_chunk, cfg.ret_eps, cfg.max_seq_len, cfg.phi_dim, cfg.state_rows) == (
        2048, 1e-6, 4096, 8320, 136)
    with pytest.raises(common.BenchFailure):
        brumby_config({**CONFIG, "tie_word_embeddings": True})
    with pytest.raises(common.BenchFailure):
        brumby_config({**CONFIG, "model_type": "qwen3"})
    with pytest.raises(common.BenchFailure):
        brumby_config({**CONFIG, "sliding_window": 4096})


# ------------------------------------------------------ the model arithmetic
def test_model_arithmetic_by_hand_at_the_published_sizes():
    """ISSUE 52's table and the bytes of its motivation, each from the shapes
    by hand, at the published depth."""
    full = {**CONFIG, "num_hidden_layers": 40}
    d, f, V, D = 5120, 17408, 151936, 128 * 129 // 2
    assert D == 8256 and mm.shapes(CONFIG)["D"] == D
    assert mm.layer_params(CONFIG) == (2 * d * d + 2 * d * 1024) + (d * 8 + 8 + 256) + 3 * d * f + 2 * d
    assert mm.layer_params(CONFIG) == 330_352_904
    assert mm.num_params(full) == 40 * 330_352_904 + 2 * V * d + d == 14_769_945_920
    assert mm.weight_bytes(full) == 2 * 14_769_945_920
    assert mm.num_params(CONFIG) == 6 * 330_352_904 + 2 * V * d + d
    # a lane's whole context in ONE layer: 8 KV heads x (8,256 x 128 + 8,256) float32, 34.08 MB
    assert mm.state_bytes_per_lane_layer(CONFIG) == 8 * (D * 128 + D) * 4 == 34_080_768
    assert mm.state_bytes_per_lane(full) == 40 * 34_080_768        # 1.36 GB a sequence, any length
    assert mm.state_bytes_per_lane(CONFIG) == 6 * 34_080_768
    # a decode step's weights: the layers and the head, not the embedding
    assert mm.decode_weight_bytes(CONFIG) == (6 * 330_352_904 + V * d + d) * 2
    # an update of ONE live lane and step: the state read and written once a
    # layer; q and o (40 x 128), k and v (8 x 128), the gates (8), float32
    assert mm.retention_update_bytes_per_lane_step(CONFIG) == 6 * (
        2 * 34_080_768 + (2 * 40 * 128 + 2 * 8 * 128 + 8) * 4)
    assert mm.retention_update_flops_per_lane_step(CONFIG) == 6 * (3 * 8 + 2 * 40) * D * 129
    # the chunked form, a layer: 16.9 MFLOP a position to build the state and 84.5 to query
    # it (ISSUE 52, without z's column). At C = 2,048 a prompt of the cell is ONE chunk: it
    # queries no state and writes its own once
    assert 8 * 2 * D * 128 == 16_908_288 and 40 * 2 * D * 128 == 84_541_440
    assert CONFIG["retention_chunk_size"] == 2048
    assert mm.retention_scan_flops(CONFIG, 1500, 1) == 6 * (
        40 * (1500 * 1501 / 2) * (2 * 128 + 2 * 129) + 8 * 1500 * (2 * D * 129 + D))
    assert mm.retention_scan_bytes(CONFIG, 1500, 1) == 6 * (
        1500 * ((2 * 40 * 128 + 2 * 8 * 128) * 2 + 8 * 4) + 34_080_768)
    # past a chunk (the 4,096 bucket, which no prompt of the cell reaches): the second chunk's
    # 952 positions query the state, which is written twice and read once
    pairs = 2048 * 2049 / 2 + 952 * 953 / 2
    assert mm.retention_scan_flops(CONFIG, 3000, 1) == 6 * (
        40 * pairs * (2 * 128 + 2 * 129) + 40 * 952 * (2 * D * 129 + D) + 8 * 3000 * (2 * D * 129 + D))
    assert mm.retention_scan_bytes(CONFIG, 3000, 1) == 6 * (3000 * 24_608 + 3 * 34_080_768)
    # the mean length never overcounts two prompts of unequal length (every count is convex)
    assert mm.retention_scan_flops(CONFIG, 2000, 2) == 2 * mm.retention_scan_flops(CONFIG, 1000, 1)
    assert mm.retention_scan_flops(CONFIG, 2000, 2) < (
        mm.retention_scan_flops(CONFIG, 600, 1) + mm.retention_scan_flops(CONFIG, 1400, 1))
    # the issue's count of a decode step at 16 live lanes and 8 layers: 56 % of the bytes state
    eight = {**CONFIG, "num_hidden_layers": 8}
    state = 16 * 2 * mm.state_bytes_per_lane(eight)
    assert 0.55 < state / (state + mm.decode_weight_bytes(eight)) < 0.57
    six = 16 * 2 * mm.state_bytes_per_lane(CONFIG)
    assert 0.53 < six / (six + mm.decode_weight_bytes(CONFIG)) < 0.55


def test_arithmetic_agrees_with_the_program():
    from benchmark.drivers.serve_brumby import brumby_config
    from ray_tpu.models import brumby, brumby_decode

    cfg = brumby_config(CONFIG)
    assert brumby.num_params(cfg) == mm.num_params(CONFIG)
    assert brumby.num_params(brumby.BrumbyConfig()) == 14_769_945_920
    # the program's layout of the state holds the work's bytes and 6.2 % more
    held, work = brumby_decode.state_bytes_per_lane(cfg), mm.state_bytes_per_lane(CONFIG)
    assert held == 6 * 8 * 136 * 8320 * 4 and 1.06 < held / work < 1.065


# ------------------------------------------- the driver, at a tiny size
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
    cfg = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.brumby.json")
    return {"name": "test", "chips": 1, "config": "tiny.brumby", "traffic": "closed",
            "config_file": cfg, "traffic_file": CLOSED}


def test_driver_end_to_end(cluster):
    from benchmark.drivers import serve_brumby

    out = serve_brumby.measure(_cell(), seed=2**31 + 52, seconds=3.0, trace=False,
                               t_process_start=common.clock())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert {"logit_gap_mean", "logit_gap_p90", "tokens_checked"} <= {c["name"] for c in out["checks"]}
    engine = out["facts"]["engine"]
    assert engine["tokens_out"] > 0 and engine["admit_rows"] > 0
    assert engine["state_lane_steps"] == engine["useful_slot_steps"] > 0
    assert out["facts"]["state_bytes"] == 3 * 2 * 24 * 144 * 4 and out["facts"]["lanes"] == 4
    assert out["device"]["platform"] == "cpu"  # never reported as a device metric


def test_control_comes_out_incorrect(cluster):
    """The int8 control at the tiny size: the comparison that decides
    `correct` tells rounded weights from sound ones."""
    from benchmark.drivers import serve_brumby

    out = serve_brumby.measure(_cell(), seed=2**31 + 53, seconds=2.0, trace=False,
                               t_process_start=common.clock(), lower_precision="int8")
    gap = next(c for c in out["checks"] if c["name"] == "logit_gap_mean")
    assert not gap["ok"] and gap["value"] > 5 * gap["limit"]


# ------------------------------------------- the marks in a device trace
STACK = "jit(macro_step_slots_paged)/while/body/cond/branch_1_fun/decode_chunk/while/body/"


def test_scope_of_takes_the_innermost_and_our_kernel_is_known_by_name():
    for scope in S.SCOPES:
        assert S.scope_of(STACK + scope + "/dot_general:") == scope
    assert S.scope_of(STACK + "retention_proj/retention_update/mul:") == "retention_update"
    assert S.scope_of(STACK) == ""
    # none of this model's scopes is part of another model's, nor the other way
    from benchmark import (afmoe_spans, hybrid_spans, longcat_flash_spans, phi4flash_spans,
                           qwen3_next_spans, sarvam_mla_spans)
    others = {s for m in (afmoe_spans, hybrid_spans, longcat_flash_spans, phi4flash_spans,
                          qwen3_next_spans, sarvam_mla_spans) for s in m.SCOPES}
    assert not [(a, b) for a in S.SCOPES for b in others if a in b or b in a]
    for theirs in (hybrid_spans, qwen3_next_spans, phi4flash_spans):  # `rfind` finds none of theirs in ours
        assert [theirs.scope_of(STACK + scope + "/mul:") for scope in S.SCOPES] == ["", "", ""]
    admit = STACK.replace("decode_chunk", "admit_prefill")
    raw = [(0.0, 0.01, "%fusion.1 = bf16[8,64]", admit + "retention_proj/dot_general:"),
           (0.01, 0.01, "%fusion.9 = bf16[8,64]", admit + "retention_scan/while/body/mul:"),
           (0.02, 0.01, "%fusion.2 = bf16[8,64]", STACK + "retention_proj/dot_general:"),
           (0.03, 0.01, "%retention_update.3 = (f32[16,8,8,128]) custom-call(...)", ""),
           (0.04, 0.01, "%fusion.3 = f32[8,64]", STACK + "retention_update/mul:"),
           (0.05, 0.01, "%fusion.4 = bf16[8,64]", STACK + "dot_general:"),
           (0.09, 0.01, "%copy.4 = bf16[8,64]", "")]
    assert [(half, scope) for _, _, half, scope in S.scoped(raw)] == [
        ("admit_prefill", "retention_proj"), ("admit_prefill", "retention_scan"),
        ("decode_chunk", "retention_proj"), ("decode_chunk", "retention_update"),
        ("decode_chunk", "retention_update"), ("decode_chunk", ""), ("", "")]


def _recorded():
    """A 1 s window that opens inside execution seq 4, two whole executions
    (seq 5, whose dispatch lies before the trace, and seq 6), a last one (seq
    7) that the trace's end cuts; operations of 10 ms as (start, duration,
    half, scope)."""
    plan = lambda seq, steps, lanes, tokens, rows, n: {  # noqa: E731
        "seq": seq, "steps": steps, "lane_steps": lanes, "state_lanes": lanes,
        "prompt_tokens": tokens, "admit_rows": rows, "admissions": n}
    spans = [("engine.resolve", 1.15, 0.01, plan(4, 8, 6, 300, 1024, 1)),
             ("engine.dispatch", 1.16, 0.001, plan(6, 12, 9, 0, 0, 0)),
             ("engine.resolve", 1.45, 0.02, plan(5, 10, 8, 2700, 4096, 3)),
             ("engine.dispatch", 1.48, 0.001, plan(7, 8, 6, 2000, 2048, 1)),
             ("engine.resolve", 1.75, 0.01, plan(6, 12, 9, 0, 0, 0))]
    modules = [("jit_macro_step_slots_paged(1)", 0.85, 0.30), ("jit_macro_step_slots_paged(1)", 1.15, 0.30),
               ("jit_macro_step_slots_paged(1)", 1.45, 0.30), ("jit_macro_step_slots_paged(1)", 1.75, 0.30)]
    a, d = "admit_prefill", "decode_chunk"
    ops = [(1.05, 0.01, d, "retention_update"),                             # seq 4 (not counted)
           (1.16, 0.01, a, "retention_proj"), (1.17, 0.01, a, "retention_scan"),
           (1.18, 0.01, a, "retention_scan"), (1.19, 0.01, a, ""),
           (1.30, 0.01, d, "retention_proj"), (1.31, 0.01, d, "retention_update"),
           (1.32, 0.01, d, "retention_update"), (1.33, 0.01, d, ""), (1.34, 0.01, d, ""),  # seq 5
           (1.50, 0.01, d, "retention_update"), (1.51, 0.01, d, "retention_proj"),
           (1.52, 0.01, d, ""),                                             # seq 6
           (1.80, 0.01, d, "retention_update"),                             # seq 7 (cut)
           (2.20, 0.01, d, "retention_update")]                             # outside a macro-step
    return {"window": (1.0, 2.0), "spans": spans, "modules": modules}, sorted(ops)


def test_view_sums_scopes_by_half_and_counts_the_whole_executions_by_their_resolve():
    trace, ops = _recorded()
    v = S.view(trace, ops)
    assert v["executions"] == 4 and v["counted_executions"] == 2
    assert v["macro_step_s"] == pytest.approx(1.2)
    w, c = v["window"], v["counted"]
    assert w["decode_chunk/retention_update"] == pytest.approx(0.05)  # not the one outside a macro-step
    assert c["decode_chunk/retention_update"] == pytest.approx(0.03)  # nor seq 4's, nor seq 7's
    assert c["admit_prefill/retention_scan"] == pytest.approx(0.02)
    assert w["admit_prefill/all"] == pytest.approx(0.04) and c["decode_chunk/all"] == pytest.approx(0.08)
    assert (v["counted_steps"], v["counted_lane_steps"], v["counted_state_lanes"],
            v["counted_prompt_tokens"], v["counted_admit_rows"], v["counted_admissions"]) == (
        22, 17, 17, 2700, 4096, 3)
    assert S.view({**trace, "window": None}, ops) is None


NEW_METRICS = ["programs.retention_share_pct", "kernels.retention_update_roofline_pct",
               "kernels.retention_scan_roofline_pct"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_on_the_recorded_trace(metric, monkeypatch):
    trace, ops = _recorded()
    recorded = S.view(trace, ops)
    monkeypatch.setattr(S, "brumby_view", lambda facts: recorded)
    ctx = {"facts": {}, "config": CONFIG, "peaks": PEAKS}
    got = common.load_module("layer_metrics", metric).read(ctx)
    if metric == "programs.retention_share_pct":     # the window's executions, both halves
        assert got["value"] == pytest.approx(100.0 * 0.10 / 1.2)
        assert got["admit_share_of_macro_steps_pct"] == pytest.approx(100.0 * 0.04 / 1.2)
        assert got["admissions"] == 3
    elif metric == "kernels.retention_update_roofline_pct":
        least = 17 * mm.retention_update_bytes_per_lane_step(CONFIG) / 819e9  # 66 B an operation: memory
        assert got["value"] == pytest.approx(100.0 * least / 0.03) and got["bound"] == "memory"
    else:
        least = mm.retention_scan_flops(CONFIG, 2700, 3) / 197e12
        assert got["value"] == pytest.approx(100.0 * least / 0.02) and got["bound"] == "compute"
    assert got["value"] < 100.0
    # a program without the scopes (the parent, another model), or an untraced run: nothing to read
    empty = S.view(trace, [(s, d, half, "") for s, d, half, _ in ops])
    monkeypatch.setattr(S, "brumby_view", lambda facts: empty)
    assert common.load_module("layer_metrics", metric).read(ctx) is None
    monkeypatch.setattr(S, "brumby_view", lambda facts: None)
    assert common.load_module("layer_metrics", metric).read(ctx) is None
