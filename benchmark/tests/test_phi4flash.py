"""The Phi-4-mini-flash configuration's benchmark files on the CPU: the
configuration held to ITS published widths against the catalog's row, the model
arithmetic against hand arithmetic at the published sizes and against the
program's parameter tree, the reference against the program, the driver end to
end at a tiny size, and the new readers on a small hand-built trace. No timing
is asserted or reported, and nothing pins the benchmark's SIZE (how many
configurations, cells or metrics it has): a later PR appends."""
import json
import os

import numpy as np
import pytest

from benchmark import common, phi4flash_spans as S
from benchmark import model_math_phi4flash as mm

CONFIG = common.load_json(f"{common.BENCH_DIR}/configs/phi-4-mini-flash-reasoning.serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config`, as this PR read it: kept here so that the test
# holds where the guide is not installed
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
    "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
REDUCED = {"max_position_embeddings": 2048}
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "reasoning-generate"


# ------------------------------------------------------- the configuration
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_key(key):
    """Key by key: as published, or listed in `reduced` with the published
    value under `published`; the table span is all that is reduced: no width,
    head count, layer count or vocabulary size differs from the row."""
    assert sorted(CONFIG["reduced"]) == sorted(REDUCED)
    if key in REDUCED:
        assert CONFIG["published"][key] == PUBLISHED[key] and CONFIG[key] == REDUCED[key]
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_the_file_is_the_catalog_row_and_says_what_it_assumes():
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
        assert row["config"] == PUBLISHED and row["source_url"] == CONFIG["source"]
    bench = common.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/phi-4-mini-flash-reasoning.serve.json"
    assert entry["reduced"] == CONFIG["reduced"] == ["max_position_embeddings"]
    # every size the row cannot confirm, each with its origin
    assert {"mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "mamba_conv_bias",
            "attention_bias", "norm", "position_embedding", "layer_kinds", "window_edge",
            "differential_attention", "ssm_state_dtype", "torch_dtype",
            "weights_distribution"} <= set(CONFIG["assumed"])
    assert (CONFIG["mamba_d_state"], CONFIG["mamba_d_conv"], CONFIG["mamba_expand"],
            CONFIG["mamba_dt_rank"], CONFIG["torch_dtype"]) == (16, 4, 2, 160, "bfloat16")
    assert "one v5e chip holds the whole model" in CONFIG["deployment"]
    assert CONFIG["departures"]["program"] and CONFIG["departures"]["reference"]
    assert sorted(CONFIG["why_reduced"]) == sorted(REDUCED)
    s = CONFIG["serve"]
    assert (s["block_size"], s["max_new_tokens"], s["prefix_cache"], s["continuous"]) == (
        16, 1024, False, True)
    assert s["n_slots"] in (64, 32) and s["why_n_slots"] and s["why_prefix_cache"]
    assert CONFIG["check"]["why"] and CONFIG["weights"] and CONFIG["driver"] == "serve_phi4flash"
    assert (CONFIG["check"]["gap_mean_limit"], CONFIG["check"]["gap_p95_limit"]) == (0.018, 0.12)
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_the_cell_and_its_traffic_are_the_issues():
    from benchmark import traffic
    from benchmark.drivers.serve import macro_variants

    cell = common.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi-4-mini-flash-reasoning.serve", "reasoning-generate.closed", 1)
    assert len(cell["why"]) <= 200
    t = cell["traffic_file"]
    lanes = CONFIG["serve"]["n_slots"]
    assert (t["kind"], t["clients"], t["stagger_s"], t["think_s"], t["profile_seed"],
            t["sampling"], t["trace_seconds"]) == ("serve_closed", 2 * lanes, 0.05, 0.05, 49,
                                                   "greedy", 8.0)
    wide = common.load_json(f"{common.BENCH_DIR}/traffic/generate-wide.closed.json")
    assert t["prompt_len"] == wide["prompt_len"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.45, "min": 129, "max": 512}
    assert t["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    e2e = {m["name"] for m in cell["end_to_end"]}
    # the latencies too: over six seeds they spread by 0.03 %, under half their bounds
    assert e2e == {"tok_s", "setup_s", "latency_p50_ms", "latency_p90_ms"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"programs.s6_share_pct", "programs.cross_share_pct", "programs.diff_attn_share_pct",
            "kernels.s6_update_roofline_pct", "kernels.s6_scan_roofline_pct",
            "kernels.cross_attn_decode_roofline_pct", "kernels.diff_ring_decode_roofline_pct",
            "programs.prefill_share_pct", "engine.lane_occupancy_pct", "engine.starved_idle_pct",
            "engine.vacant_lane_pct", "engine.blocked_lane_pct", "engine.admit_real_pct",
            "device.idle_pct.serve", "entry.deploy_s"} <= names
    # the cell judges its latencies, so it carries the readers that say where a latency
    # went (half of its p50 is the wait for a lane) and the step readers that move a
    # latency, not their `.tok_s` twins, which PR 39 made for cells that judge none
    assert {"engine.lane_wait_ms", "engine.queue_ms", "engine.plan_wait_ms",
            "engine.dispatch_lead_ms", "engine.admit_stall_ms", "engine.deliver_lag_ms",
            "engine.finish_wait_steps", "serve_plane.overhead_ms", "programs.macro_step_ms",
            "programs.decode_step_ms"} <= names
    assert not {n for n in names if n.endswith(".tok_s")}
    assert all(m["moves"] in e2e for m in cell["per_layer"])
    bench = common.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"].split(".")[1].startswith(("s6_", "cross_", "diff_")):
            assert (m["workloads"], m["moves"], m["unit"], m["source"]) == (
                [CELL], "tok_s", "%", "device_trace")
    for m in cell["per_layer"]:
        assert os.path.isfile(f"{common.BENCH_DIR}/layer_metrics/{m['name']}.py")
    plan = traffic.plan(t, 2**31 + 5, 40.0, CONFIG["vocab_size"])
    p = np.array([len(r["prompt"]) for r in plan["requests"]])
    o = np.array([r["max_new_tokens"] for r in plan["requests"]])
    assert 129 <= p.min() < p.max() <= 512 and 256 <= o.min() < o.max() <= 1024
    assert 230 < np.median(p) < 290 and 600 < o.mean() < 680
    assert p.max() + o.max() <= CONFIG["max_position_embeddings"]  # the table span holds the longest
    variants = macro_variants(t, CONFIG["serve"], CONFIG["max_position_embeddings"])
    assert variants[0] == [lanes, 512] and variants[-1] == [1, 16]
    assert {P for _, P in variants} == {512, 256, 16}


def test_program_config_from_the_file():
    from benchmark.drivers.serve_phi4flash import phi4flash_config

    cfg = phi4flash_config(CONFIG)
    # every published width, and the sizes the file assumes
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.n_layers,
            cfg.vocab_size, cfg.sliding_window, cfg.mb_per_layer) == (
        2560, 40, 20, 64, 10240, 32, 200064, 512, 2)
    assert (cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_expand, cfg.mamba_dt_rank, cfg.d_inner,
            cfg.layer_norm_eps, cfg.max_seq_len) == (16, 4, 2, 160, 5120, 1e-5, 2048)
    assert (cfg.n_mamba_layers, cfg.n_window_layers, cfg.n_cross_layers) == (9, 8, 7)
    with pytest.raises(common.BenchFailure):
        phi4flash_config({**CONFIG, "tie_word_embeddings": False})
    with pytest.raises(common.BenchFailure):
        phi4flash_config({**CONFIG, "model_type": "phi3"})


# ------------------------------------------------------ the model arithmetic
def test_model_arithmetic_by_hand_at_the_published_sizes():
    """ISSUE 49's table (mixers without their LayerNorm) and the bytes of its
    motivation, each from the shapes by hand."""
    d, di, f, V, N = 2560, 5120, 10240, 200064, 16
    assert mm.mlp_params(CONFIG) == d * 2 * f + f * d + 2 * d                     # 78.6 M
    assert mm.mamba_params(CONFIG) == (d * 2 * di + 4 * di + di + di * 192 + 160 * di + di
                                       + N * di + di + di * d + 2 * d)                # 41.2 M
    assert mm.attn_params(CONFIG) == d * 5120 + 5120 + d * d + d + 6 * 64 + 2 * d   # 19.7 M
    assert mm.cross_params(CONFIG) == 2 * (d * d + d) + 6 * 64 + 2 * d              # 13.1 M
    assert mm.gmu_params(CONFIG) == 2 * d * di + 2 * d                              # 26.2 M
    assert mm.num_params(CONFIG) == 3_852_562_944 and mm.weight_bytes(CONFIG) == 7_705_125_888
    assert mm.num_params(CONFIG) == (32 * mm.mlp_params(CONFIG) + 9 * mm.mamba_params(CONFIG)
                                     + 9 * mm.attn_params(CONFIG) + 7 * mm.gmu_params(CONFIG)
                                     + 7 * mm.cross_params(CONFIG) + V * d + 2 * d)
    # a position in the WHOLE model's paged cache: one layer's 20 KV heads of 64, K and V
    assert mm.kv_bytes_per_token(CONFIG) == 2 * 20 * 64 * 2 == 5120
    # a lane: eight rings of 512 positions, nine float32 states and conv tails
    assert mm.state_bytes_per_lane(CONFIG) == 8 * 512 * 5120 + 9 * (3 * di * 2 + N * di * 4)
    # a state update of ONE live lane and step: state and tail read and
    # written once, x, dt (float32), B, C read, y written, in nine layers
    assert mm.s6_update_bytes_per_lane_step(CONFIG) == 9 * (
        2 * (N * di * 4 + 3 * di * 2) + di * 2 + di * 4 + 2 * N * 2 + di * 2)
    assert mm.s6_update_bytes_per_step(CONFIG) == 9 * (N * di * 4 + di * 4 + 5 * di * 2)
    assert mm.s6_scan_flops_per_token(CONFIG) == 9 * (7 * N * di + 11 * di)
    assert mm.s6_scan_bytes_per_token(CONFIG) == 9 * (3 * di * 2 + di * 4 + 2 * N * 2)
    # seven readers of ONE pool layer: the attended positions once a layer,
    # Wq and out_proj with their biases once a layer and step
    assert mm.cross_attn_decode_bytes(CONFIG, 1000, 3) == 7 * (1000 * 5120 + 3 * (2 * d * d + 2 * d) * 2)
    # eight window layers: 512 positions for a lane-step past the window, one for another
    assert mm.diff_ring_decode_bytes(CONFIG, 100, 60, 3) == 8 * (
        (60 * 512 + 40) * 5120 + 3 * (d * 5120 + 5120 + d * d + d) * 2)
    step = mm.decode_step_bytes(CONFIG, 64, 700)
    assert step["rings"] == 64 * 8 * 512 * 5120 and step["pool"] == 64 * 700 * 5120 * 8
    assert step["mlp_weights"] == 32 * 3 * d * f * 2 and step["head"] == V * d * 2


def test_arithmetic_agrees_with_the_program():
    from benchmark.drivers.serve_phi4flash import phi4flash_config
    from ray_tpu.models import phi4flash, phi4flash_decode

    cfg = phi4flash_config(CONFIG)
    assert phi4flash.num_params(cfg) == mm.num_params(CONFIG)
    assert phi4flash_decode.state_bytes_per_lane(cfg) == mm.state_bytes_per_lane(CONFIG)


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
    cfg = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.phi4flash.json")
    return {"name": "test", "chips": 1, "config": "tiny.phi4flash", "traffic": "closed",
            "config_file": cfg, "traffic_file": CLOSED}


def test_driver_end_to_end(cluster):
    from benchmark.drivers import serve_phi4flash

    out = serve_phi4flash.measure(_cell(), seed=2**31 + 49, seconds=3.0, trace=False,
                                  t_process_start=common.clock())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert {"logit_gap_mean", "logit_gap_p90", "tokens_checked"} <= {c["name"] for c in out["checks"]}
    engine = out["facts"]["engine"]
    assert engine["tokens_out"] > 0 and engine["ctx_tokens"] > 0
    # what the device says it ran is what the plan says: the self-decoder the
    # admissions' rows whole, the cross-decoder ONE token row an admission row
    assert engine["self_rows"] == engine["admit_rows"] > 0
    assert engine["requests_completed"] <= engine["cross_rows"] < engine["self_rows"] // 32
    assert engine["state_lane_steps"] == engine["useful_slot_steps"] > 0
    assert out["facts"]["state_bytes"] > 0 and out["facts"]["lanes"] == 4
    assert out["device"]["platform"] == "cpu"  # never reported as a device metric


def test_control_comes_out_incorrect(cluster):
    """The int8 control at the tiny size: the comparison that decides
    `correct` tells rounded weights from sound ones."""
    from benchmark.drivers import serve_phi4flash

    out = serve_phi4flash.measure(_cell(), seed=2**31 + 50, seconds=2.0, trace=False,
                                  t_process_start=common.clock(), lower_precision="int8")
    gap = next(c for c in out["checks"] if c["name"] == "logit_gap_mean")
    assert not gap["ok"] and gap["value"] > 5 * gap["limit"]


# ------------------------------------------- the marks in a device trace
STACK = "jit(macro_step_slots_paged)/while/body/cond/branch_1_fun/decode_chunk/while/body/"


def test_scope_of_takes_the_innermost_and_our_kernels_are_known_by_name():
    for scope in S.SCOPES:
        assert S.scope_of(STACK + scope + "/dot_general:") == scope
    assert S.scope_of(STACK + "s6_proj/s6_update/mul:") == "s6_update" and S.scope_of(STACK) == ""
    # none of this model's scopes is part of another model's, nor the other way
    from benchmark import afmoe_spans, hybrid_spans, longcat_flash_spans, qwen3_next_spans, sarvam_mla_spans
    others = {s for m in (afmoe_spans, hybrid_spans, longcat_flash_spans, qwen3_next_spans,
                          sarvam_mla_spans) for s in m.SCOPES}
    assert not [(a, b) for a in S.SCOPES for b in others if a in b or b in a]
    admit = STACK.replace("decode_chunk", "admit_prefill")
    raw = [(0.0, 0.01, "%fusion.1 = bf16[8,64]", admit + "s6_proj/dot_general:"),
           (0.01, 0.01, "%fusion.9 = bf16[8,64]", admit + "s6_scan/while/body/mul:"),
           (0.02, 0.01, "%flash_fwd.8 = (bf16[40,512,128]) custom-call(...)", ""),
           (0.03, 0.01, "%fusion.7 = bf16[8,64]", admit + "cross_attn/dot_general:"),
           (0.04, 0.01, "%fusion.2 = bf16[8,64]", STACK + "gmu/dot_general:"),
           (0.05, 0.01, "%s6_update.3 = (f32[64,1,5120]) custom-call(...)", ""),
           (0.06, 0.01, "%fusion.3 = bf16[8,64]", STACK + "diff_window/dot_general:"),
           (0.07, 0.01, "%fusion.4 = bf16[8,64]", STACK + "diff_full/while/body/dot_general:"),
           (0.09, 0.01, "%copy.4 = bf16[8,64]", "")]
    assert [(half, scope) for _, _, half, scope in S.scoped(raw)] == [
        ("admit_prefill", "s6_proj"), ("admit_prefill", "s6_scan"), ("admit_prefill", "diff_window"),
        ("admit_prefill", "cross_attn"), ("decode_chunk", "gmu"), ("decode_chunk", "s6_update"),
        ("decode_chunk", "diff_window"), ("decode_chunk", "diff_full"), ("", "")]


def _recorded():
    """A 1 s window that opens inside execution seq 4, two whole executions
    (seq 5, whose dispatch lies before the trace, and seq 6), a last one (seq
    7) that the trace's end cuts; operations of 10 ms as (start, duration,
    half, scope)."""
    plan = lambda seq, steps, lanes, tokens, ctx, past, rows, n, **dev: {  # noqa: E731
        "seq": seq, "steps": steps, "lane_steps": lanes, "state_lanes": lanes,
        "prompt_tokens": tokens, "ctx_tokens": ctx, "past_window_lane_steps": past,
        "admit_rows": rows, "admissions": n, **dev}
    dev = lambda self_rows, cross_rows: {"self_rows": self_rows, "cross_rows": cross_rows}  # noqa: E731
    spans = [("engine.resolve", 1.15, 0.01, plan(4, 8, 60, 300, 9000, 10, 512, 1, **dev(512, 1))),
             ("engine.dispatch", 1.16, 0.001, plan(6, 12, 90, 0, 40000, 70, 0, 0)),
             ("engine.resolve", 1.45, 0.02, plan(5, 10, 80, 700, 30000, 50, 1024, 3, **dev(1024, 3))),
             ("engine.dispatch", 1.48, 0.001, plan(7, 8, 64, 2000, 20000, 64, 2560, 5)),
             ("engine.resolve", 1.75, 0.01, plan(6, 12, 90, 0, 40000, 70, 0, 0, **dev(0, 0)))]
    modules = [("jit_macro_step_slots_paged(1)", 0.85, 0.30), ("jit_macro_step_slots_paged(1)", 1.15, 0.30),
               ("jit_macro_step_slots_paged(1)", 1.45, 0.30), ("jit_macro_step_slots_paged(1)", 1.75, 0.30)]
    a, d = "admit_prefill", "decode_chunk"
    ops = [(1.05, 0.01, d, "s6_update"),                                    # seq 4 (not counted)
           (1.16, 0.01, a, "s6_proj"), (1.17, 0.01, a, "s6_scan"), (1.18, 0.01, a, "s6_scan"),
           (1.19, 0.01, a, "diff_window"), (1.20, 0.01, a, "cross_attn"), (1.21, 0.01, a, ""),
           (1.30, 0.01, d, "s6_proj"), (1.31, 0.01, d, "s6_update"), (1.32, 0.01, d, "diff_window"),
           (1.33, 0.01, d, "diff_window"), (1.34, 0.01, d, "diff_full"), (1.35, 0.01, d, "cross_attn"),
           (1.36, 0.01, d, "cross_attn"), (1.37, 0.01, d, "gmu"), (1.38, 0.01, d, ""),  # seq 5
           (1.50, 0.01, d, "s6_update"), (1.51, 0.01, d, "diff_window"), (1.52, 0.01, d, "cross_attn"),
           (1.53, 0.01, d, "gmu"),                                          # seq 6
           (1.80, 0.01, d, "s6_update"),                                    # seq 7 (cut)
           (2.20, 0.01, d, "s6_update")]                                    # outside a macro-step
    return {"window": (1.0, 2.0), "spans": spans, "modules": modules}, sorted(ops)


def test_view_sums_scopes_by_half_and_counts_the_whole_executions_by_their_resolve():
    trace, ops = _recorded()
    v = S.view(trace, ops)
    assert v["executions"] == 4 and v["counted_executions"] == 2
    assert v["macro_step_s"] == pytest.approx(1.2)
    w, c = v["window"], v["counted"]
    assert w["decode_chunk/s6_update"] == pytest.approx(0.04)   # not the one outside a macro-step
    assert c["decode_chunk/s6_update"] == pytest.approx(0.02)   # nor seq 4's, nor seq 7's
    assert c["decode_chunk/cross_attn"] == pytest.approx(0.03) and c["admit_prefill/s6_scan"] == pytest.approx(0.02)
    assert c["decode_chunk/diff_window"] == pytest.approx(0.03)
    assert w["admit_prefill/all"] == pytest.approx(0.06) and c["decode_chunk/all"] == pytest.approx(0.13)
    assert (v["counted_steps"], v["counted_lane_steps"], v["counted_state_lanes"],
            v["counted_prompt_tokens"], v["counted_ctx_tokens"], v["counted_past_window_lane_steps"],
            v["counted_admissions"]) == (22, 170, 170, 700, 70000, 120, 3)
    assert (v["counted_self_rows"], v["counted_cross_rows"]) == (1024, 3)
    assert S.view({**trace, "window": None}, ops) is None


NEW_METRICS = ["programs.s6_share_pct", "programs.cross_share_pct", "programs.diff_attn_share_pct",
               "kernels.s6_update_roofline_pct", "kernels.s6_scan_roofline_pct",
               "kernels.cross_attn_decode_roofline_pct", "kernels.diff_ring_decode_roofline_pct"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_on_the_recorded_trace(metric, monkeypatch):
    trace, ops = _recorded()
    recorded = S.view(trace, ops)
    monkeypatch.setattr(S, "phi4flash_view", lambda facts: recorded)
    ctx = {"facts": {}, "config": CONFIG, "peaks": PEAKS}
    got = common.load_module("layer_metrics", metric).read(ctx)
    if metric == "programs.s6_share_pct":       # the window's executions, both halves
        assert got["value"] == pytest.approx(100.0 * 0.08 / 1.2)
        assert got["admit_share_of_macro_steps_pct"] == pytest.approx(100.0 * 0.06 / 1.2)
    elif metric == "programs.cross_share_pct":
        assert got["value"] == pytest.approx(100.0 * 0.06 / 1.2)
        assert (got["self_rows"], got["cross_rows"], got["admissions"]) == (1024, 3, 3)
    elif metric == "programs.diff_attn_share_pct":
        assert got["value"] == pytest.approx(100.0 * 0.09 / 1.2)
    elif metric == "kernels.s6_update_roofline_pct":
        least = (170 * mm.s6_update_bytes_per_lane_step(CONFIG)
                 + 22 * mm.s6_update_bytes_per_step(CONFIG)) / 819e9
        assert got["value"] == pytest.approx(100.0 * least / 0.02) and got["bound"] == "memory"
    elif metric == "kernels.s6_scan_roofline_pct":
        least = 700 * mm.s6_scan_bytes_per_token(CONFIG) / 819e9  # memory-bound: 51 B an operation
        assert got["value"] == pytest.approx(100.0 * least / 0.02) and got["bound"] == "memory"
    elif metric == "kernels.cross_attn_decode_roofline_pct":
        least = mm.cross_attn_decode_bytes(CONFIG, 70000, 22) / 819e9
        assert got["value"] == pytest.approx(100.0 * least / 0.03) and got["bound"] == "memory"
        assert got["ctx_tokens_a_lane_step"] == pytest.approx(70000 / 170)
    else:
        least = mm.diff_ring_decode_bytes(CONFIG, 170, 120, 22) / 819e9
        assert got["value"] == pytest.approx(100.0 * least / 0.03) and got["bound"] == "memory"
    assert got["value"] < 100.0
    # a program without the scopes (the parent, another model), or an untraced run: nothing to read
    empty = S.view(trace, [(s, d, half, "") for s, d, half, _ in ops])
    monkeypatch.setattr(S, "phi4flash_view", lambda facts: empty)
    assert common.load_module("layer_metrics", metric).read(ctx) is None
    monkeypatch.setattr(S, "phi4flash_view", lambda facts: None)
    assert common.load_module("layer_metrics", metric).read(ctx) is None


def test_the_accepted_step_readers_read_this_cells_trace_unedited(monkeypatch):
    """`programs.decode_step_ms` (the accepted reader over `program_spans`)
    needs the two halves and the dispatch spans' `steps` and no scope of its
    own model's: of the recorded executions it pairs seq 6 with its dispatch
    (seq 5's lies before the trace, seq 7 is cut), so the cell, which judges
    its latencies, joins its `workloads` and not the `.tok_s` twin's."""
    from benchmark import program_spans

    trace, ops = _recorded()
    view = program_spans.serve_view({**trace, "busy": [(s, s + d) for s, d, _, _ in ops],
                                     "ops": [(s, d, half) for s, d, half, _ in ops]})
    monkeypatch.setattr(program_spans, "run_serve_view", lambda facts: view)
    got = common.load_module("layer_metrics", "programs.decode_step_ms").read({"facts": {}})
    assert got["value"] == pytest.approx(1e3 * 0.04 / 12) and got["steps"] == 12
    assert got["paired_executions"] == 1
