"""The latent-attention configuration's benchmark files on the CPU: the
configuration held to ITS published widths against the catalog's row, the
model arithmetic against figures counted by hand (ISSUE 39) and against the
program's parameter tree, the reference against a second, loop-written form
(NumPy float64, one head and one position at a time), the driver end to end
at a tiny size, and the new readers on a small recorded trace. No timing is
asserted or reported."""
import json
import os

import numpy as np
import pytest

from benchmark import common, sarvam_mla_spans as S
from benchmark import model_math_sarvam_mla as mm

CONFIG = common.load_json(f"{common.BENCH_DIR}/configs/sarvam-105b.serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's `config`, as this PR read it: kept here so that the test
# holds where the guide is not installed
PUBLISHED = {
    "attn_implementation": None, "default_theta": 10000, "first_k_dense_replace": 1,
    "head_dim": 576, "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "kv_lora_rank": 512, "max_position_embeddings": 131072, "model_type": "sarvam_mla",
    "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_shared_experts": 1, "q_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "deepseek_yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "tie_word_embeddings": False,
    "use_qk_norm": True, "v_head_dim": 128, "vocab_size": 262144}
REDUCED = {"num_hidden_layers": 5, "num_experts": 32, "vocab_size": 65536,
           "max_position_embeddings": 8192}
FULL = {**CONFIG, **PUBLISHED}  # the published model, in the file's other keys
FULL.pop("router_num_experts")


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
            row = next(r for r in map(json.loads, f) if r["name"] == "sarvam-105b")
        assert row["config"] == PUBLISHED and row["source_url"] == CONFIG["source"]
    bench = common.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] and entry["file"] == "benchmark/configs/sarvam-105b.serve.json"
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert (CONFIG["router_num_experts"], CONFIG["held_experts_first"]) == (128, 0)
    assert {"routing", "norm_topk_prob", "use_qk_norm", "rope", "torch_dtype",
            "weights_distribution"} <= set(CONFIG["assumed"])
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert "640" in CONFIG["departures"]["program"] and CONFIG["departures"]["reference"]
    assert sorted(CONFIG["why_reduced"]) == sorted(REDUCED)
    s = CONFIG["serve"]
    assert (s["n_slots"], s["block_size"], s["max_new_tokens"], s["prefix_cache"]) == (8, 16, 128, False)
    assert s["why_prefix_cache"] and CONFIG["check"]["why"] and CONFIG["weights"]
    assert len(bench["configs"]) == 5 and len(bench["workloads"]) == 7
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_the_cell_and_its_traffic_are_the_issues():
    from benchmark import traffic
    from benchmark.drivers.serve import macro_variants

    cell = common.load_cell("longdoc-qa")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sarvam-105b.serve", "longdoc.closed", 1)
    t = cell["traffic_file"]
    assert (t["kind"], t["clients"], t["stagger_s"], t["think_s"], t["profile_seed"],
            t["sampling"], t["trace_seconds"]) == ("serve_closed", 16, 0.13, 0.05, 39, "greedy", 2.5)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 2560, "sigma": 0.4, "min": 1025,
                               "max": 4096}
    assert t["output_len"] == {"dist": "uniform", "min": 32, "max": 128}
    # the latencies are not this cell's to report: over six seeds they spread by 1.6 and 1.8 %
    # of their median, twice their bounds' halves (my chip runs, PR 39), where `tok_s` reads
    # the same to the digit; a decode step and a macro-step are read under names of their own
    # that move `tok_s`
    assert {m["name"] for m in cell["end_to_end"]} == {"tok_s", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {"programs.mla_share_pct", "kernels.mla_prefill_roofline_pct",
                     "kernels.mla_decode_roofline_pct", "kernels.moe_held_roofline_pct",
                     "kernels.moe_held_prefill_roofline_pct", "programs.decode_step_ms.tok_s",
                     "programs.macro_step_ms.tok_s",
                     "programs.moe_share_pct", "programs.prefill_share_pct",
                     "engine.lane_occupancy_pct", "engine.starved_idle_pct",
                     "device.idle_pct.serve", "entry.deploy_s"}
    assert all(m["moves"] in ("tok_s", "setup_s") for m in cell["per_layer"])
    assert "programs.serve_roofline_pct" not in names  # Llama's arithmetic
    for m in cell["per_layer"]:
        assert os.path.isfile(f"{common.BENCH_DIR}/layer_metrics/{m['name']}.py")
    plan = traffic.plan(t, 2**31 + 5, 40.0, CONFIG["vocab_size"])
    p = np.array([len(r["prompt"]) for r in plan["requests"]])
    o = np.array([r["max_new_tokens"] for r in plan["requests"]])
    assert 1025 <= p.min() < p.max() <= 4096 and 32 <= o.min() < o.max() <= 128
    assert 2300 < np.median(p) < 2800
    assert max(max(r["prompt"]) for r in plan["requests"][:64]) < 65536  # inside the slice
    variants = macro_variants(t, CONFIG["serve"], CONFIG["max_position_embeddings"])
    assert len(variants) == 9 and variants[0] == [8, 4096] and variants[-1] == [1, 16]


def test_program_config_from_the_file():
    from benchmark.drivers.serve_sarvam_mla import mla_config

    cfg = mla_config(CONFIG)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_moe_layers) == (5, 1, 4)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.d_ff, cfg.moe_d_ff, cfg.n_experts,
            cfg.held_experts, cfg.top_k, cfg.n_shared_experts, cfg.vocab_size,
            cfg.max_seq_len) == (4096, 64, 512, 128, 64, 128, 16384, 2048, 128, (0, 32), 8, 1,
                                 65536, 8192)
    assert (cfg.route_scale, cfg.rope_factor, cfg.rope_original_max, cfg.rms_eps) == (
        2.5, 40.0, 4096, 1e-6)
    assert cfg.sm_scale == pytest.approx(0.13523, abs=1e-5)
    with pytest.raises(common.BenchFailure):
        mla_config({**CONFIG, "q_lora_rank": 1536})
    with pytest.raises(common.BenchFailure):
        mla_config({**CONFIG, "rope_scaling": {**CONFIG["rope_scaling"], "type": "linear"}})


# ------------------------------------------------------ the model arithmetic
def test_model_arithmetic_from_the_shapes():
    """The figures of ISSUE 39, counted by hand from the widths."""
    M_ = 1e6
    assert mm.attn_matmul_params(CONFIG) == (4096 * 64 * 192 + 4096 * 576 + 512 * 64 * 256
                                             + 8192 * 4096)                       # 94.63 M
    assert mm.attn_matmul_params(CONFIG) / M_ == pytest.approx(94.63, abs=0.005)
    assert mm.expert_params(CONFIG) == mm.shared_params(CONFIG) == 25_165_824     # 25.17 M
    assert mm.router_params(CONFIG) == 4096 * 128
    assert mm.expert_layer_params(CONFIG) / M_ == pytest.approx(925.6, abs=0.1)
    assert mm.expert_layer_params(FULL) / M_ == pytest.approx(3341.5, abs=0.1)    # 6.68 GB
    assert mm.dense_layer_params(CONFIG) / M_ == pytest.approx(296.0, abs=0.05)
    assert mm.embed_and_head_params(CONFIG) / M_ == pytest.approx(536.9, abs=0.05)
    assert mm.num_params(CONFIG) / 1e9 == pytest.approx(4.535, abs=0.001)
    assert mm.weight_bytes(CONFIG) / 1e9 == pytest.approx(9.07, abs=0.005)
    assert mm.num_params(FULL) / 1e9 == pytest.approx(106.0, abs=0.05)           # published: 105B
    assert mm.latent_bytes_per_token(CONFIG) == 5 * 1152
    six = {**CONFIG, "num_hidden_layers": 6}
    assert mm.weight_bytes(six) / 1e9 == pytest.approx(10.92, abs=0.005)
    # a pair of the admission's attention: 64 heads x (192 + 128) x 2 operations a layer
    assert mm.mla_prefill_flops(CONFIG, 1000) == 1000 * 2 * 64 * 320 * 5
    # a 3,072-token prompt: 63 MFLOP a token of scores and values in an expert layer
    assert mm.mla_prefill_flops({**CONFIG, "num_hidden_layers": 1}, 3072 * 3073 // 2) / 3072 / M_ == \
        pytest.approx(62.9, abs=0.1)
    # a decode step of 8 lanes at 3k: 0.14 GB of cached rows, W_kv_b 16.8 MB a layer
    assert mm.mla_decode_bytes(CONFIG, 8 * 3072, 1) == 8 * 3072 * 5760 + 5 * 512 * 64 * 256 * 2
    assert mm.mla_decode_bytes(CONFIG, 8 * 3072, 0) / 1e9 == pytest.approx(0.14, abs=0.005)
    # 8 live rows: 16 held pairs a layer, 12.7 of 32 held experts hit
    assert mm.expected_held_hit(CONFIG, 8) == pytest.approx(12.9, abs=0.3)
    assert mm.decode_step_bytes(CONFIG, mm.expected_held_hit(CONFIG, 8)) / 1e9 == pytest.approx(
        4.69, abs=0.05)


def test_arithmetic_agrees_with_the_program():
    from benchmark.drivers.serve_sarvam_mla import mla_config
    from ray_tpu.models import sarvam_mla, sarvam_mla_decode

    cfg = mla_config(CONFIG)
    assert sarvam_mla.num_params(cfg) == mm.num_params(CONFIG)
    assert sarvam_mla_decode.state_bytes_per_lane(cfg) == 0
    assert sarvam_mla.num_params(sarvam_mla.SarvamMlaConfig()) == mm.num_params(FULL)
    assert cfg.latent_row * 2 * cfg.n_layers == mm.latent_bytes_per_token(CONFIG)


# ----------------------------------- the reference, spelled a second time
def test_reference_attention_against_a_numpy_loop():
    """One layer's latent attention over 9 positions in NumPy float64, one
    Python loop a head, a query position and a key position, from the
    equations of ISSUE 39: keys and values expanded from the latent, RoPE on
    k_r once for all heads, YaRN's blend and scale."""
    import math

    import jax
    import jax.numpy as jnp

    from benchmark import reference_sarvam_mla as R
    from benchmark import weights_sarvam_mla as W
    from ray_tpu.models.sarvam_mla import SarvamMlaConfig

    cfg = SarvamMlaConfig.tiny(dtype=jnp.float32, rope_original_max=4)  # 9 positions pass it
    T, h, r, nope, rope = 9, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    w32 = W.make_layer(jax.random.PRNGKey(5), cfg)
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), w32)
    u = np.random.default_rng(0).normal(size=(T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(R.attention(jnp.asarray(u, jnp.float32), R._f32(w32), cfg))

    norm = lambda x, g: x / np.sqrt((x * x).mean(-1, keepdims=True) + cfg.rms_eps) * g  # noqa: E731
    half = rope // 2
    freq = np.empty(half)
    pair = lambda turns: rope * math.log(4 / (turns * 2 * math.pi)) / (2 * math.log(10000.0))  # noqa: E731
    low, high = max(math.floor(pair(32)), 0), min(math.ceil(pair(1)), rope - 1)
    for i in range(half):
        base = 10000.0 ** (-i / half)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        freq[i] = base / 8.0 * ramp + base * (1.0 - ramp)
    assert 0 < sum(f < 10000.0 ** (-i / half) for i, f in enumerate(freq)) <= half  # some pairs blended

    def rot(x, t):
        c, s = np.cos(t * freq), np.sin(t * freq)
        return np.concatenate([x[:half] * c - x[half:] * s, x[half:] * c + x[:half] * s])

    scale = (nope + rope) ** -0.5 * (0.1 * math.log(8.0) + 1.0) ** 2
    q = norm((u @ w["wq"]).reshape(T, h, nope + rope), w["q_norm"])
    ckr = u @ w["w_kv_a"]
    c = norm(ckr[:, :r], w["kv_norm"])
    k_r = np.stack([rot(x, t) for t, x in enumerate(norm(ckr[:, r:], w["k_rope_norm"]))])
    out = np.zeros((T, h, cfg.v_head_dim))
    for head in range(h):
        for t in range(T):
            scores = []
            for j in range(t + 1):
                k_nope = w["w_uk"][head] @ c[j]
                scores.append(scale * (q[t, head, :nope] @ k_nope + rot(q[t, head, nope:], t) @ k_r[j]))
            p = np.exp(np.array(scores) - max(scores))
            p /= p.sum()
            out[t, head] = sum(p[j] * (c[j] @ w["w_uv"][head]) for j in range(t + 1))
    want = out.reshape(T, -1) @ w["wo"]
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_reference_gaps_are_its_logits_gaps_and_a_share_is_its_held_experts():
    import jax.numpy as jnp

    from benchmark import reference_sarvam_mla as R
    from benchmark import weights_sarvam_mla as W
    from ray_tpu.models.sarvam_mla import SarvamMlaConfig

    cfg = SarvamMlaConfig.tiny(dtype=jnp.float32)
    key = W.seed_key(2**31 + 7)
    tokens = np.random.default_rng(1).integers(0, 512, (2, 24)).astype(np.int32)
    first, count = np.array([10, 20], np.int32), np.array([6, 3], np.int32)
    gaps, spread = (np.asarray(a) for a in R.logit_gaps(
        key, jnp.asarray(tokens), jnp.asarray(first), jnp.asarray(count), cfg, 8))
    lg = np.asarray(R.logits(key, jnp.asarray(tokens), cfg))
    for s in range(2):
        for t in range(8):
            if t >= count[s]:
                assert gaps[s, t] == -1.0
                continue
            at = lg[s, first[s] - 1 + t]
            assert gaps[s, t] == pytest.approx(at.max() - at[tokens[s, first[s] + t]], abs=1e-4)
            assert spread[s, t] == pytest.approx(at.std(), rel=1e-3)
    # the held experts are made from the keys of their indices among the router's
    moe = W.init_params(key, cfg)["moe"]
    k_e = W.moe_keys(W.part_keys(key, cfg)[4][1], cfg)[2]
    np.testing.assert_array_equal(np.asarray(moe["experts"]["w_up"][1, 2]),
                                  np.asarray(W.make_expert(k_e[cfg.held_first + 2], cfg)["w_up"]))
    assert moe["experts"]["w_up"].shape[:2] == (2, 4) and moe["router"].shape == (2, 64, 16)


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
    cfg = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.sarvam_mla.json")
    return {"name": "test", "chips": 1, "config": "tiny.sarvam_mla", "traffic": "closed",
            "config_file": cfg, "traffic_file": CLOSED}


def test_mla_driver_end_to_end(cluster):
    from benchmark.drivers import serve_sarvam_mla

    out = serve_sarvam_mla.measure(_cell(), seed=2**31 + 39, seconds=3.0, trace=False,
                                   t_process_start=common.clock())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert {"logit_gap_mean", "logit_gap_p90", "tokens_checked"} <= {c["name"] for c in out["checks"]}
    engine = out["facts"]["engine"]
    assert engine["tokens_out"] > 0 and engine["ctx_tokens"] > 33 * engine["useful_slot_steps"]
    assert engine["prompt_pairs"] >= engine["requests_completed"] * 33 * 34 // 2
    # held experts only: a quarter of the router's, so fewer than top-4 pairs a row and layer
    assert 0 < engine["expert_rows"] < engine["useful_slot_steps"] * 4 * 2
    assert engine["expert_rows"] >= engine["experts_hit"] >= engine["expert_rows_max"] > 0
    assert out["facts"]["state_bytes"] == 0 and out["facts"]["lanes"] == 4
    assert out["device"]["platform"] == "cpu"  # never reported as a device metric


def test_mla_control_comes_out_incorrect(cluster):
    """The int8 control at the tiny size: the comparison that decides
    `correct` tells rounded weights from sound ones."""
    from benchmark.drivers import serve_sarvam_mla

    out = serve_sarvam_mla.measure(_cell(), seed=2**31 + 40, seconds=2.0, trace=False,
                                   t_process_start=common.clock(), lower_precision="int8")
    gap = next(c for c in out["checks"] if c["name"] == "logit_gap_mean")
    assert not gap["ok"] and gap["value"] > 5 * gap["limit"]


# ------------------------------------------- the marks in a device trace
def test_scope_of_takes_the_innermost_and_the_flash_kernel_is_the_admissions_context():
    stack = "jit(macro_step_slots_paged)/while/body/cond/branch_1_fun/decode_chunk/while/body/"
    assert S.scope_of(stack + "mla_proj/dot_general:") == "mla_proj"
    assert S.scope_of(stack + "mla_proj/mla_absorb/dot_general:") == "mla_absorb"
    assert S.scope_of(stack + "mla_ctx/while/body/dynamic_slice:") == "mla_ctx"
    assert S.scope_of(stack + "moe_experts/sort:") == "moe_experts" and S.scope_of(stack) == ""
    admit = stack.replace("decode_chunk", "admit_prefill")
    raw = [(0.0, 0.01, "%fusion.1 = bf16[8,64]", admit + "mla_proj/dot_general:"),
           (0.02, 0.01, "%flash_fwd.8 = (bf16[128,4096,128]) custom-call(...)", ""),
           (0.04, 0.01, "%fusion.2 = bf16[8,64]", admit + "moe_experts/sort:"),
           (0.06, 0.01, "%ragged-dot.3 = bf16[8,64]", ""),
           (0.08, 0.01, "%copy.4 = bf16[8,64]", "")]
    assert [(half, scope) for _, _, half, scope in S.scoped(raw)] == [
        ("admit_prefill", "mla_proj"), ("admit_prefill", "mla_ctx"),
        ("admit_prefill", "moe_experts"), ("admit_prefill", "moe_experts"), ("", "")]


def _recorded():
    data = common.load_json(f"{common.BENCH_DIR}/tests/data/sarvam_mla_trace_small.json")
    trace = {"window": tuple(data["window"]),
             "spans": [(n, s, d, st) for n, s, d, st in data["spans"]],
             "modules": [tuple(m) for m in data["modules"]]}
    return trace, sorted(tuple(op) for op in data["ops"])


def test_view_sums_scopes_by_half_and_counts_the_whole_executions_by_their_resolve():
    trace, ops = _recorded()
    v = S.view(trace, ops)
    # four executions have their middle in the window; seq 4 began before it and seq 7 is cut
    assert v["executions"] == 4 and v["counted_executions"] == 2
    assert v["macro_step_s"] == pytest.approx(1.15)
    w, c = v["window"], v["counted"]
    assert w["admit_prefill/mla_ctx"] == c["admit_prefill/mla_ctx"] == pytest.approx(0.02)
    assert w["decode_chunk/mla_ctx"] == pytest.approx(0.05)   # not the one outside a macro-step
    assert c["decode_chunk/mla_ctx"] == pytest.approx(0.03)   # nor seq 4's, nor seq 7's
    assert w["decode_chunk/mla_absorb"] == pytest.approx(0.02)
    assert w["decode_chunk/mla_proj"] == w["admit_prefill/mla_proj"] == pytest.approx(0.01)
    assert w["decode_chunk/moe_experts"] == pytest.approx(0.03)
    assert w["admit_prefill/moe_experts"] == pytest.approx(0.02)
    assert w["decode_chunk/all"] == pytest.approx(0.13) and w["admit_prefill/all"] == pytest.approx(0.06)
    assert c["decode_chunk/all"] == pytest.approx(0.11) and c["admit_prefill/all"] == pytest.approx(0.06)
    assert (v["counted_steps"], v["counted_lane_steps"], v["counted_prompt_tokens"],
            v["counted_ctx_tokens"], v["counted_prompt_pairs"]) == (22, 170, 5000, 510000, 6252500)
    assert (v["counted_experts_hit"], v["counted_expert_rows"], v["counted_expert_rows_max"]) == (
        1050, 1340, 58)
    assert S.view({**trace, "window": None}, ops) is None


def test_resolves_find_their_executions_where_the_dispatch_lies_before_the_trace():
    """Dispatch seq ran as executions[seq + offset]: a resolve that returns
    late (the next execution has ended too) reads one too far, and the least
    over the resolves and the dispatch pairs is right. Without a single
    `engine.dispatch` in the trace the resolves alone still find it."""
    trace, _ = _recorded()
    executions = sorted((s, d) for n, s, d in trace["modules"] if n.startswith("jit_macro"))
    want = [(4, executions[0]), (5, executions[1]), (6, executions[2])]
    got = S.pair_resolves(trace["spans"], executions)
    assert [(st["seq"], ex) for st, ex in got] == want
    late = [(n, s, 0.25 if st["seq"] == 5 and n == "engine.resolve" else d, st)
            for n, s, d, st in trace["spans"]]   # resolve(5) ends at 1.70, after seq 6 ended
    assert [(st["seq"], ex) for st, ex in S.pair_resolves(late, executions)] == want
    alone = [sp for sp in trace["spans"] if sp[0] == "engine.resolve"]
    assert [(st["seq"], ex) for st, ex in S.pair_resolves(alone, executions)] == want
    assert S.pair_resolves([sp for sp in trace["spans"] if sp[0] != "engine.resolve"], executions) == []
    # a program whose resolve spans carry no plan counts (the parent's): nothing is counted
    bare = [(n, s, d, {"seq": st["seq"]}) if n == "engine.resolve" else (n, s, d, st)
            for n, s, d, st in trace["spans"]]
    assert S.view({**trace, "spans": bare}, [])["counted_executions"] == 0


NEW_METRICS = ["programs.mla_share_pct", "kernels.mla_prefill_roofline_pct",
               "kernels.mla_decode_roofline_pct", "kernels.moe_held_roofline_pct",
               "kernels.moe_held_prefill_roofline_pct", "programs.decode_step_ms.tok_s"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_on_the_recorded_trace(metric, monkeypatch):
    trace, ops = _recorded()
    recorded = S.view(trace, ops)
    monkeypatch.setattr(S, "mla_view", lambda facts: recorded)
    peaks = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"facts": {}, "config": CONFIG, "peaks": peaks}
    got = common.load_module("layer_metrics", metric).read(ctx)
    if metric == "programs.mla_share_pct":
        assert got["value"] == pytest.approx(100.0 * 0.11 / 1.15)
        assert got["moe_share_pct"] == pytest.approx(100.0 * 0.07 / 1.15)
        assert got["decode_chunk_mla_ctx_s"] == pytest.approx(0.05)
    elif metric == "kernels.mla_prefill_roofline_pct":
        least = 6252500 * 2 * 64 * 320 * 5 / 197e12
        assert got["value"] == pytest.approx(100.0 * least / 0.02) and got["bound"] == "compute"
    elif metric == "kernels.mla_decode_roofline_pct":
        least = (510000 * 5760 + 22 * 5 * 512 * 64 * 256 * 2) / 819e9
        assert got["value"] == pytest.approx(100.0 * least / 0.05) and got["bound"] == "memory"
        assert got["ctx_tokens_a_lane_step"] == pytest.approx(3000.0)
        other = 22 * 5 * (mm.attn_matmul_params(CONFIG) - 512 * 64 * 256) * 2 / 819e9
        assert got["attention_half_pct"] == pytest.approx(100.0 * (least + other) / 0.06)
    elif metric == "kernels.moe_held_roofline_pct":
        least = (1050 * 50_331_648 + 1340 * 2 * 4096 * 2) / 819e9
        assert got["value"] == pytest.approx(100.0 * least / 0.03) and got["bound"] == "memory"
        assert got["held_hit_a_layer_step"] == pytest.approx(1050 / 88)
        assert got["live_rows_a_step"] == pytest.approx(1340 / 2 / 88)
        assert got["uniform_held_hit"] == pytest.approx(12.9, abs=0.3)
    elif metric == "kernels.moe_held_prefill_roofline_pct":
        # 5,000 real tokens x 4 expert layers x 2 held pairs x three products of 4096 x 2048
        least = 5000 * 4 * 2 * 2 * 3 * 4096 * 2048 / 197e12
        assert got["value"] == pytest.approx(100.0 * least / 0.02) and got["bound"] == "compute"
        assert got["held_pairs_a_token"] == 2.0
    else:
        assert got["value"] == pytest.approx(1e3 * 0.11 / 22) and got["steps"] == 22
    # a program without the scopes or without the counts on its resolve spans (the parent),
    # or an untraced run: nothing to read
    bare = [(n, s, d, {"seq": st["seq"]}) if n == "engine.resolve" else (n, s, d, st)
            for n, s, d, st in trace["spans"]]
    empty = (S.view({**trace, "spans": bare}, ops) if metric == "programs.decode_step_ms.tok_s"
             else S.view(trace, [(s, d, half, "") for s, d, half, _ in ops]))
    monkeypatch.setattr(S, "mla_view", lambda facts: empty)
    assert common.load_module("layer_metrics", metric).read(ctx) is None
    monkeypatch.setattr(S, "mla_view", lambda facts: None)
    assert common.load_module("layer_metrics", metric).read(ctx) is None


def test_the_accepted_expert_share_reader_reads_this_cells_trace_unedited(monkeypatch):
    """`programs.moe_share_pct` (PR 33's reader over `afmoe_spans`) on this
    model's recorded operations: the expert layer's three scopes are the
    shared `moe_ffn`'s, so it reads 0.07 of 1.15 s and the cell joins its
    `workloads`."""
    from benchmark import afmoe_spans

    trace, ops = _recorded()
    theirs = afmoe_spans.view(trace, [(s, d, half, scope if scope in afmoe_spans.SCOPES else "")
                                      for s, d, half, scope in ops])
    monkeypatch.setattr(afmoe_spans, "afmoe_view", lambda facts: theirs)
    got = common.load_module("layer_metrics", "programs.moe_share_pct").read({"facts": {}})
    assert got["value"] == pytest.approx(100.0 * 0.07 / 1.15)
    assert got["decode_step_ms"] == pytest.approx(1e3 * 0.04 / 12)  # seq 6, the one it can pair
    assert got["prefill_share_pct"] == pytest.approx(100.0 * 0.06 / 1.15)


def test_the_macro_step_under_its_tok_s_name_is_the_accepted_readers_value():
    facts = {"reduced": {"modules": {
        "jit_macro_step_slots_paged(1)": {"total_s": 2.0, "median_s": 0.7, "count": 3},
        "jit_other": {"total_s": 0.1, "median_s": 0.01, "count": 9}}}}
    got = common.load_module("layer_metrics", "programs.macro_step_ms.tok_s").read({"facts": facts})
    assert got == common.load_module("layer_metrics", "programs.macro_step_ms").read({"facts": facts})
    assert got["value"] == pytest.approx(700.0) and got["executions"] == 3
    assert common.load_module("layer_metrics", "programs.macro_step_ms.tok_s").read({"facts": {}}) is None

