"""The LFM2-MoE training configuration's benchmark files on the CPU: the
configuration held to ITS published widths against the catalog's row, the
model arithmetic against hand arithmetic at the published sizes and against
the program's parameter tree, the driver and the control end to end at a tiny
size, and the new readers on a small hand-built trace. No timing is asserted
or reported, and nothing pins the benchmark's SIZE or its LAST entries: a
later PR appends."""
import json
import os

import pytest

from benchmark import common, lfm2_moe_spans as S
from benchmark import model_math_lfm2_moe as mm

CONFIG = common.load_json(f"{common.BENCH_DIR}/configs/lfm2-8b-a1b.train.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
_A, _C = "full_attention", "conv"
# the catalog row's `config`, as this PR read it: kept here so that the test
# holds where the guide is not installed
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": [_C, _C, _A, _C, _C, _C, _A, _C, _C, _C, _A, _C, _C, _C, _A, _C, _C, _C, _A, _C,
                    _C, _A, _C, _C],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
KEPT = [0] + list(range(2, 13))
REDUCED = {"num_hidden_layers": 12, "num_dense_layers": 1, "num_experts": 8, "vocab_size": 16384,
           "layer_types": [PUBLISHED["layer_types"][i] for i in KEPT]}
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "pretrain-moe-8k"
NEW_METRICS = ("programs.mfu_pct.moe", "programs.moe_train_share_pct",
               "programs.short_conv_share_pct", "programs.optimizer_share_pct",
               "kernels.moe_train_roofline_pct", "programs.attn_train_share_pct",
               "kernels.flash_roofline_pct.moe")


# ------------------------------------------------------- the configuration
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_key(key):
    """Key by key: as published, or listed in `reduced` with the published
    value under `published`: depth (and with it the leading dense layers and
    the kept layers' types), the experts held and the vocabulary's slice. No
    width differs from the row."""
    assert sorted(CONFIG["reduced"]) == sorted(REDUCED)
    if key in REDUCED:
        assert CONFIG["published"][key] == PUBLISHED[key] and CONFIG[key] == REDUCED[key]
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_the_file_is_the_catalog_row_and_says_what_it_assumes():
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
        assert row["config"] == PUBLISHED and row["source_url"] == CONFIG["source"]
    bench = common.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/lfm2-8b-a1b.train.json"
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) == sorted(REDUCED)
    assert sorted(CONFIG["why_reduced"]) == sorted(REDUCED)
    assert {"head_dim", "tie_word_embeddings", "expert_bias", "eps_r", "no_auxiliary_loss",
            "optimizer", "torch_dtype"} <= set(CONFIG["assumed"])
    assert "NOT invented" in CONFIG["assumed"]["expert_bias"]
    assert (CONFIG["router_num_experts"], CONFIG["held_experts_first"], CONFIG["kept_layers"]) == (
        32, 0, KEPT)
    assert "two pipeline stages of twelve layers" in CONFIG["deployment"]
    assert "experts four ways" in CONFIG["deployment"] and "nothing stands in" in CONFIG["deployment"]
    assert CONFIG["departures"]["program"] and CONFIG["departures"]["reference"]
    assert CONFIG["driver"] == "train_lfm2_moe" and CONFIG["weights"] and CONFIG["check"]["why"]
    t = CONFIG["train"]
    assert (t["strategy"], t["attn_impl"], t["remat"], t["seq_len"], t["batch"]) == (
        "dp", "auto", True, 8192, 2)
    # a whole period, at least four layers after the dense one, at least 8
    # experts, at least an eighth of the vocabulary: the guide's floors
    assert CONFIG["layer_types"][1:5] == [_A, _C, _C, _C] and CONFIG["num_hidden_layers"] - 1 >= 4
    assert CONFIG["num_experts"] >= 8 and CONFIG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_cell_and_its_traffic_are_the_issues():
    bench = common.load_benchmark()
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [(CELL, "tokens-8k.steady", 1)]
    assert "1/4" in cells[0]["why"] and len(cells[0]["why"]) <= 200
    cell = common.load_cell(CELL)
    tf = cell["traffic_file"]
    assert (tf["kind"], tf["seq_len"], tf["batch"]) == ("train_job", 8192, 2)
    assert [m["name"] for m in cell["end_to_end"]] == ["train_tok_s", "setup_s"]
    names = [m["name"] for m in cell["per_layer"]]
    assert set(NEW_METRICS) | {"entry.first_step_s", "device.idle_pct.train"} == set(names)
    # the dense decoder's arithmetic is not this cell's
    assert not {"programs.mfu_pct", "kernels.flash_roofline_pct", "kernels.flash_fwd_ms"} & set(names)
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert (m["workloads"], m["moves"], m["unit"]) == ([CELL], "train_tok_s", "%")
            assert os.path.isfile(f"{common.BENCH_DIR}/layer_metrics/{m['name']}.py")


# ------------------------------------------------------------ the arithmetic
def test_model_arithmetic_by_hand():
    d, fe, f = 2048, 1792, 7168
    assert mm.expert_params(CONFIG) == 3 * d * fe == 11_010_048
    conv = 3 * d * d + d * d + 3 * d                 # W_in, W_out, three taps a channel
    attn = 2 * d * d + 2 * d * 512 + 2 * 64          # q, o, k, v, two head norms of 64
    expert_layer = d * 32 + 32 + 8 * 11_010_048      # router, choice bias, 8 held experts
    assert mm.layer_params(CONFIG, (_C, "dense")) == conv + 3 * d * f + 2 * d
    assert mm.layer_params(CONFIG, (_C, "moe")) == conv + expert_layer + 2 * d == 104_933_408
    assert mm.layer_params(CONFIG, (_A, "moe")) == attn + expert_layer + 2 * d == 98_635_936
    kept = 16384 * d + (conv + 3 * d * f + 2 * d) + 8 * 104_933_408 + 3 * 98_635_936 + d
    assert mm.num_params(CONFIG) == kept == 1_229_759_200
    assert mm.state_bytes(CONFIG) == 8 * kept                       # 9.84 GB: 61 % of the chip
    whole = mm.published(CONFIG)
    assert (whole["num_hidden_layers"], whole["num_experts"], whole["router_num_experts"]) == (24, 32, 32)
    assert mm.num_params(whole) == 8_339_930_560                    # 8.34 B, tied
    assert (mm.attention_layers(CONFIG), mm.expert_layers(CONFIG)) == (3, 11)
    # a step's required operations: 6 a matrix weight outside the experts, causal attention once,
    # 18 x d x fe a held pair
    outside = 9 * 4 * d * d + 3 * (2 * d * d + 2 * d * 512) + 3 * d * f + 11 * d * 32 + d * 16384
    assert mm.matmul_params_outside_experts(CONFIG) == outside
    tokens, pairs = 16384, 11 * 16384
    want = tokens * (6 * outside + 3 * 6 * 32 * 64 * 8192) + 18 * d * fe * pairs
    assert mm.train_flops(CONFIG, tokens, 8192, pairs) == want
    assert abs(want / tokens - 2.593e9) < 1e6
    assert mm.ragged_flops(CONFIG, pairs) == 18 * d * fe * pairs
    assert mm.ragged_bytes(CONFIG, pairs, 11) == 2 * 9 * ((d + fe) * pairs + 8 * d * fe * 11)
    roof = mm.roofline(mm.ragged_flops(CONFIG, pairs), mm.ragged_bytes(CONFIG, pairs, 11), PEAKS)
    assert roof["bound"] == "compute"


def test_arithmetic_agrees_with_the_program():
    from benchmark.drivers.train_lfm2_moe import lfm2_moe_config
    from ray_tpu.models import lfm2_moe

    cfg = lfm2_moe_config(CONFIG)
    assert (cfg.n_layers, cfg.held_experts, cfg.n_experts, cfg.head_dim, cfg.vocab_size) == (
        12, (0, 8), 32, 64, 16384)
    assert lfm2_moe.num_params(cfg) == mm.num_params(CONFIG)
    whole = lfm2_moe_config({**mm.published(CONFIG), "held_experts_first": 0})
    assert lfm2_moe.num_params(whole) == mm.num_params(mm.published(CONFIG)) == 8_339_930_560
    # the program's own estimate is the same arithmetic under a router in balance
    assert lfm2_moe.flops_per_token(cfg, 8192) == mm.train_flops(CONFIG, 1, 8192, 11)
    assert lfm2_moe.pair_chunk(cfg, 16384) == 20480


# ------------------------------------------------------- the driver, tiny
@pytest.fixture(scope="module")
def cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=1)
    yield
    ray_tpu.shutdown()


def _tiny_cell():
    cfg = common.load_json(f"{common.BENCH_DIR}/tests/data/tiny.lfm2_moe.json")
    return {"name": "test", "chips": 1, "config": "tiny.lfm2_moe", "traffic": "job",
            "config_file": cfg, "traffic_file": {"kind": "train_job", "seq_len": 64, "batch": 2}}


def test_driver_end_to_end(cluster):
    from benchmark.drivers import train_lfm2_moe

    out = train_lfm2_moe.measure(_tiny_cell(), seed=2**31 + 5, seconds=1.0, trace=False,
                                 t_process_start=common.clock(), platform="cpu")
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert all(c["ok"] for c in out["checks"]), out["checks"]
    assert set(train_lfm2_moe.GRADIENT_CHECKS) | {"compilations_in_window"} <= {
        c["name"] for c in out["checks"]}
    facts = out["facts"]
    assert facts["held_pairs_untraced"] > 0 and facts["untraced_steps"] == out["attempted"]
    assert out["device"]["platform"] == "cpu"  # never reported as a device metric
    ctx = {"cell": {}, "config": _tiny_cell()["config_file"], "facts": facts, "e2e": out["e2e"],
           "peaks": PEAKS}
    got = common.load_module("layer_metrics", "programs.mfu_pct.moe").read(ctx)
    assert got["untraced_steps"] == out["attempted"] and 0 < got["held_pairs_per_token_and_layer"] < 4
    for name in NEW_METRICS[1:]:  # an untraced run: nothing to read, and no reader raises
        assert common.load_module("layer_metrics", name).read(ctx) is None


def test_control_comes_out_incorrect(cluster):
    """The weights rounded to int8 where the configuration says float32: the
    same comparison, at the tiny cell's limits, says not correct."""
    from benchmark.drivers import train_lfm2_moe

    out = train_lfm2_moe.measure(_tiny_cell(), seed=2**31 + 5, seconds=0.5, trace=False,
                                 t_process_start=common.clock(), platform="cpu",
                                 lower_precision="int8")
    bad = [c["name"] for c in out["checks"] if not c["ok"]]
    assert bad and set(bad) <= set(train_lfm2_moe.GRADIENT_CHECKS)


def test_the_checks_check_at_a_tiny_size():
    """benchmark/check_lfm2_moe.py, every phase, in float32: the program as it
    is and with every layer's second pass taken come out correct and read the
    same; a gradient of zero for the experts, and a ragged product whose
    backward halves its d rhs, come out not correct by the experts' own error
    and leave the routers' alone; float32 against float32 flips no choice."""
    from benchmark import check_lfm2_moe

    rows = check_lfm2_moe.run(_tiny_cell(), seed=2**31 + 7)
    assert rows["device"]["platform"] == "cpu"
    verdicts = lambda name: {c["name"]: c["ok"] for c in rows[name]["checks"]}  # noqa: E731
    assert rows["sound"]["correct"] and rows["second_pass"]["correct"]
    assert (rows["sound"]["counters"]["second_passes"], rows["second_pass"]["counters"]["second_passes"]) == (
        0, rows["second_pass"]["expert_layers"])
    for fault, err in (("experts_zero", 1.0), ("d_rhs_halved", 0.5)):
        assert not rows[fault]["correct"]
        assert not verdicts(fault)["grad_rel_err_experts"] and verdicts(fault)["grad_rel_err_router"]
        assert abs(rows[fault]["parts"]["experts"]["rel_err"] - err) < 1e-5
    assert rows["flips"]["pairs"] == 2 * 64 * 3 and rows["flips"]["pairs_differ"] == 0


@pytest.mark.parametrize("part, scale", [("experts", 0.0), ("experts", 0.5), ("experts", -1.0),
                                         ("router", 0.0)])
def test_a_wrong_expert_or_router_gradient_is_not_correct_under_the_committed_limits(part, scale):
    """What the whole tree's norm cannot see (REVIEW 57): the experts' matrices
    are 0.65 % of the gradient's squared norm at the cell's size and the
    routers 0.006 % (my chip runs, PR 57), so with that part of the system's
    gradient scaled by `scale` the whole tree still reads under its limit; the
    part's own error, judged beside it, does not."""
    from benchmark.drivers import train_lfm2_moe

    check = CONFIG["check"]
    share = {"experts": 0.0065, "router": 0.00006}[part]
    sound = {"grad_rel_err": 0.174, "grad_rel_err_experts": 0.234, "grad_rel_err_router": 0.291,
             "grad_row_err_median": 0.096}  # the largest sound readings (PERF.md section 2)
    assert all(c["ok"] for c in train_lfm2_moe.gradient_checks(sound, check))
    # ||scale g - r|| / ||r|| for a g that stands e from r with ||g|| = ||r||
    e = sound["grad_rel_err_" + part]
    wrong = (scale * scale - 2 * scale * (1 - e * e / 2) + 1) ** 0.5
    whole = (sound["grad_rel_err"] ** 2 + share * (wrong ** 2 - e ** 2)) ** 0.5
    verdicts = {c["name"]: c["ok"] for c in train_lfm2_moe.gradient_checks(
        {**sound, "grad_rel_err": whole, "grad_rel_err_" + part: wrong}, check)}
    assert verdicts["grad_rel_err"] and not verdicts["grad_rel_err_" + part]


# ----------------------------------------------------------- the new readers
def test_scope_of_takes_the_innermost_whole_word():
    assert S.scope_of("fusion.1", "jit(step_fn)/jvp(short_conv)/dot_general") == S.CONV
    assert S.scope_of("fusion.2", "jit(step_fn)/transpose(jvp(jvp()))/checkpoint/moe_experts/add_any") == S.EXPERTS
    assert S.scope_of("fusion.3", "jit(step_fn)/checkpoint/rematted_computation/attn/flash_fwd/pallas_call") == S.ATTN
    assert S.scope_of("fusion.4", "jit(step_fn)/optimizer/mul") == S.OPTIMIZER
    assert S.scope_of("fusion.5", "jit(step_fn)/jvp(moe_route)/moe_experts/gather") == S.EXPERTS
    # a part of another name is no scope; a kernel the compiler named itself is the expert layer's
    assert S.scope_of("fusion.6", "jit(step_fn)/flash_attn_thing/mul") == S.REST
    assert S.scope_of("%ragged-dot-none.7 = bf16[20480,1792] custom-call()", "") == S.EXPERTS
    assert S.scope_of("copy.8", "") == S.REST


def _recorded():
    """Two traced steps by hand: (start_s, duration_s, HLO name, name stack)."""
    ops, t = [], 10.0

    def op(dur, name, text):
        nonlocal t
        ops.append((t, dur, name, text))
        t += dur

    for _ in range(2):
        op(0.010, "fusion.a", "jit(step_fn)/jvp(short_conv)/dot_general")
        op(0.002, "fusion.b", "jit(step_fn)/jvp(moe_route)/top_k")
        op(0.001, "%ragged-dot-metadata.1 = (s32[9]) custom-call()", "")
        for _ in range(9):
            op(0.002, "%ragged-dot-none.2 = bf16[20480,1792] custom-call()", "")
        op(0.004, "fusion.c", "jit(step_fn)/transpose(jvp(jvp()))/checkpoint/moe_experts/scatter-add")
        op(0.006, "fusion.d", "jit(step_fn)/transpose(jvp(jvp()))/checkpoint/short_conv/mul")
        op(0.005, "fusion.e", "jit(step_fn)/optimizer/mul")
        op(0.003, "fusion.f", "jit(step_fn)/jvp()/add")
        op(0.040, "flash_fwd.1", "jit(step_fn)/jvp(attn)/flash_fwd/pallas_call")
    # behind the window: counted by no reader
    ops.append((99.0, 1.0, "fusion.z", "jit(step_fn)/optimizer/mul"))
    return ops, (9.9, t + 0.1)


def _ctx(monkeypatch, ops, window, held=2 * 11 * 2048):
    from benchmark import program_spans

    trace = {"named_ops": ops, "window": window}
    monkeypatch.setattr(program_spans, "run_trace", lambda facts: trace)
    busy = sum(d for s, d, _, _ in ops if window[0] <= s + d / 2 <= window[1])
    pallas = [d for s, d, _, text in ops if "pallas_call" in text and window[0] <= s + d / 2 <= window[1]]
    # as trace_reduce labels an operation: a Pallas call by its kernel's name and `tpu_custom_call`
    by_label = {"flash_fwd.1 tpu_custom_call": {"count": len(pallas), "total_s": sum(pallas)},
                # the compiler's ragged kernels carry the same label on the chip (my chip run, PR 57)
                "ragged-dot-none.262 tpu_custom_call": {"count": 18, "total_s": 0.036},
                "fusion.a bf16[16384,2048] kOutput": {"count": 2, "total_s": 0.02}}
    facts = {"reduced": {"busy_s": busy, "window_s": window[1] - window[0], "devices": 1, "ops": by_label},
             "traced_steps": 2, "held_pairs_traced": held, "held_pairs_untraced": 30 * 11 * 16384,
             "untraced_steps": 30, "untraced_s": 15.0, "job": {"batch": 2, "seq_len": 8192}}
    return {"cell": {}, "config": CONFIG, "facts": facts, "e2e": {}, "peaks": PEAKS}


def test_view_sums_scopes_inside_the_window(monkeypatch):
    ops, window = _recorded()
    v = S.view(ops, window)
    assert abs(v["by_scope"][S.CONV] - 2 * 0.016) < 1e-12
    assert abs(v["by_scope"][S.EXPERTS] - 2 * (0.001 + 9 * 0.002 + 0.004)) < 1e-12
    assert abs(v["by_scope"][S.OPTIMIZER] - 2 * 0.005) < 1e-12 and abs(v["by_scope"][S.REST] - 0.006) < 1e-12
    assert (v["ragged_calls"], round(v["ragged_s"], 9), round(v["ragged_metadata_s"], 9)) == (18, 0.036, 0.002)
    assert abs(v["by_scope"][S.ATTN] - 2 * 0.040) < 1e-12 and abs(v["ops_s"] - 2 * 0.089) < 1e-12
    assert S.view(ops, None) is None
    assert S.view([(10.0, 0.1, "fusion.q", "jit(step_fn)/jvp()/add")], window) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_on_the_recorded_trace(metric, monkeypatch):
    ops, window = _recorded()
    read = common.load_module("layer_metrics", metric).read
    got = read(_ctx(monkeypatch, ops, window))
    step = 0.089
    want = {"programs.moe_train_share_pct": 100 * 0.025 / step,
            "programs.attn_train_share_pct": 100 * 0.040 / step,
            # three attention layers of the twelve: 7 products of 2 x T x T x 64 a head, causal
            # half, 32 heads, 2 sequences, 2 steps, over two kernels of 40 ms
            "kernels.flash_roofline_pct.moe":
                100 * (2 * 3 * 2 * 32 * 7 * 8192 * 8192 * 64 / 197e12) / 0.080,
            "programs.short_conv_share_pct": 100 * 0.016 / step,
            "programs.optimizer_share_pct": 100 * 0.005 / step,
            # at 2,048 pairs a layer the bytes bound: nine products' rows and, a layer and
            # step, the held experts' matrices, over the bandwidth, over 18 kernels of 2 ms
            "kernels.moe_train_roofline_pct":
                100 * (2 * 9 * (3840 * 2 * 11 * 2048 + 8 * 2048 * 1792 * 11 * 2) / 819e9) / 0.036,
            "programs.mfu_pct.moe":
                100 * mm.train_flops(CONFIG, 30 * 16384, 8192, 30 * 11 * 16384) / 15.0 / 197e12}[metric]
    assert abs(got["value"] - want) < 1e-9 * want and 0 < got["value"] < 100
    if "share" in metric:
        assert abs(got["scopes_sum_over_busy"] - 1.0) < 1e-9
    if "flash" in metric:
        assert got["bound"] == "compute" and got["attention_layers"] == 3
    elif "roofline" in metric:
        assert got["bound"] == "memory" and got["ragged_calls"] == 18
        assert got["compute_s"] == 18 * 2048 * 1792 * 2 * 11 * 2048 / 197e12 < got["memory_s"]
    # a program without the scopes (the parent, another model's step): nothing to read, no error
    bare = [(s, d, "fusion.x", "jit(step_fn)/jvp()/dot_general") for s, d, _, _ in ops]
    ctx = _ctx(monkeypatch, bare, window)
    if metric != "programs.mfu_pct.moe":
        assert read(ctx) is None
    ctx["facts"] = {"reduced": ctx["facts"]["reduced"], "job": {"batch": 2, "seq_len": 8192}}
    assert read(ctx) is None  # the parent's driver hands over no counters either
