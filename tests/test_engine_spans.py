"""Names, scopes and spans that a device trace is read by (ISSUE 27).

- every jitted decode program carries its function's name (never
  `jit__unknown`) and the three flash-attention Pallas calls their own;
- the names and the two `jax.named_scope`s of the macro-step are metadata
  only: the lowered program, names and metadata stripped, is the one the same
  code gives with `named_scope`, `name=` and `_bind` patched out;
- the engine's macro loop tiles its thread with `ENGINE_SPANS` in a
  `jax.profiler` trace, and each `engine.dispatch` carries the plan's counts.

CPU, tiny sizes; the Pallas calls are lowered FOR the tpu platform
(`lowering_platforms`), which needs no chip and compiles nothing. The profiler
is started inside a test, never at import.
"""
import base64
import contextlib
import dataclasses
import functools
import glob
import re
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, paged
from ray_tpu.models import llama_decode as D
from ray_tpu.observability import ENGINE_SPANS, REQUEST_SPAN
from ray_tpu.ops import flash_attention as FA
from ray_tpu.serve import llm_engine
from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

N_SLOTS, MAX_LEN, BLOCK, N_BLOCKS = 2, 32, 8, 10
MB = MAX_LEN // BLOCK
K, A, P, NS, CHUNK, N_SPEC = 2, 1, 16, 4, 4, 2


@functools.lru_cache(maxsize=1)
def _cfg_params():
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise", remat=False)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _plan_args():
    """Plan arrays in the order the macro-steps take them after `feed`."""
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    return (i32(K), jnp.zeros(K, bool), i32(K, A, P), i32(K, A),
            i32(K, A), i32(K, A), i32(K, A),                        # starts, slots, rems
            jnp.zeros((K, A), jnp.uint32), i32(K, N_SLOTS, MB),
            jnp.zeros((K, N_SLOTS), jnp.float32), i32(K, N_SLOTS),
            jnp.ones((K, N_SLOTS), jnp.float32),
            jnp.full((K, N_SLOTS, NS), -1, jnp.int32))


def _spec_parts():
    from ray_tpu.serve._internal.speculative import resolve_draft_model

    cfg, params = _cfg_params()
    draft_params, draft_cfg = resolve_draft_model(
        {"cfg": dataclasses.replace(cfg, d_model=96, d_ff=192)}, params, cfg)
    return draft_params, draft_cfg


def _program(name: str):
    """(jitted program from its factory, arguments to lower it with)."""
    cfg, params = _cfg_params()
    feed = jnp.zeros(N_SLOTS, jnp.int32)
    dense = lambda: D.init_cache(cfg, 1, MAX_LEN)  # noqa: E731
    pool = lambda: D.init_paged_cache(cfg, N_SLOTS, N_BLOCKS, BLOCK)  # noqa: E731
    blocks = jnp.zeros(2, jnp.int32)
    kv = jnp.zeros((cfg.n_layers, 2, BLOCK, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    if name == "prefill":
        return D._jitted_prefill(cfg), (params, jnp.zeros((1, 8), jnp.int32), dense())
    if name == "decode_step":
        return D._jitted_decode_step(cfg), (params, dense(), jnp.zeros(1, jnp.int32))
    if name == "decode_loop":
        return D._jitted_decode_loop(cfg, 3), (params, dense(), jnp.zeros(1, jnp.int32))
    if name == "sample_loop":
        return D._jitted_sample_loop(cfg, 3), (
            params, dense(), jnp.zeros((1, cfg.vocab_size), jnp.float32),
            jax.random.PRNGKey(0), 1.0, 0, 1.0)
    if name == "macro_step_slots_paged":
        return D.jitted_macro_step_slots_paged(cfg, CHUNK, sampled=True), (
            params, pool(), feed) + _plan_args()
    if name == "macro_step_slots_spec":
        draft_params, draft_cfg = _spec_parts()
        return D.jitted_macro_step_slots_spec(cfg, draft_cfg, CHUNK, N_SPEC), (
            params, draft_params, pool(),
            D.init_spec_cache(draft_cfg, N_SLOTS, N_BLOCKS, BLOCK), feed) + _plan_args()
    if name == "gather_kv_blocks":
        return paged.jitted_gather_kv_blocks(), (pool(), blocks)
    if name == "scatter_kv_blocks":
        return paged.jitted_scatter_kv_blocks(), (pool(), blocks, kv, kv)
    if name == "import_kv_blocks":
        return paged.jitted_import_kv_blocks(), (
            pool(), blocks, kv, kv, jnp.int32(0), jnp.int32(0), jnp.int32(0),
            jnp.zeros(2, jnp.uint32))
    raise KeyError(name)


PROGRAMS = ("prefill", "decode_step", "decode_loop", "sample_loop", "macro_step_slots_paged",
            "macro_step_slots_spec", "gather_kv_blocks", "scatter_kv_blocks",
            "import_kv_blocks")
# the factories of the block movers live with the movers, in models/paged.py
IN_PAGED = {"gather_kv_blocks", "scatter_kv_blocks", "import_kv_blocks"}


def test_every_jitted_factory_is_listed():
    """A factory of either module is a case of PROGRAMS, in the one module
    that holds it (a name imported from the other would be found twice)."""
    def factories(module):
        return {n for n in vars(module) if n.startswith(("jitted_", "_jitted_"))}

    assert factories(paged) == {"jitted_" + n for n in IN_PAGED}
    assert factories(D) == {("_jitted_" if n in ("prefill", "decode_step", "decode_loop",
                                                 "sample_loop") else "jitted_") + n
                            for n in set(PROGRAMS) - IN_PAGED}


@pytest.mark.parametrize("name", PROGRAMS)
def test_jitted_program_carries_its_functions_name(name):
    jitted, args = _program(name)
    module = re.match(r"module @(\S+)", jitted.lower(*args).as_text()).group(1)
    assert module == "jit_" + name, module


# ------------------------------------------------------- the Pallas calls
def _flash_lowered(which: str):
    B, T, H, KVH, Dh = 1, 256, 4, 2, 64
    q = jax.ShapeDtypeStruct((B, T, H, Dh), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, T, KVH, Dh), jnp.bfloat16)
    if which == "fwd":
        fn = functools.partial(FA._flash_fwd_pallas, causal=True, sm_scale=None,
                               block_q=128, block_k=128, interpret=False)
        args = (q, kv, kv)
    else:
        fn = functools.partial(FA._flash_bwd_pallas, causal=True, sm_scale=None,
                               block_q=128, block_k=128)
        lse = jax.ShapeDtypeStruct((B, T, H), jnp.float32)
        args = (q, kv, kv, q, lse, q)
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def test_pallas_calls_carry_their_names():
    names = re.findall(r'kernel_name = "([^"]*)"',
                       _flash_lowered("fwd").as_text() + _flash_lowered("bwd").as_text())
    assert names == ["flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq"]


# ------------------------------------- names and scopes are metadata only
_MOSAIC_BODY = re.compile(r'(body\\22: \\22)([A-Za-z0-9+/=]+)')


def _mosaic_text(body_b64: str) -> str:
    """A Pallas kernel rides its custom call as serialized MLIR; its text
    without locations, so that two kernels compare as programs."""
    from jax.extend.mlir import ir
    from jax.interpreters import mlir

    with mlir.make_ir_context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(body_b64))
        return module.operation.get_asm(enable_debug_info=False)


def _stripped(lowered) -> str:
    """The lowered program without names and metadata: `as_text()` carries no
    locations (where a named scope lives); module and kernel names blanked."""
    text = _MOSAIC_BODY.sub(lambda m: m.group(1) + _mosaic_text(m.group(2)), lowered.as_text())
    text = re.sub(r'kernel_name = "[^"]*"', 'kernel_name = ""', text)
    return re.sub(r"module @\S+", "module @_", text)


@contextlib.contextmanager
def _names_patched_out(monkeypatch):
    """The same code with no `named_scope`, no `name=` on a Pallas call and
    bare partials under `jax.jit`."""
    real_pl = FA.pl
    pl = types.SimpleNamespace(**{k: getattr(real_pl, k) for k in dir(real_pl)
                                  if not k.startswith("__")})
    pl.pallas_call = lambda *a, name=None, **kw: real_pl.pallas_call(*a, **kw)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        m.setattr(paged, "_bind", functools.partial)
        m.setattr(FA, "pl", pl)
        yield


def _lower_macro(name: str):
    for f in (D.jitted_macro_step_slots_paged, D.jitted_macro_step_slots_spec):
        f.cache_clear()  # the factories memoize the jitted function
    jitted, args = _program(name)
    return jitted.lower(*args)


def _lower_train_step(monkeypatch):
    """The program's own train step with the flash kernels in it, lowered for
    the tpu platform (on the CPU `attn_impl="flash"` takes the XLA route)."""
    from ray_tpu.train.step import setup_sharded_training

    monkeypatch.setattr(FA, "_on_tpu", lambda: True)
    cfg = llama.LlamaConfig.tiny(d_model=256, attn_impl="flash", remat=True)  # head_dim 64
    _, init_fn, step_fn, shard_batch, _ = setup_sharded_training(
        cfg, strategy="dp", devices=jax.devices()[:1])
    state = init_fn(jax.random.PRNGKey(0))
    batch = shard_batch({"tokens": np.zeros((1, 129), np.int32)})
    return step_fn.__wrapped__.trace(state, batch).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("name", ["macro_step_slots_paged", "macro_step_slots_spec",
                                  "train_step"])
def test_names_and_scopes_are_metadata_only(name, monkeypatch):
    lower = (functools.partial(_lower_train_step, monkeypatch) if name == "train_step"
             else functools.partial(_lower_macro, name))
    named = lower()
    with _names_patched_out(monkeypatch):
        bare = lower()
    with_locations = named.as_text(debug_info=True)
    if name == "train_step":
        assert with_locations.count("tpu_custom_call") == 3  # fwd (once: remat keeps what it made), dK/dV, dQ
        assert 'kernel_name = "flash_fwd"' in named.as_text()
        assert 'kernel_name = "flash_fwd"' not in bare.as_text()
    else:
        assert paged.ADMIT_SCOPE in with_locations and paged.DECODE_SCOPE in with_locations
        assert paged.ADMIT_SCOPE not in bare.as_text(debug_info=True)
        assert bare.as_text().startswith("module @jit__unknown")
    assert _stripped(named) == _stripped(bare)
    for f in (D.jitted_macro_step_slots_paged, D.jitted_macro_step_slots_spec):
        f.cache_clear()  # nothing built under the patch outlives it


# ------------------------------------------------- the plan's counts (d)
def _req(prompt_len, remaining, start=0, max_new=1, submit=0.0, seen=0.0, admit=0.0):
    return types.SimpleNamespace(prompt=[0] * prompt_len, _start=start, _remaining=remaining,
                                 max_new_tokens=max_new, _t_submit=submit, _t_seen=seen,
                                 _t_admit=admit, _acct=llm_engine._Account())


def test_finish_wait_steps_on_a_hand_built_plan():
    """Three lanes (the third never taken), chunk 4, four phases;
    `_remaining` is the state `_plan` leaves behind (0 = the request's last
    token is in this dispatch). The plan began at 10.25 s: a, b and d were
    first seen by it, c by an earlier one (the stamps are binary fractions,
    so every difference is exact)."""
    a, b, c, d = (_req(9, 0, max_new=3, submit=10.0, seen=10.25, admit=10.25),
                  _req(12, 7, max_new=20, submit=10.0, seen=10.25, admit=10.25),
                  _req(20, 0, start=8, max_new=7, submit=9.0, seen=9.5, admit=10.25),
                  _req(5, 0, submit=10.125, seen=10.25, admit=10.25))
    phases = [
        # lane 2 empty and nobody waiting
        {"steps": 2, "admissions": [(0, a), (1, b)], "takes": [(0, a, 2), (1, b, 2)],
         "vacant": 1, "blocked": 0},
        # lane 2 empty with a request waiting that the pool refused
        {"steps": 4, "admissions": [(0, c)], "takes": [(0, c, 4), (1, b, 4)],
         "vacant": 0, "blocked": 1},
        # a phase that says neither: its empty lane counts as vacant
        {"steps": 2, "admissions": [], "takes": [(0, c, 2), (1, b, 2)]},
        # d owes one token only: the prefill's own, before this phase's steps
        {"steps": 4, "admissions": [(0, d)], "takes": [(1, b, 4)], "vacant": 0, "blocked": 1},
    ]
    counts = llm_engine._dispatch_counts(phases)
    assert counts == {
        "phases": 4, "steps": 12, "admissions": 4, "prompt_tokens": 9 + 12 + 12 + 5,
        "prefix_tokens": 8, "lane_steps": 20, "finishing": 3,
        # a after 2 of 12 steps, c after 8, d after 8 (its phase's 4 steps follow it)
        "finish_wait_steps": 10 + 4 + 4,
        # a attends 10, 11; b 13..24; c 21..26: what the decode steps read
        "ctx_tokens": 21 + 222 + 141,
        # n (n + 1) / 2 a prompt, and c's 12 new tokens over its 8 reused
        "prompt_pairs": 45 + 78 + (96 + 78) + 15,
        # no compiled variant given: no rows
        "admit_rows": 0,
        # phases 0, 1 and 3 admit, each through one admission body
        "admit_phases": 3, "admit_pieces": 3,
        # submit to first seen: a and b 0.25 s, c 0.5 s, d 0.125 s; c alone
        # waited on for a lane, 9.5 to 10.25; the three others in their first plan
        "plan_wait_us": 250_000 + 250_000 + 500_000 + 125_000, "lane_wait_us": 750_000,
        "admitted_first_plan": 3,
        # c behind phase 0's 2 steps and its admission, d behind 8 steps and
        # two admitting phases (0 and 1; phase 2 admits nobody)
        "admit_lead_steps": 2 + 8, "admit_lead_phases": 1 + 2,
        # b is live through c's admission and through d's
        "stall_lane_phases": 2,
        # the last phase is not one a vacant lane closed
        "short": 0, "q": 0}
    # a plan whose last phase a vacant lane closed says so, with its quantum
    closed = llm_engine._dispatch_counts(phases[:1] + [{**phases[2], "vacant": 1, "short": 3}])
    assert (closed["short"], closed["q"]) == (1, 3)
    # three phases admit, 2, 1 and 1 prompts: each runs at the width of its
    # own admissions (`admit_pieces`), not of the program, so in a program of
    # (2, 16) they are 2 x 16 + 1 x 16 + 1 x 16 = 64 rows of the 3 x 2 x 16 =
    # 96 that every phase at full width would be, and 64 as well in one of
    # (4, 16); a program one lane wide cannot run wider than that
    assert llm_engine._dispatch_counts(phases, variant=(2, 16)) == {**counts, "admit_rows": 64}
    assert llm_engine._dispatch_counts(phases, variant=(4, 16))["admit_rows"] == 64
    assert llm_engine._dispatch_counts(phases, variant=(1, 16))["admit_rows"] == 48
    # three prompts a phase take four rows, five take eight, where a row is
    # too short to be worth a pass over the weights: 4 x 32 + 8 x 32 in two
    # bodies; at P = 1024 they run as 2 + 1 and 4 + 1 rows, in four
    wide = [{"steps": 0, "admissions": [(i, d) for i in range(n)], "takes": []} for n in (3, 5)]
    short, long = (llm_engine._dispatch_counts(wide, variant=(8, P)) for P in (32, 1024))
    assert (short["admit_rows"], short["admit_pieces"], short["admit_phases"]) == (384, 2, 2)
    assert (long["admit_rows"], long["admit_pieces"], long["admit_phases"]) == (8 * 1024, 4, 2)
    # the lane account of three lanes: lane 2 vacant through phases 0 and 2
    # (2 + 2 steps), blocked through 1 and 3 (4 + 4), lane 0 spent on d (4)
    lanes = llm_engine._dispatch_counts(phases, n_slots=3)
    assert lanes == {**counts, "vacant_lane_steps": 4, "blocked_lane_steps": 8,
                     "spent_lane_steps": 4}
    assert 20 + 4 + 8 + 4 == 3 * 12
    # a plan that finishes nobody waits for nothing
    assert llm_engine._dispatch_counts([{"steps": 4, "admissions": [], "takes": [(1, b, 4)]}])[
        "finish_wait_steps"] == 0
    # chunks of 8 positions the decode attention reads, a step: the longest
    # live context is b's 13, 14; c's 21..24; c's 25, 26; b's 21..24
    with_ctx = llm_engine._dispatch_counts(phases, ctx_chunk=8)
    assert with_ctx == {**counts, "ctx_chunks": 2 * 2 + 4 * 3 + 2 * 4 + 4 * 3}


def _drive(eng, prompts_and_answers):
    """Run requests through a paged engine whose loop is stopped, one plan at
    a time: -> (requests, per dispatch its phases' (steps, [(request, take)])
    and its counts)."""
    reqs = [eng.submit(p, n) for p, n in prompts_and_answers]
    eng._drain_queue()
    seen = []
    while eng._waiting or any(r is not None for r in eng._slots):
        phases = eng._plan()
        A, P = eng._variant(phases)
        counts = llm_engine._dispatch_counts(phases, False, eng._ctx_chunk, variant=(A, P),
                                             n_slots=eng.n_slots)
        # the lane account: every lane-step of the dispatch is live, vacant, blocked or spent
        assert (counts["lane_steps"] + counts["vacant_lane_steps"] + counts["blocked_lane_steps"]
                + counts["spent_lane_steps"] == eng.n_slots * counts["steps"])
        # the admissions' rows with their padding, as the device runs them:
        # the pieces of a phase's count, times P; A is the lanes' bucket and
        # bounds every phase
        assert A == 1 << (eng.n_slots - 1).bit_length()
        admitting = [len(ph["admissions"]) for ph in phases if ph["admissions"]]
        pieces = [paged.admit_pieces(n, A, P) for n in admitting]
        assert counts["admit_rows"] == P * sum(map(sum, pieces))
        assert counts["admit_pieces"] == sum(map(len, pieces)) >= counts["admit_phases"]
        assert counts["prompt_tokens"] <= counts["admit_rows"] <= A * P * len(admitting)
        seen.append(([(ph["steps"], [(r, t) for _, r, t in ph["takes"]]) for ph in phases], counts))
        eng._dispatch_macro(phases, counts)
    while eng._pending:
        eng._resolve(eng._pending.popleft())
    assert all(r.done.is_set() and r.error is None for r in reqs)
    return reqs, seen


def test_ctx_chunks_follow_the_planned_contexts():
    """`ctx_chunks` is the decode attention's trip counts: per decode step
    ceil(longest live context / chunk), where a lane's context is its prompt,
    what it has decoded and the token it feeds. Recomputed forwards from the
    requests' lengths (the engine walks its plan backwards from
    `_remaining`); the metric is the spans' sum and never passes
    `span_chunks`, every step reading the whole table span."""
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, chunk=4, macro_phases=4,
                                   max_len=1024, block_size=16, prefix_cache=False)
    eng.shutdown()  # the plans below are made on this thread
    C = paged.decode_chunk_positions(16, 1024 // 16)
    assert eng._ctx_chunk == C == 128
    rng = np.random.default_rng(1)
    prompt = lambda n: rng.integers(0, cfg.vocab_size, n).tolist()  # noqa: E731

    # one request alone: 11 decode steps on contexts of 251..261 positions,
    # six of them inside the second chunk, five reaching into the third
    _, seen = _drive(eng, [(prompt(250), 12)])
    assert sum(c["steps"] for _, c in seen) == 11
    assert sum(c["ctx_chunks"] for _, c in seen) == 6 * 2 + 5 * 3
    m = eng.metrics()
    assert (m["ctx_chunks"], m["span_chunks"]) == (27, 11 * 8)

    # lanes of unequal length that come and go: the longest live lane decides
    reqs, seen = _drive(eng, [(prompt(250), 12), (prompt(30), 9), (prompt(505), 10),
                              (prompt(17), 5)])
    decoded = {id(r): 0 for r in reqs}
    want = steps = 0
    for phases, counts in seen:
        mine = 0
        for n, takes in phases:
            assert all(t == n for _, t in takes)
            longest = max((len(r.prompt) + decoded[id(r)] + 1 for r, _ in takes), default=0)
            mine += sum(-(-(longest + t) // C) for t in range(n))
            for r, t in takes:
                decoded[id(r)] += t
            steps += n
        assert counts["ctx_chunks"] == mine <= counts["steps"] * 8
        want += mine
    assert [decoded[id(r)] for r in reqs] == [11, 8, 9, 4]
    assert steps < want < 8 * steps  # some steps read one chunk, none the whole span
    m2 = eng.metrics()
    assert m2["ctx_chunks"] - m["ctx_chunks"] == want
    assert m2["span_chunks"] - m["span_chunks"] == 8 * steps
    # four prompts in buckets of 256, 32, 512 and 32 positions at the least
    assert (m2["admit_rows"] - m["admit_rows"] == sum(c["admit_rows"] for _, c in seen)
            >= 256 + 32 + 512 + 32 > 0)
    assert m2["admit_pieces"] - m["admit_pieces"] == sum(c["admit_pieces"] for _, c in seen) > 0


# ------------------------------------ the lane and the wait accounts (ISSUE 41)
LANE_SCENARIOS = {
    # (engine options, [(prompt length, answer)]): what the account has to read
    # one request and a one-token one on four lanes: lanes empty, nobody waiting
    "fewer_requests_than_lanes": (dict(n_slots=4), [(9, 12), (11, 1)]),
    # six equal requests on two lanes: a pair admitted as the pair before it ends
    "more_requests_than_lanes": (dict(n_slots=2), [(9, 5)] * 6),
    # four blocks a request and five in the pool: one lane runs, the queue waits on blocks
    "pool_too_small_for_the_queue": (dict(n_slots=2, n_blocks=6), [(20, 12)] * 3),
}


@pytest.mark.parametrize("scenario", sorted(LANE_SCENARIOS))
def test_lane_account_on_a_real_engine(scenario):
    """`lane_steps + vacant + blocked + spent == n_slots x steps` for every
    dispatch (`_drive` holds each to it), and each scenario reads the cause
    it was built for; `engine.metrics()` sums what the dispatches carried."""
    cfg, params = _cfg_params()
    options, load = LANE_SCENARIOS[scenario]
    eng = ContinuousBatchingEngine(params, cfg, chunk=4, macro_phases=4, max_len=64,
                                   block_size=8, prefix_cache=False, **options)
    eng.shutdown()  # the plans below are made on this thread
    rng = np.random.default_rng(2)
    _, seen = _drive(eng, [(rng.integers(0, cfg.vocab_size, n).tolist(), new) for n, new in load])
    total = {k: sum(c[k] for _, c in seen) for k in (
        "steps", "lane_steps", "vacant_lane_steps", "blocked_lane_steps", "spent_lane_steps")}
    m = eng.metrics()
    assert m["slot_steps"] == eng.n_slots * total["steps"]
    assert m["useful_slot_steps"] == total["lane_steps"] == sum(new - 1 for _, new in load)
    for key in ("vacant_lane_steps", "blocked_lane_steps", "spent_lane_steps"):
        assert m[key] == total[key]
    vacant, blocked, spent = (total[k] for k in (
        "vacant_lane_steps", "blocked_lane_steps", "spent_lane_steps"))
    if scenario == "fewer_requests_than_lanes":
        # lanes 2 and 3 all along, lane 1 after its phase: and lane 1 in the
        # phase that admits the one-token request is neither live nor empty
        assert vacant > 0 == blocked and spent == 4
        assert vacant == 2 * 11 + (11 - 4)
    elif scenario == "more_requests_than_lanes":
        # every phase full: no lane-step carries nothing
        assert vacant == 0 == blocked and spent == 0
        assert total["lane_steps"] == eng.n_slots * total["steps"]
    else:
        # the second lane is empty while a request waits for blocks, through
        # the first two requests; through the third nobody waits any more
        assert blocked == 2 * 11 and vacant == 11 and spent == 0


def test_lane_account_holds_in_a_speculative_plan(monkeypatch):
    """Verify rounds for steps, estimates for counts, lanes never evicted at
    plan time: every dispatch of the live loop still accounts for each of its
    lane-steps, and the counters add up to `slot_steps`."""
    cfg, params = _cfg_params()
    seen = []
    counted = llm_engine._dispatch_counts
    monkeypatch.setattr(llm_engine, "_dispatch_counts",
                        lambda *a, **kw: seen.append(counted(*a, **kw)) or seen[-1])
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, chunk=4, macro_phases=4, max_len=64,
                                   block_size=8, draft_model="self", num_speculative_tokens=N_SPEC)
    rng = np.random.default_rng(4)
    try:
        reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(), new)
                for n, new in ((9, 6), (12, 1), (7, 9), (10, 4), (8, 3))]
        assert all(r.done.wait(120) for r in reqs) and all(r.error is None for r in reqs)
    finally:
        eng.shutdown()
    assert seen and sum(c["admissions"] for c in seen) == len(reqs)
    for c in seen:
        assert (c["lane_steps"] + c["vacant_lane_steps"] + c["blocked_lane_steps"]
                + c["spent_lane_steps"] == eng.n_slots * c["steps"])
    m = eng.metrics()
    assert m["slot_steps"] == m["useful_slot_steps"] + sum(
        m[k] for k in ("vacant_lane_steps", "blocked_lane_steps", "spent_lane_steps"))
    assert m["lane_wait_us"] > 0 == m["blocked_lane_steps"]  # five requests on two lanes


def test_wait_account_per_request():
    """One lane, plans of two phases of four steps. `plan_wait + lane_wait`
    is the admitting plan's start minus the submit, to the microsecond, and
    no more than the lifeline's `admit` - `submit` (stamped inside the plan,
    on the wall clock: a millisecond of room for the two clocks)."""
    from ray_tpu.observability import lifeline

    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(params, cfg, n_slots=1, chunk=4, macro_phases=2,
                                   max_len=64, block_size=8, prefix_cache=False)
    eng.shutdown()  # planned on this thread, at this test's pace
    rng = np.random.default_rng(3)
    prompt = lambda n: rng.integers(0, cfg.vocab_size, n).tolist()  # noqa: E731
    plans = []  # (start, end on the engine's clock, counts) of each plan made

    def plan_and_dispatch():
        phases = eng._plan()
        end = time.perf_counter()
        counts = llm_engine._dispatch_counts(phases, n_slots=eng.n_slots)
        plans.append((eng._t_plan, end, counts))
        eng._dispatch_macro(phases, counts)

    first = eng.submit(prompt(9), 10, rid="wait-first")    # the queue empty, the lane free
    queued = eng.submit(prompt(9), 3, rid="wait-queued")   # behind it: no lane in the first plan
    time.sleep(0.003)
    eng._drain_queue()
    plan_and_dispatch()                                    # admits `first`; sees `queued`
    late = eng.submit(prompt(9), 2, rid="wait-late")       # arrives inside the dispatch
    time.sleep(0.003)
    eng._drain_queue()
    while eng._waiting or any(r is not None for r in eng._slots):
        plan_and_dispatch()
    while eng._pending:
        eng._resolve(eng._pending.popleft())
    reqs = (first, queued, late)
    assert all(r.done.is_set() and r.error is None for r in reqs)

    starts = [start for start, _, _ in plans]
    # `first` decodes 9 steps, 8 a plan: the lane frees inside the second plan
    assert first._t_seen == first._t_admit == starts[0]
    assert queued._t_seen == starts[0] and queued._t_admit == starts[1]
    assert late._t_seen == starts[1] and late._t_admit in starts[1:]
    for r in reqs:
        plan_wait, lane_wait = llm_engine._wait_us(r)
        assert plan_wait >= 0 and lane_wait >= 0
        assert plan_wait + lane_wait == round((r._t_admit - r._t_submit) * 1e6)
        ev = {e["kind"]: e["t"] for e in lifeline.events(r.rid)}
        plan_s = next(end - start for start, end, _ in plans if start == r._t_admit)
        waited = (plan_wait + lane_wait) * 1e-6
        assert waited - 1e-3 <= ev["admit"] - ev["submit"] <= waited + plan_s + 1e-3
    assert llm_engine._wait_us(first)[0] >= 3000 and llm_engine._wait_us(first)[1] == 0
    assert llm_engine._wait_us(queued)[1] == round((starts[1] - queued._t_submit) * 1e6) \
        - llm_engine._wait_us(queued)[0] > 0
    # the dispatches carry the sums, `engine.metrics()` their total
    m = eng.metrics()
    for i, key in enumerate(("plan_wait_us", "lane_wait_us")):
        assert sum(c[key] for _, _, c in plans) == m[key] == sum(
            llm_engine._wait_us(r)[i] for r in reqs)
    assert plans[0][2]["admitted_first_plan"] == 1 == plans[0][2]["admissions"]
    assert m["admitted_first_plan"] == sum(r._t_seen == r._t_admit for r in reqs) >= 1
    assert sum(c["admissions"] for _, _, c in plans) == 3


# ---------------------------------------------- spans in a real trace (c)
def _engine_events(trace_dir):
    """{line: [(name, start_ns, end_ns, stats)]} of the host lines that hold
    an `engine.*` event."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                      for e in line.events if e.name.startswith("engine.")]
            if events:
                lines[(plane.name, i)] = sorted(events, key=lambda e: e[1])
    return lines


def test_macro_loop_spans_in_a_profiler_trace(tmp_path):
    cfg, params = _cfg_params()
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, chunk=4,
                                   macro_phases=4, max_len=64, block_size=8)
    eng._quantum = lambda: 2  # held: what this CPU's timings would make of it is not the test's
    rng = np.random.default_rng(0)
    prompt = lambda n: rng.integers(0, cfg.vocab_size, n).tolist()  # noqa: E731
    try:
        warm = prompt(9)
        eng.generate(warm, 5)  # the loop is up and a program compiled
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            m0 = eng.metrics()
            lengths, answers = (9, 17, 12, 9, 30), (3, 20, 7, 1, 11)
            reqs = [eng.submit(prompt(n), new) for n, new in zip(lengths, answers)]
            # one radix-cache hit: the warm-up prompt's first block, reused
            reqs.append(eng.submit(warm[:8] + prompt(6), 4))
            assert all(r.done.wait(120) for r in reqs)
            # a seventh alone on the two lanes: one stands vacant, so its plans are short
            reqs.append(eng.submit(prompt(9), 6))
            assert reqs[-1].done.wait(120)
            assert all(r.error is None for r in reqs)
            m1 = eng.metrics()
            time.sleep(0.15)  # the last dispatch resolves, then a few idle iterations
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()

    lines = _engine_events(tmp_path)
    assert len(lines) == 1, "engine spans on more than the engine's loop thread"
    (events,) = lines.values()
    # the loop's spans, and one `engine.request` a finished request (tests/test_request_account.py)
    assert {e[0] for e in events} == set(ENGINE_SPANS) | {REQUEST_SPAN}
    assert sum(e[0] == REQUEST_SPAN for e in events) == len(reqs)

    top = [e for e in events if e[0] not in ("engine.fetch", REQUEST_SPAN)]
    for (_, _, end, _), (name, start, _, _) in zip(top, top[1:]):
        assert start >= end, f"{name} begins inside the span before it"
    for name, start, end, _ in events:
        if name == "engine.fetch":  # lies inside one engine.resolve
            assert any(n == "engine.resolve" and s <= start and end <= e for n, s, e, _ in top)

    dispatches = [stats for name, _, _, stats in top if name == "engine.dispatch"]
    account = ("admit_phases", "admit_pieces", "vacant_lane_steps", "blocked_lane_steps",
               "spent_lane_steps", "plan_wait_us", "lane_wait_us", "admitted_first_plan",
               "admit_lead_steps", "admit_lead_phases", "stall_lane_phases")  # ISSUE 41, 46
    keys = {"seq", "phases", "steps", "admissions", "A", "P", "prompt_tokens", "prefix_tokens",
            "lane_steps", "finishing", "finish_wait_steps", "ctx_chunks", "ctx_tokens",
            "prompt_pairs", "admit_rows", "short", "q", *account}
    assert all(set(d) == keys for d in dispatches)
    # a plan a vacant lane closed (ISSUE 47): it says so, with the quantum its
    # one last phase decoded by, and `short_plans` counts them (the seventh
    # request's at the least)
    assert sum(d["short"] for d in dispatches) == m1["short_plans"] - m0["short_plans"] > 0
    assert all((d["short"], d["q"] > 0) in ((0, False), (1, True)) for d in dispatches)
    assert all(d["steps"] <= d["q"] or d["phases"] > 1 for d in dispatches if d["short"])
    diff = {k: m1[k] - m0[k] for k in ("dispatches", "slot_steps", "useful_slot_steps",
                                       "prefill_tokens", "reused_prefix_tokens",
                                       "requests_completed", "ctx_chunks", "span_chunks",
                                       "ctx_tokens", "prompt_pairs", "admit_rows", *account)}
    assert len(dispatches) == diff["dispatches"] >= 2
    assert [d["seq"] for d in dispatches] == list(
        range(m0["dispatches"], m0["dispatches"] + len(dispatches)))
    assert sum(d["steps"] for d in dispatches) * eng.n_slots == diff["slot_steps"]
    assert sum(d["lane_steps"] for d in dispatches) == diff["useful_slot_steps"]
    assert sum(d["admissions"] for d in dispatches) == len(reqs)
    assert sum(d["prompt_tokens"] for d in dispatches) == diff["prefill_tokens"]
    assert sum(d["prefix_tokens"] for d in dispatches) == diff["reused_prefix_tokens"] == 8
    # a span of 64 positions is one chunk: every step reads it, no more
    assert (sum(d["ctx_chunks"] for d in dispatches) == diff["ctx_chunks"]
            == diff["span_chunks"] == sum(d["steps"] for d in dispatches))
    assert sum(d["finishing"] for d in dispatches) == diff["requests_completed"] == len(reqs)
    # what the attention of each half has to do, for every model (PR 39)
    assert sum(d["ctx_tokens"] for d in dispatches) == diff["ctx_tokens"] > diff["useful_slot_steps"]
    assert sum(d["prompt_pairs"] for d in dispatches) == diff["prompt_pairs"] > diff["prefill_tokens"]
    # A is the lanes' bucket in every dispatch; P names the program
    assert all(d["P"] in (16, 32) and d["A"] == 2 for d in dispatches)
    # the admissions' rows, padding included, as the device runs them: whole
    # rows of P, one or two a phase that admits (the pieces of its count),
    # none where the plan admits nobody (PR 40, ISSUE 42, ISSUE 46)
    assert sum(d["admit_rows"] for d in dispatches) == diff["admit_rows"]
    for d in dispatches:
        n, rest = divmod(d["admit_rows"], d["P"])
        assert rest == 0 and d["admissions"] <= n <= d["A"] * d["admit_phases"]
        assert d["admit_phases"] <= d["admit_pieces"] <= n and (n > 0) == (d["admissions"] > 0)
        assert d["prompt_tokens"] <= d["admit_rows"] <= d["A"] * d["P"] * d["admit_phases"]
    # six requests on two lanes: some phase admitted one prompt alone, and ran one row
    assert diff["admit_rows"] < sum(d["A"] * d["P"] * d["admit_phases"] for d in dispatches)
    # the lane, wait and lead accounts (ISSUE 41): the spans' sums are the
    # counters', every dispatch's lane-steps are all accounted for, and the
    # six requests were each seen by a plan, then admitted by one
    for key in account:
        assert sum(d[key] for d in dispatches) == diff[key]
    for d in dispatches:
        assert (d["lane_steps"] + d["vacant_lane_steps"] + d["blocked_lane_steps"]
                + d["spent_lane_steps"] == eng.n_slots * d["steps"])
        assert d["admit_phases"] <= d["phases"] and d["admitted_first_plan"] <= d["admissions"]
        assert d["admit_lead_phases"] <= d["admissions"] * d["admit_phases"]
    assert diff["spent_lane_steps"] > 0 == diff["blocked_lane_steps"]  # the one-token request
    assert diff["plan_wait_us"] > 0 and diff["lane_wait_us"] > 0  # six requests on two lanes
    assert diff["stall_lane_phases"] > 0 and diff["admit_lead_steps"] > 0
    # each resolve repeats its dispatch's plan counts: an execution whose
    # dispatch lies before a trace's start is still counted (PR 39)
    resolved = [stats for name, _, _, stats in top if name == "engine.resolve"]
    plan = keys - {"A", "P"}
    assert [{k: r[k] for k in plan} for r in resolved] == [{k: d[k] for k in plan} for d in dispatches]


def test_a_recurrent_expert_models_spans_sum_to_its_counters(tmp_path):
    """The Qwen3-Next decoder as a case (ISSUE 43): a model whose lanes hold
    recurrent state AND whose macro-step counts experts on the device. The
    plan's `state_lanes` on each `engine.dispatch` and the device's counts of
    HELD experts on each `engine.resolve` sum to `engine.metrics()`' own, and
    to what the requests' lengths say they must be."""
    from benchmark import weights_qwen3_next as W
    from ray_tpu.models import qwen3_next as M
    from ray_tpu.models import qwen3_next_decode as QD

    cfg = M.Qwen3NextConfig.tiny(dtype=jnp.float32)
    params = W.init_params(W.seed_key(43), cfg)
    eng = ContinuousBatchingEngine(params, cfg, n_slots=3, chunk=4, macro_phases=4, max_len=128,
                                   block_size=4, prefix_cache=False)
    rng = np.random.default_rng(1)
    try:
        lengths, answers = (9, 30, 21, 9, 30, 5), (6, 20, 11, 11, 6, 1)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]
        eng.generate(prompts[0], 2)  # the loop is up, a program compiled
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            m0 = eng.metrics()
            reqs = [eng.submit(p, n) for p, n in zip(prompts, answers)]
            assert all(r.done.wait(240) for r in reqs)
            assert all(r.error is None for r in reqs)
            m1 = eng.metrics()
            time.sleep(0.15)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    (events,) = _engine_events(tmp_path).values()
    dispatches = [st for name, _, _, st in events if name == "engine.dispatch"]
    resolves = [st for name, _, _, st in events if name == "engine.resolve"]
    moved = {k: m1[k] - m0[k] for k in QD.DEVICE_COUNTERS + (
        "state_lane_steps", "useful_slot_steps", "prefill_tokens", "dispatches")}
    lane_steps = moved["useful_slot_steps"]
    assert lane_steps == sum(n - 1 for n in answers) == moved["state_lane_steps"]
    assert len(dispatches) == moved["dispatches"]
    assert sum(int(d["state_lanes"]) for d in dispatches) == lane_steps
    assert sum(int(d["prompt_tokens"]) for d in dispatches) == moved["prefill_tokens"] == sum(lengths)
    assert m1["state_bytes"] == QD.state_bytes_per_lane(cfg) > 0
    # held experts only (4 of 16, top-4): fewer than top_k pairs a live row and layer
    assert 0 < moved["expert_rows"] < lane_steps * cfg.top_k * cfg.n_layers
    assert moved["expert_rows"] >= moved["experts_hit"] >= moved["expert_rows_max"] > 0
    for key in QD.DEVICE_COUNTERS:
        assert sum(int(st[key]) for st in resolves) == moved[key]
    # each resolve repeats its dispatch's plan counts, `state_lanes` among them
    assert [int(r["state_lanes"]) for r in resolves] == [int(d["state_lanes"]) for d in dispatches]
    assert sorted(int(st["seq"]) for st in resolves) == sorted(int(st["seq"]) for st in dispatches)


def test_a_shortcut_expert_models_scopes_and_choice_counters(tmp_path):
    """The LongCat-Flash decoder as a case (ISSUE 45): a model with a latent
    pool of two planes a layer whose macro-step counts, beside the held
    experts' three, a live row's chosen indices under and past the real
    experts. Its scopes (`mla_proj` / `mla_absorb` / `mla_ctx` over both
    attentions, `ffn_dense`, `moe_route`, `moe_experts`, the new `moe_zero`)
    name operations of BOTH halves of the compiled macro-step; the five device
    counts on each `engine.resolve` sum to `engine.metrics()`' own, and the
    two of the choices to top_k a live row and layer."""
    from benchmark import weights_longcat_flash as W
    from ray_tpu.models import longcat_flash as M
    from ray_tpu.models import longcat_flash_decode as LD

    cfg = M.LongcatFlashConfig.tiny(dtype=jnp.float32)
    params = W.init_params(W.seed_key(45), cfg)
    eng = ContinuousBatchingEngine(params, cfg, n_slots=3, chunk=4, macro_phases=4, max_len=128,
                                   block_size=4, prefix_cache=False)
    rng = np.random.default_rng(1)
    try:
        lengths, answers = (9, 30, 21, 9, 30, 5), (6, 20, 11, 11, 6, 1)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]
        eng.generate(prompts[0], 2)  # the loop is up, a program compiled
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            m0 = eng.metrics()
            reqs = [eng.submit(p, n) for p, n in zip(prompts, answers)]
            assert all(r.done.wait(240) for r in reqs)
            assert all(r.error is None for r in reqs)
            m1 = eng.metrics()
            time.sleep(0.15)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    # the scopes, in the name stacks of the macro-step program lowered at the engine's shapes
    K, A, P, B, MB = 4, 4, 16, 3, 32
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
    from ray_tpu.serve._internal.sampling import MAX_STOP_TOKENS

    text = LD.jitted_macro_step_slots_paged(cfg, 4, sampled=False).lower(
        params, LD.init_paged_cache(cfg, B, B * MB + 1, 4), i32(B), i32(K), jnp.zeros((K,), bool),
        i32(K, A, P), i32(K, A), i32(K, A), i32(K, A), i32(K, A), jnp.zeros((K, A), jnp.uint32),
        i32(K, B, MB), f32(K, B), i32(K, B), f32(K, B), i32(K, B, MAX_STOP_TOKENS)
    ).compile().as_text()
    stacks = set(re.findall(r'op_name="([^"]*)"', text))
    for half in ("admit_prefill", "decode_chunk"):
        for scope in ("mla_proj", "mla_ctx", "ffn_dense", "moe_route", "moe_experts", "moe_zero"):
            assert any(f"/{half}/" in st and f"/{scope}/" in st for st in stacks), (half, scope)
    assert any("/decode_chunk/" in st and "/mla_proj/mla_absorb/" in st for st in stacks)
    assert not any("/admit_prefill/" in st and "/mla_absorb/" in st for st in stacks)  # it expands
    assert "moe_shared" not in text  # no shared expert
    (events,) = _engine_events(tmp_path).values()
    dispatches = [st for name, _, _, st in events if name == "engine.dispatch"]
    resolves = [st for name, _, _, st in events if name == "engine.resolve"]
    assert LD.DEVICE_COUNTERS == ("expert_rows", "experts_hit", "expert_rows_max",
                                  "real_choices", "zero_choices")
    moved = {k: m1[k] - m0[k] for k in LD.DEVICE_COUNTERS + (
        "useful_slot_steps", "prefill_tokens", "dispatches", "ctx_tokens")}
    lane_steps = moved["useful_slot_steps"]
    assert lane_steps == sum(n - 1 for n in answers) and len(dispatches) == moved["dispatches"]
    assert moved["real_choices"] + moved["zero_choices"] == lane_steps * cfg.top_k * cfg.n_layers
    assert moved["zero_choices"] > 0 and moved["real_choices"] > moved["expert_rows"] > 0
    for key in LD.DEVICE_COUNTERS:
        assert sum(int(st[key]) for st in resolves) == moved[key]
    assert sum(int(d["ctx_tokens"]) for d in dispatches) == moved["ctx_tokens"]
    assert m1["state_bytes"] == 0 and "state_lanes" not in dispatches[0]
    assert sorted(int(st["seq"]) for st in resolves) == sorted(int(st["seq"]) for st in dispatches)
