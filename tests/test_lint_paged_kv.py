"""Lint: the PAGED decode program must not smuggle the dense KV cache
back in. Walks the full macro_step_slots_paged jaxpr (including
scan/cond sub-jaxprs) and rejects any aval whose shape contains the
(n_slots, max_len) dim pair — the signature of a slots x max_len KV
stripe (the per-layer dense cache is (n_slots, max_len, kvh, hd); the
stacked one adds a leading n_layers). Dims are chosen so the legal
paged shapes can't collide: max_len=40 is NOT a multiple of
block_size=16, so the per-layer gather workspace is (n_slots, 48, ...),
never (n_slots, 40, ...).

Plus two companions: the zero-draft-FLOPs lint (speculation off must
compile a program bit-identical to a draft-free build — the spec macro
is a third static variant family, never a runtime branch) and the
engine-level allocator block-leak audit (the pure-allocator audit
lives in test_paged_kv.py): a real engine serving a mixed
admit/evict/prefix-hit/stop workload must return every non-cache block
reference by the time the requests finish.
"""
import numpy as np
import pytest

import jax
import jax.extend.core
import jax.numpy as jnp

N_SLOTS, MAX_LEN, BLOCK = 3, 40, 16  # 40 % 16 != 0 on purpose
MB = -(-MAX_LEN // BLOCK)  # 3 blocks -> gather span 48 != 40
N_BLOCKS = 10
K_PHASES, A_ROWS, P_WIDTH, NS = 2, 1, 16, 4
CHUNK = 4


def _cfg_params():
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise",
                                 remat=False)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _walk_avals(jaxpr):
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


def test_paged_macro_jaxpr_has_no_dense_cache_aval():
    from ray_tpu.models import llama_decode as D

    cfg, params = _cfg_params()
    cache = D.init_paged_cache(cfg, N_SLOTS, N_BLOCKS, BLOCK)
    args = (
        params, cache,
        jnp.zeros(N_SLOTS, jnp.int32),                       # feed
        jnp.zeros(K_PHASES, jnp.int32),                      # steps
        jnp.zeros(K_PHASES, bool),                           # has_admit
        jnp.zeros((K_PHASES, A_ROWS, P_WIDTH), jnp.int32),   # prompts
        jnp.zeros((K_PHASES, A_ROWS), jnp.int32),            # lengths
        jnp.zeros((K_PHASES, A_ROWS), jnp.int32),            # starts
        jnp.zeros((K_PHASES, A_ROWS), jnp.int32),            # slots
        jnp.zeros((K_PHASES, A_ROWS), jnp.int32),            # rems
        jnp.zeros((K_PHASES, A_ROWS), jnp.uint32),           # seeds
        jnp.zeros((K_PHASES, N_SLOTS, MB), jnp.int32),       # tables
        jnp.zeros((K_PHASES, N_SLOTS), jnp.float32),         # temps
        jnp.zeros((K_PHASES, N_SLOTS), jnp.int32),           # top_ks
        jnp.ones((K_PHASES, N_SLOTS), jnp.float32),          # top_ps
        jnp.full((K_PHASES, N_SLOTS, NS), -1, jnp.int32),    # stop_ids
    )
    jaxpr = jax.make_jaxpr(
        lambda *a: D.macro_step_slots_paged(*a, chunk=CHUNK, cfg=cfg)
    )(*args)
    bad = []
    for aval in _walk_avals(jaxpr.jaxpr):
        shape = tuple(getattr(aval, "shape", ()))
        for i in range(len(shape) - 1):
            if shape[i] == N_SLOTS and shape[i + 1] == MAX_LEN:
                bad.append(shape)
    assert not bad, (
        f"dense (n_slots={N_SLOTS}, max_len={MAX_LEN}) KV avals survived "
        f"behind the paged flag: {bad}"
    )
    # the paged pool itself IS in the program
    pool = (cfg.n_layers, N_BLOCKS, BLOCK, cfg.n_kv_heads, cfg.head_dim)
    assert any(tuple(getattr(a, "shape", ())) == pool
               for a in _walk_avals(jaxpr.jaxpr)), "paged pool aval missing"


def test_greedy_variant_has_no_sampling_pipeline():
    """The sampled flag is a STATIC program split: the all-greedy macro
    variant (what a default bare-list workload compiles) must contain
    no vocab sort and no rng traffic — greedy serving pays exactly the
    pre-sampling per-step cost. The sampled variant keeps both."""
    from ray_tpu.models import llama_decode as D

    cfg, params = _cfg_params()

    def prims(sampled):
        cache = D.init_paged_cache(cfg, N_SLOTS, N_BLOCKS, BLOCK)
        args = (
            params, cache, jnp.zeros(N_SLOTS, jnp.int32),
            jnp.zeros(K_PHASES, jnp.int32), jnp.zeros(K_PHASES, bool),
            jnp.zeros((K_PHASES, A_ROWS, P_WIDTH), jnp.int32),
            jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
            jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
            jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
            jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
            jnp.zeros((K_PHASES, A_ROWS), jnp.uint32),
            jnp.zeros((K_PHASES, N_SLOTS, MB), jnp.int32),
            jnp.zeros((K_PHASES, N_SLOTS), jnp.float32),
            jnp.zeros((K_PHASES, N_SLOTS), jnp.int32),
            jnp.ones((K_PHASES, N_SLOTS), jnp.float32),
            jnp.full((K_PHASES, N_SLOTS, NS), -1, jnp.int32),
        )
        jaxpr = jax.make_jaxpr(
            lambda *a: D.macro_step_slots_paged(
                *a, chunk=CHUNK, cfg=cfg, sampled=sampled)
        )(*args)
        names = set()

        def walk(jx):
            for eqn in jx.eqns:
                names.add(eqn.primitive.name)
                for p in eqn.params.values():
                    for sub in _sub_jaxprs(p):
                        walk(sub)

        walk(jaxpr.jaxpr)
        return names

    greedy = prims(sampled=False)
    assert not any("sort" in n for n in greedy), sorted(greedy)
    assert not any("threefry" in n or "random" in n for n in greedy), \
        sorted(greedy)
    sampled = prims(sampled=True)
    assert any("sort" in n for n in sampled)


def test_non_speculative_program_has_zero_draft_flops():
    """Speculation OFF must be FREE: the spec macro program is a third
    static variant family, so a deployment that never sets draft_model
    traces a program containing zero draft parameters and zero draft
    FLOPs — bit-identical to a build that has never heard of drafts.
    Marker: a draft config with widths (d_model=96, d_ff=192) that no
    target-side shape can produce; the spec jaxpr must carry dim-96
    avals (proving the marker detects draft compute) and the non-spec
    jaxpr must not, before OR after the spec program is traced."""
    import dataclasses

    from ray_tpu.models import llama, llama_decode as D
    from ray_tpu.serve._internal.speculative import resolve_draft_model

    cfg, params = _cfg_params()
    N_SPEC = 2

    def paged_jaxpr():
        cache = D.init_paged_cache(cfg, N_SLOTS, N_BLOCKS, BLOCK)
        args = (
            params, cache, jnp.zeros(N_SLOTS, jnp.int32),
            jnp.zeros(K_PHASES, jnp.int32), jnp.zeros(K_PHASES, bool),
            jnp.zeros((K_PHASES, A_ROWS, P_WIDTH), jnp.int32),
            jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
            jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
            jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
            jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
            jnp.zeros((K_PHASES, A_ROWS), jnp.uint32),
            jnp.zeros((K_PHASES, N_SLOTS, MB), jnp.int32),
            jnp.zeros((K_PHASES, N_SLOTS), jnp.float32),
            jnp.zeros((K_PHASES, N_SLOTS), jnp.int32),
            jnp.ones((K_PHASES, N_SLOTS), jnp.float32),
            jnp.full((K_PHASES, N_SLOTS, NS), -1, jnp.int32),
        )
        return jax.make_jaxpr(
            lambda *a: D.macro_step_slots_paged(*a, chunk=CHUNK, cfg=cfg)
        )(*args)

    def dims(jaxpr):
        out = set()
        for aval in _walk_avals(jaxpr.jaxpr):
            out.update(tuple(getattr(aval, "shape", ())))
        return out

    before = paged_jaxpr()
    assert 96 not in dims(before) and 192 not in dims(before)
    before_str = str(before)

    # trace the speculative variant with the uniquely-dimensioned draft
    draft_cfg = dataclasses.replace(cfg, d_model=96, d_ff=192)
    draft_params, draft_cfg = resolve_draft_model(
        {"cfg": draft_cfg}, params, cfg)
    cache = D.init_paged_cache(cfg, N_SLOTS, N_BLOCKS, BLOCK)
    draft_cache = D.init_spec_cache(draft_cfg, N_SLOTS, N_BLOCKS, BLOCK)
    spec_args = (
        params, draft_params, cache, draft_cache,
        jnp.zeros(N_SLOTS, jnp.int32),
        jnp.zeros(K_PHASES, jnp.int32), jnp.zeros(K_PHASES, bool),
        jnp.zeros((K_PHASES, A_ROWS, P_WIDTH), jnp.int32),
        jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
        jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
        jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
        jnp.zeros((K_PHASES, A_ROWS), jnp.int32),
        jnp.zeros((K_PHASES, A_ROWS), jnp.uint32),
        jnp.zeros((K_PHASES, N_SLOTS, MB), jnp.int32),
        jnp.zeros((K_PHASES, N_SLOTS), jnp.float32),
        jnp.zeros((K_PHASES, N_SLOTS), jnp.int32),
        jnp.ones((K_PHASES, N_SLOTS), jnp.float32),
        jnp.full((K_PHASES, N_SLOTS, NS), -1, jnp.int32),
    )
    spec = jax.make_jaxpr(
        lambda *a: D.macro_step_slots_spec(
            *a, chunk=CHUNK, n_spec=N_SPEC, cfg=cfg, draft_cfg=draft_cfg)
    )(*spec_args)
    spec_dims = dims(spec)
    assert 96 in spec_dims and 192 in spec_dims, sorted(spec_dims)

    # re-tracing after the spec program exists changes NOTHING
    after = paged_jaxpr()
    assert 96 not in dims(after) and 192 not in dims(after)
    assert str(after) == before_str, "spec tracing perturbed the non-spec program"

    # engine level: a spec-off engine binds the SAME lru-cached greedy
    # program object as a plain build — not a spec variant with inert
    # knobs — and carries no draft state at all
    eng = None
    try:
        from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

        eng = ContinuousBatchingEngine(
            params, cfg, n_slots=N_SLOTS, chunk=CHUNK, macro_phases=2,
            max_len=MAX_LEN, block_size=BLOCK)
        assert eng._macro_paged_fn is D.jitted_macro_step_slots_paged(
            cfg, CHUNK, sampled=False)
        assert eng.draft_params is None and eng.draft_cache is None
    finally:
        if eng is not None:
            eng.shutdown()


def test_engine_block_leak_audit_mixed_workload():
    """Engine-level leak audit: mixed greedy / sampled / stop-token /
    prefix-hit traffic through a REAL paged engine; after all requests
    finish, the only live references belong to the radix cache, and
    clearing it zeroes the allocator."""
    from ray_tpu.models import llama, llama_decode as D
    from ray_tpu.serve._internal.sampling import SamplingParams
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise",
                                 remat=False)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, n_slots=3, chunk=4,
                                   macro_phases=4, max_len=64,
                                   block_size=8)
    try:
        rng = np.random.default_rng(0)
        shared = [int(t) for t in rng.integers(1, cfg.vocab_size, size=8)]
        w = D.generate(params, jnp.asarray([shared + [3]], jnp.int32), cfg,
                       max_new_tokens=8)[0].tolist()
        reqs = []
        for i in range(10):
            kind = i % 4
            if kind == 0:
                reqs.append(eng.submit(shared + [3 + i], 6))
            elif kind == 1:
                reqs.append(eng.submit(
                    [int(t) for t in rng.integers(1, cfg.vocab_size, size=5)],
                    8, sampling=SamplingParams(temperature=0.9, seed=i)))
            elif kind == 2:
                reqs.append(eng.submit(
                    shared + [3], 8, sampling=SamplingParams(stop=(w[1],))))
            else:
                reqs.append(eng.submit([1, 2], 3))
        for r in reqs:
            assert r.done.wait(300), "mixed workload stalled"
            assert r.error is None, r.error
        # every non-cache reference returned
        leaked = eng._alloc.leaked()
        assert all(r == 1 for r in leaked.values()), leaked
        assert len(leaked) == eng._prefix.nodes, (leaked, eng._prefix.nodes)
    finally:
        eng.shutdown()
    eng._prefix.clear()
    assert eng._alloc.check_zero(), eng._alloc.leaked()


def _engine_mode_findings(source: str):
    """What the one-engine lint holds serve/llm_engine.py to: no dense slot
    mode (`self.paged`, `init_slot_cache`), no per-chunk loop, no call of a
    dense slot program, and one loop over `self._running`."""
    found = [word for word in ("self.paged", "_loop_chunked", "init_slot_cache",
                               "_chunk_fn(", "_prefill_slots(", "_macro_fn(")
             if word in source]
    loops = source.count("while self._running")
    return found + ([f"{loops} loops over self._running"] if loops != 1 else [])


def test_engine_source_has_one_mode_and_one_loop():
    import inspect

    from ray_tpu.serve import llm_engine

    assert _engine_mode_findings(inspect.getsource(llm_engine)) == []
    # the lint flags what it is there to keep out
    two_modes = ("while self._running:\n  if self.paged: x = self._macro_fn(y)\n"
                 "while self._running: pass")
    assert _engine_mode_findings(two_modes) == [
        "self.paged", "_macro_fn(", "2 loops over self._running"]


# ------------------------------------------- which way the imports point
def _imports_of(source: str):
    """[(module imported, the function the import stands in or None)] of a
    source file: `from a.b import c` counts as `a.b.c` (it may be a module),
    an import under `if TYPE_CHECKING:` as none."""
    import ast

    found = []

    def walk(node, inside):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.If) and "TYPE_CHECKING" in ast.unparse(child.test):
                continue
            if isinstance(child, ast.Import):
                found.extend((alias.name, inside) for alias in child.names)
            elif isinstance(child, ast.ImportFrom):
                found.extend((f"{child.module}.{alias.name}", inside) for alias in child.names)
            walk(child, child.name if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef))
                 else inside)

    walk(ast.parse(source), None)
    return found


def _import_direction_findings(name: str, source: str):
    """What the model layer's one-way rule holds a file to, `name` its path
    under ray_tpu/: models/paged.py imports no module of ray_tpu.models; a
    model definition (a module of models/ that is neither paged nor a
    `*_decode`) imports no decode module but in the `decode_module` property
    by which its config names one; nothing under serve/ imports a decode
    module (the engine reaches it through `cfg.decode_module`)."""
    import re

    imports = _imports_of(source)
    decode = re.compile(r"ray_tpu\.models\.(\w+_decode)\b")
    if name == "models/paged.py":
        return [m for m, _ in imports if m.startswith("ray_tpu.models")]
    if name.startswith("serve/"):
        return [m for m, _ in imports if decode.match(m)]
    if name.startswith("models/") and not name.endswith("_decode.py"):
        return [m for m, inside in imports if decode.match(m) and inside != "decode_module"]
    return []


def _sources_under(package: str):
    import pathlib

    import ray_tpu

    root = pathlib.Path(ray_tpu.__file__).parent
    return {str(p.relative_to(root)): p.read_text() for p in sorted((root / package).rglob("*.py"))}


MODEL_FILES = sorted(_sources_under("models"))


def test_the_model_layer_is_what_the_lint_walks():
    """Eight decode modules, the skeleton they share, and a case below for
    every file there is."""
    assert "models/paged.py" in MODEL_FILES
    assert len([f for f in MODEL_FILES if f.endswith("_decode.py")]) == 8


@pytest.mark.parametrize("name", MODEL_FILES + ["serve/"])
def test_imports_point_one_way(name):
    files = _sources_under("serve") if name == "serve/" else {name: _sources_under("models")[name]}
    assert {f: found for f, src in files.items()
            if (found := _import_direction_findings(f, src))} == {}


def test_the_import_lint_flags_what_it_keeps_out():
    wrong = ("from ray_tpu.models import llama_decode as D\n"
             "from ray_tpu.models.llama_decode import rows_a_piece\n"
             "if TYPE_CHECKING:\n    from ray_tpu.models.llama import LlamaConfig\n"
             "class C:\n    @property\n    def decode_module(self):\n"
             "        from ray_tpu.models import afmoe_decode\n        return afmoe_decode\n")
    assert _import_direction_findings("models/paged.py", wrong) == [
        "ray_tpu.models.llama_decode", "ray_tpu.models.llama_decode.rows_a_piece",
        "ray_tpu.models.afmoe_decode"]
    assert _import_direction_findings("models/afmoe.py", wrong) == [
        "ray_tpu.models.llama_decode", "ray_tpu.models.llama_decode.rows_a_piece"]
    assert _import_direction_findings("serve/_internal/kv_plane.py", wrong) == [
        "ray_tpu.models.llama_decode", "ray_tpu.models.llama_decode.rows_a_piece",
        "ray_tpu.models.afmoe_decode"]
    assert _import_direction_findings("models/afmoe_decode.py", wrong) == []
