"""Paged-KV serving subsystem: block-table decode parity, radix prefix
reuse, real sampling, and plan-and-repair stop handling
(serve/_internal/ + models/paged.py and llama_decode's paged halves)."""
import numpy as np
import pytest


def _tiny():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, attn_impl="blockwise",
                                 remat=False)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _paged_engine(params, cfg, **kw):
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    kw.setdefault("n_slots", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("macro_phases", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 8)
    return ContinuousBatchingEngine(params, cfg, **kw)


# --------------------------------------------------------------- allocator
def test_block_allocator_refcounts_and_cow():
    from ray_tpu.serve._internal.kv_blocks import (
        NULL_BLOCK, BlockAllocator, BlockPoolExhausted)

    a = BlockAllocator(8, 4)  # 7 usable, block 0 null
    t1 = a.alloc(3)
    assert NULL_BLOCK not in t1 and len(set(t1)) == 3
    assert a.used_blocks == 3
    # fork shares every block; COW barrier makes one private again
    t2 = a.fork(t1)
    assert all(a.refcount(b) == 2 for b in t1)
    pair = a.ensure_writable(t2, 1)
    assert pair is not None
    src, dst = pair
    assert src == t1[1] and t2[1] == dst and a.refcount(src) == 1
    # already-exclusive block: no copy
    assert a.ensure_writable(t2, 1) is None
    with pytest.raises(BlockPoolExhausted):
        a.alloc(100)
    a.decref(t1)
    a.decref(t2)
    assert a.check_zero(), a.leaked()


def test_copy_kv_blocks_device_cow():
    """The device half of COW: after fork + ensure_writable, copying the
    (src, dst) pair makes the forked table's contents identical."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as D
    from ray_tpu.models import paged
    from ray_tpu.serve._internal.kv_blocks import BlockAllocator

    params, cfg = _tiny()
    cache = D.init_paged_cache(cfg, 2, 8, 4)
    cache["k"] = cache["k"].at[:, 3].set(1.5)
    a = BlockAllocator(8, 4)
    table = a.alloc(2)
    cache["k"] = cache["k"].at[:, table[1]].set(2.5)
    forked = a.fork(table)
    src, dst = a.ensure_writable(forked, 1)
    cache = paged.copy_kv_blocks(cache, np.asarray([src]), np.asarray([dst]))
    np.testing.assert_array_equal(
        np.asarray(cache["k"][:, dst]), np.asarray(cache["k"][:, src])
    )
    a.decref(table)
    a.decref(forked)
    assert a.check_zero()


# ------------------------------------------------------------ radix cache
def test_radix_prefix_cache_lookup_insert_evict():
    from ray_tpu.serve._internal.kv_blocks import BlockAllocator
    from ray_tpu.serve._internal.prefix_cache import RadixPrefixCache

    a = BlockAllocator(16, 4)
    c = RadixPrefixCache(a)
    prompt = list(range(100, 112))  # 3 full blocks
    table = a.alloc(3)
    assert c.insert(prompt, table) == 3
    # full-prompt lookup is capped at a PROPER prefix (needs 1 suffix token)
    blocks, matched = c.lookup(prompt)
    assert matched == 8 and blocks == table[:2]
    a.decref(blocks)
    # longer prompt sharing 2 blocks
    blocks, matched = c.lookup(prompt[:8] + [7, 7, 7, 7, 7])
    assert matched == 8 and blocks == table[:2]
    a.decref(blocks)
    # miss
    blocks, matched = c.lookup([9, 9, 9, 9, 9, 9, 9, 9, 9])
    assert blocks == [] and matched == 0
    # while the owner holds refs nothing is evictable
    assert c.evict(10) == 0
    a.decref(table)  # owner done: cache is sole owner
    assert c.evict(1) == 1  # LRU leaf (deepest block) goes first
    assert c.evict(10) == 2
    assert a.check_zero(), a.leaked()
    st = c.stats()
    assert st["prefix_cache_evictions"] == 3 and st["prefix_cache_hits"] == 2


def test_block_leak_audit_mixed_workload():
    """CI audit: a mixed admit/evict/prefix-hit/fork workload returns
    every reference — allocator refcounts sum to zero at the end."""
    from ray_tpu.serve._internal.kv_blocks import (
        BlockAllocator, BlockPoolExhausted)
    from ray_tpu.serve._internal.prefix_cache import RadixPrefixCache

    rng = np.random.default_rng(0)
    a = BlockAllocator(64, 4)
    c = RadixPrefixCache(a)
    live = []
    for step in range(200):
        if live and (rng.random() < 0.4 or len(live) > 8):
            blocks, _ = live.pop(rng.integers(len(live)))
            a.decref(blocks)
            continue
        plen = int(rng.integers(1, 24))
        prompt = [int(t) for t in rng.integers(0, 4, size=plen)]  # collisions likely
        shared, matched = c.lookup(prompt)
        need = a.blocks_for_tokens(plen + 8) - len(shared)
        try:
            private = a.alloc(need)
        except BlockPoolExhausted:
            c.evict(need)
            try:
                private = a.alloc(need)
            except BlockPoolExhausted:
                a.decref(shared)
                continue
        table = shared + private
        c.insert(prompt, table)
        if rng.random() < 0.2:  # COW fork + immediate release
            f = a.fork(table)
            try:
                if len(f) > 1:
                    a.ensure_writable(f, 0)
            except BlockPoolExhausted:
                pass  # alloc is all-or-nothing: f is untouched
            a.decref(f)
        live.append((table, prompt))
    for blocks, _ in live:
        a.decref(blocks)
    c.clear()
    assert a.check_zero(), a.leaked()


# ------------------------------------------------- device-level parity
def test_paged_decode_matches_dense_wrapped_tables():
    """Paged decode with NON-CONTIGUOUS block tables that wrap the pool
    out of order produces logits identical (1e-5) to the dense batch
    cache (what `generate` runs: one prompt a row at its true length),
    token for token."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as D

    params, cfg = _tiny()
    n_slots, bs, MB = 2, 8, 4
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]
    A, P = 2, 8
    pr = np.zeros((A, P), np.int32)
    lengths = np.zeros(A, np.int32)
    for i, p in enumerate(prompts):
        pr[i, : len(p)] = p
        lengths[i] = len(p)
    slots = np.arange(A, dtype=np.int32)
    rems = np.full(A, 5, np.int32)

    prefill, decode_step = D._jitted_prefill(cfg), D._jitted_decode_step(cfg)  # `generate`'s own
    dense, feed_d = [], []
    for p in prompts:
        last, cache = prefill(params, jnp.asarray([p], jnp.int32), D.init_cache(cfg, 1, MB * bs))
        dense.append(cache)
        feed_d.append(jnp.argmax(last, axis=-1).astype(jnp.int32))

    paged = D.init_paged_cache(cfg, n_slots, 12, bs)
    # shuffled, interleaved, wrapping the pool: slot 0 high-to-low,
    # slot 1 interleaved between slot 0's blocks
    tables = np.asarray([[11, 3, 9, 1], [2, 10, 4, 8]], np.int32)
    feed_p = jnp.zeros(n_slots, jnp.int32)
    greedy = dict(
        temps=jnp.zeros(n_slots, jnp.float32),
        top_ks=jnp.zeros(n_slots, jnp.int32),
        top_ps=jnp.ones(n_slots, jnp.float32),
        stop_ids=jnp.full((n_slots, 4), -1, jnp.int32),
    )
    first_p, paged, feed_p = D.admit_slots_paged(
        params, jnp.asarray(pr), jnp.asarray(lengths),
        jnp.zeros(A, jnp.int32), jnp.asarray(slots), jnp.asarray(rems),
        jnp.zeros(A, jnp.uint32), paged, feed_p, jnp.asarray(tables),
        greedy["temps"], greedy["top_ks"], greedy["top_ps"],
        greedy["stop_ids"], cfg)
    np.testing.assert_array_equal(np.concatenate(feed_d), np.asarray(first_p))

    for _ in range(4):
        rows = [decode_step(params, c, f) for c, f in zip(dense, feed_d)]
        logits_d = np.concatenate([np.asarray(lg) for lg, _ in rows])
        dense = [c for _, c in rows]
        feed_d = [jnp.argmax(lg, axis=-1).astype(jnp.int32) for lg, _ in rows]
        logits_p, feed_p, paged = D.decode_step_slots_paged(
            params, paged, feed_p, jnp.asarray(tables), greedy["temps"],
            greedy["top_ks"], greedy["top_ps"], greedy["stop_ids"], cfg)
        np.testing.assert_allclose(
            logits_d, np.asarray(logits_p), rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.concatenate(feed_d), np.asarray(feed_p))


# ------------------------------- admission attention: parity and lowering
def _one_shot_attention(q, k, v, k_layer, v_layer, adm_tables, starts, cfg):
    """The formulation admission had until PR 28, kept as the reference:
    gather every row's WHOLE table span from the pool (the suffix K/V are
    read back from it, so `k`, `v` go unused), build one f32
    (A, kvh, g, P, span) score, mask it by s <= starts[n] + t, soft-max it
    in one shot."""
    import jax
    import jax.numpy as jnp

    A, P, h, hd = q.shape
    kvh = cfg.n_kv_heads
    S = adm_tables.shape[1] * k_layer.shape[1]
    k_ctx = k_layer[adm_tables].reshape(A, S, kvh, hd)
    v_ctx = v_layer[adm_tables].reshape(A, S, kvh, hd)
    positions = starts[:, None] + jnp.arange(P, dtype=jnp.int32)[None, :]
    qg = q.reshape(A, P, kvh, h // kvh, hd)
    scores = jnp.einsum("apkgd,askd->akgps", qg, k_ctx,
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
    mask = jnp.arange(S)[None, None, :] <= positions[:, :, None]
    scores = jnp.where(mask[:, None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("akgps,askd->apkgd", probs.astype(v_ctx.dtype), v_ctx,
                     preferred_element_type=jnp.float32)
    return out.reshape(A, P, h * hd).astype(cfg.dtype)


_ADM_BS, _ADM_MB = 8, 128  # a table span of 1024: two prefix chunks of 512

# name -> (P, starts, lengths); every case has A = 4 rows
ADMISSION_CASES = {
    # (a) no prefix, mixed lengths with right-padding
    "no-prefix-ragged": (32, [0, 0, 0, 0], [32, 5, 17, 1]),
    # (b) block-aligned prefixes of different lengths, one row without
    "prefixes-differ": (32, [24, 0, 8, 64], [9, 32, 20, 3]),
    # (c) invalid rows (length 0) beside valid ones
    "invalid-row": (32, [8, 0, 0, 0], [7, 0, 12, 0]),
    # (d) row 1's prefix is the two blocks row 0 is filling in this phase
    "shared-blocks-same-phase": (32, [0, 16, 0, 16], [24, 5, 9, 2]),
    # (e) contexts that end on the chunk edge (504 + 8) and one block past
    # it (512 + 8); prefixes one block short of, on and past the edge
    "chunk-edge": (16, [504, 512, 520, 0], [8, 8, 16, 16]),
}


def _admission_case(name, cfg):
    """Random pool (as if earlier admissions filled it), per-row tables of
    distinct shuffled blocks; case (d) makes rows 1 and 3 name the first
    two blocks of rows 0 and 2."""
    P, starts, lengths = ADMISSION_CASES[name]
    rng = np.random.default_rng(sorted(ADMISSION_CASES).index(name))
    A, MB, bs = 4, _ADM_MB, _ADM_BS
    n_blocks = A * MB + 1
    tables = (rng.permutation(n_blocks - 1) + 1).reshape(A, MB).astype(np.int32)
    if name == "shared-blocks-same-phase":
        tables[1, :2] = tables[0, :2]
        tables[3, :2] = tables[2, :2]
    pool = lambda: rng.standard_normal(  # noqa: E731
        (cfg.n_layers, n_blocks, bs, cfg.n_kv_heads, cfg.head_dim)).astype(np.float32)
    return (P, np.asarray(starts, np.int32), np.asarray(lengths, np.int32), tables,
            pool(), pool(), rng)


@pytest.mark.parametrize("name", ADMISSION_CASES)
def test_admission_attention_matches_one_shot(name, monkeypatch):
    """The admission attention (own suffix by the flash forward, reused
    prefix in chunks from the pool, merged by log-sum-exp) against the
    one-shot span formulation: the attention's output on every real query,
    then a whole admission (pool, positions, first tokens)."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as D
    from ray_tpu.models import paged

    params, cfg = _tiny()
    P, starts, lengths, tables, pool_k, pool_v, rng = _admission_case(name, cfg)
    A, bs, S = 4, _ADM_BS, _ADM_MB * _ADM_BS
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    assert paged.PREFIX_CHUNK == 512 < S

    # --- the attention alone, on a pool that holds the rows' suffixes
    q = rng.standard_normal((A, P, h, hd)).astype(np.float32)
    k = rng.standard_normal((A, P, kvh, hd)).astype(np.float32)
    v = rng.standard_normal((A, P, kvh, hd)).astype(np.float32)
    k_layer, v_layer = pool_k[0].copy(), pool_v[0].copy()
    for n in np.flatnonzero(lengths):  # plan order == write order
        for t in range(P):
            blk, off = divmod(int(starts[n]) + t, bs)
            k_layer[tables[n, blk], off] = k[n, t]
            v_layer[tables[n, blk], off] = v[n, t]
    args = [jnp.asarray(x) for x in (q, k, v, k_layer, v_layer, tables, starts)]
    got = np.asarray(paged._attend_admission(*args, cfg))
    want = np.asarray(_one_shot_attention(*args, cfg))
    real = np.arange(P)[None, :] < lengths[:, None]  # (A, P) real queries
    assert real.any(axis=1).tolist() == (lengths > 0).tolist()
    np.testing.assert_allclose(got[real], want[real], rtol=1e-5, atol=1e-5)

    # --- a whole admission, new against the reference patched in
    prompts = rng.integers(1, cfg.vocab_size, (A, P)).astype(np.int32)

    def admit():
        cache = D.init_paged_cache(cfg, A, pool_k.shape[1], bs)
        cache = {**cache, "k": jnp.asarray(pool_k), "v": jnp.asarray(pool_v)}
        return D.admit_slots_paged(
            params, jnp.asarray(prompts), jnp.asarray(lengths), jnp.asarray(starts),
            jnp.arange(A, dtype=jnp.int32), jnp.full(A, 5, jnp.int32),
            jnp.zeros(A, jnp.uint32), cache, jnp.zeros(A, jnp.int32),
            jnp.asarray(tables), jnp.zeros(A, jnp.float32), jnp.zeros(A, jnp.int32),
            jnp.ones(A, jnp.float32), jnp.full((A, 4), -1, jnp.int32), cfg,
            sampled=False)

    first_new, cache_new, feed_new = admit()
    monkeypatch.setattr(paged, "_attend_admission", _one_shot_attention)
    first_ref, cache_ref, feed_ref = admit()
    valid = lengths > 0
    np.testing.assert_array_equal(np.asarray(first_new)[valid], np.asarray(first_ref)[valid])
    np.testing.assert_array_equal(np.asarray(feed_new), np.asarray(feed_ref))
    for key in ("pos", "remaining"):
        np.testing.assert_array_equal(np.asarray(cache_new[key]), np.asarray(cache_ref[key]))
    np.testing.assert_array_equal(np.asarray(cache_new["pos"])[valid], (starts + lengths)[valid])
    for key in ("k", "v"):  # block 0 is the null block: pad columns' dump
        np.testing.assert_allclose(np.asarray(cache_new[key])[:, 1:],
                                   np.asarray(cache_ref[key])[:, 1:], rtol=1e-5, atol=1e-5)


def _sub_jaxprs(p):
    import jax.extend  # not loaded by `import jax` alone

    if isinstance(p, jax.extend.core.ClosedJaxpr):
        yield p.jaxpr
    elif isinstance(p, jax.extend.core.Jaxpr):
        yield p
    elif isinstance(p, (list, tuple)):
        for item in p:
            yield from _sub_jaxprs(item)


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (pjit / scan / cond / while /
    custom_vjp bodies) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                yield from _walk_eqns(sub)


def _admission_span_tensors():
    """(arrays as large as the old score tensor, gathers of a whole span a
    row) in the jaxpr of admit_slots_paged at (A, P) = (4, 64), span 1024."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as D

    params, cfg = _tiny()
    A, P, bs, MB = 4, 64, 16, 64
    span = MB * bs
    cache = D.init_paged_cache(cfg, A, A * MB + 1, bs)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    jaxpr = jax.make_jaxpr(
        lambda *a: D.admit_slots_paged(*a, cfg, sampled=False))(
        params, i32(A, P), i32(A), i32(A), i32(A), i32(A), jnp.zeros(A, jnp.uint32),
        cache, i32(A), i32(A, MB), jnp.zeros(A, jnp.float32), i32(A),
        jnp.ones(A, jnp.float32), i32(A, 4))
    scores = A * cfg.n_heads * P * span
    span_ctx = A * span * cfg.n_kv_heads * cfg.head_dim
    big, gathers = [], []
    for eqn in _walk_eqns(jaxpr.jaxpr):
        for var in eqn.outvars:
            shape = tuple(getattr(var.aval, "shape", ()))
            if int(np.prod(shape)) >= scores:
                big.append((eqn.primitive.name, shape))
            if eqn.primitive.name == "gather" and int(np.prod(shape)) >= span_ctx:
                gathers.append(shape)
    return big, gathers


def test_admission_lowering_has_no_span_tensor(monkeypatch):
    """Lint: admission neither builds an array with as many elements as
    the (A, heads, P, span) scores nor gathers a whole table span a row
    out of the pool, so the tensor PR 28 removed cannot come back
    unnoticed. The one-shot reference must trip both detectors."""
    from ray_tpu.models import paged

    big, gathers = _admission_span_tensors()
    assert not big, f"admission materializes {big}"
    assert not gathers, f"admission gathers the table span: {gathers}"
    monkeypatch.setattr(paged, "_attend_admission", _one_shot_attention)
    big, gathers = _admission_span_tensors()
    assert big and gathers, "the lint failed to flag the one-shot formulation"


# ---------------------------- decode attention: parity and lowering (PR 30)
def _one_shot_decode_attention(q, k_full, v_full, li, tables, pos, active, scale):
    """The formulation the paged decode step had until PR 30, kept as the
    reference for both pool layouts: slice layer `li` off the pools, gather
    every lane's WHOLE table span from it, build one f32 (B, heads, span)
    score, mask it by s <= pos[b] and soft-max it in one shot. `active`
    goes unused: a dead lane attends its stale positions like a live one."""
    import jax
    import jax.numpy as jnp

    B, h, hd = q.shape
    k_layer = jax.lax.dynamic_index_in_dim(k_full, li, 0, keepdims=False)
    v_layer = jax.lax.dynamic_index_in_dim(v_full, li, 0, keepdims=False)
    S = tables.shape[1] * k_layer.shape[1]
    ctx_k = k_layer[tables].reshape(B, S, -1, hd)  # heads split, whichever the layout
    ctx_v = v_layer[tables].reshape(B, S, -1, hd)
    kvh = ctx_k.shape[2]
    qg = q.reshape(B, kvh, h // kvh, hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, ctx_k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(S)[None, None, None, :] <= pos[:, None, None, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(ctx_v.dtype), ctx_v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, h * hd).astype(q.dtype)


_DEC_BS, _DEC_MB = 16, 64  # a table span of 1024: eight chunks of 128

# name -> (pos, active) of B = 4 lanes; a lane's context is pos + 1 positions
DECODE_CASES = {
    # lanes of unequal length, one of them of length 1
    "unequal-and-length-1": ([0, 37, 300, 700], [1, 1, 1, 1]),
    # contexts that are exact multiples of the chunk, and one past it
    "chunk-multiples": ([127, 511, 128, 767], [1, 1, 1, 1]),
    # the span's last position: the loop runs every chunk
    "span-end": ([1023, 5, 1022, 511], [1, 1, 1, 1]),
    # dead lanes, one holding more than any live lane, beside live ones
    "inactive-beside-live": ([900, 40, 0, 260], [0, 1, 0, 1]),
    # nothing live: no iteration, every output discarded
    "no-active-lane": ([900, 40, 0, 260], [0, 0, 0, 0]),
}


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("layout", ["rows-kvh-hd", "rows-flat"])
@pytest.mark.parametrize("name", DECODE_CASES)
def test_decode_attention_matches_one_shot(name, layout, dtype):
    """The chunked decode attention against the one-shot formulation on
    every live lane, for Mistral's pool rows (kvh, hd) and the hybrid's flat
    rows of kvh * hd columns, out of a random pool through shuffled tables.
    float32 within 1e-5; bfloat16 within 2e-2 (the two round their
    probabilities to 8 bits of mantissa at different scales before PV)."""
    import jax.numpy as jnp

    from ray_tpu.models import paged

    pos, active = (np.asarray(x, np.int32) for x in DECODE_CASES[name])
    B, bs, MB, h, kvh, hd, li = 4, _DEC_BS, _DEC_MB, 4, 2, 16, 1
    C = paged.decode_chunk_positions(bs, MB)
    assert C == 128 and MB * bs == 8 * C
    rng = np.random.default_rng(sorted(DECODE_CASES).index(name))
    n_blocks = B * MB + 1
    tables = (rng.permutation(n_blocks - 1) + 1).reshape(B, MB).astype(np.int32)
    row = (kvh * hd,) if layout == "rows-flat" else (kvh, hd)
    jdt = jnp.float32 if dtype == "f32" else jnp.bfloat16
    k_full, v_full = (jnp.asarray(rng.standard_normal((3, n_blocks, bs) + row), jdt)
                      for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, h, hd)), jdt)
    args = (q, k_full, v_full, jnp.int32(li), jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(active.astype(bool)), 0.2)
    got = np.asarray(paged.attend_decode_paged(*args), np.float32)
    want = np.asarray(_one_shot_decode_attention(*args), np.float32)
    assert got.shape == want.shape == (B, h * hd) and np.isfinite(got).all()
    live = active.astype(bool)
    tol = 1e-5 if dtype == "f32" else 2e-2
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    if not live.any():
        assert not got.any()  # the loop ran no chunk


def _decode_step_jaxpr(model):
    """(jaxpr of the model's paged decode step, its pool-layer shape prefix,
    lanes, span) at 4 lanes, blocks of 16, a span of 1024."""
    import jax
    import jax.numpy as jnp

    B, bs, MB = 4, _DEC_BS, _DEC_MB
    n_blocks = B * MB + 1
    if model == "llama":
        from ray_tpu.models import llama_decode as D

        params, cfg = _tiny()
    else:
        from ray_tpu.models import granite_hybrid as G
        from ray_tpu.models import granite_hybrid_decode as D

        cfg = G.GraniteHybridConfig.tiny(dtype=jnp.float32)
        params = G.init_params(jax.random.PRNGKey(0), cfg)
    cache = D.init_paged_cache(cfg, B, n_blocks, bs)
    assert cache["k"].shape[0] != n_blocks  # a layer's shape is not the pool's
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    jaxpr = jax.make_jaxpr(
        lambda *a: D.decode_step_slots_paged(*a, cfg, sampled=False))(
        params, cache, i32(B), i32(B, MB), jnp.zeros(B, jnp.float32), i32(B),
        jnp.ones(B, jnp.float32), i32(B, 4))
    return jaxpr, cache["k"].shape[1:], B, MB * bs


def _decode_span_tensors(model):
    """(arrays of a whole pool layer; gathers of every lane's whole span and
    arrays with the span on an axis) that the model's paged decode step
    produces."""
    jaxpr, layer, B, span = _decode_step_jaxpr(model)
    span_ctx = B * span * int(np.prod(layer[2:]))
    layers, spans = [], []
    for eqn in _walk_eqns(jaxpr.jaxpr):
        for var in eqn.outvars:
            shape = tuple(getattr(var.aval, "shape", ()))
            name = eqn.primitive.name
            if shape == layer:
                layers.append((name, shape))
            if (name == "gather" and int(np.prod(shape)) >= span_ctx) or (
                    len(shape) > 2 and shape[0] == B and span in shape[1:]):
                spans.append((name, shape))
    return layers, spans


@pytest.mark.parametrize("model", ["llama", "granite-hybrid"])
def test_decode_lowering_has_no_layer_copy_and_no_span_gather(model, monkeypatch):
    """Lint: neither model's paged decode step produces an array of a whole
    pool layer (n_blocks, bs, ...) nor one of a lane's whole table span
    (B, MB * bs, ...): gathered context, scores or probabilities. The
    one-shot reference must trip both detectors."""
    from ray_tpu.models import paged

    layers, spans = _decode_span_tensors(model)
    assert not layers, f"the decode step copies a pool layer: {layers}"
    assert not spans, f"the decode step builds a table span: {spans}"
    monkeypatch.setattr(paged, "attend_decode_paged", _one_shot_decode_attention)
    layers, spans = _decode_span_tensors(model)
    assert layers and spans, "the lint failed to flag the one-shot formulation"
    assert any(name == "gather" for name, _ in spans)


# --------------- q / k / v projections: the head split stays out of the product (PR 32)
def _qkv_folded(a, layer, cfg):
    """The formulation every paged program had until PR 32, kept as the
    lint's reference: the head reshape applied straight to the product, which
    the TPU compiler folds into the matmul (tests/test_tpu_compile.py)."""
    lead = a.shape[:-1]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return ((a @ layer["wq"]).reshape(*lead, h, hd),
            (a @ layer["wk"]).reshape(*lead, kvh, hd),
            (a @ layer["wv"]).reshape(*lead, kvh, hd))


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("rows", [(4, 1), (4, 512)])
def test_qkv_helper_equals_plain_products(rows, dtype):
    """_qkv is `a @ w` bit for bit, split into heads afterwards, for a decode
    step's 4 x 1 rows and an admission's 4 x 512."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.models import llama_decode as D

    jdt = jnp.float32 if dtype == "f32" else jnp.bfloat16
    cfg = llama.LlamaConfig.tiny(dtype=jdt)
    layer = jax.tree.map(lambda x: x[1], llama.init_params(jax.random.PRNGKey(3), cfg)["layers"])
    a = jnp.asarray(np.random.default_rng(0).standard_normal(rows + (cfg.d_model,)), jdt)
    got = jax.jit(lambda a, layer: D._qkv(a, layer, cfg))(a, layer)
    for out, name, heads in zip(got, ("wq", "wk", "wv"),
                                (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads)):
        want = jax.jit(jnp.matmul)(a, layer[name])
        assert out.dtype == want.dtype == jdt
        assert out.shape == rows + (heads, cfg.head_dim)
        np.testing.assert_array_equal(
            np.asarray(out, np.float32).reshape(want.shape), np.asarray(want, np.float32))


def test_layer_index_traced_matches_static():
    """The rolled layer scans hand `li` to the pool's writers and to the
    decode attention as a run-time value: each gives, for every layer, what
    it gives for the same index as a Python constant."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import paged

    L, B, bs, MB, h, kvh, hd, P = 3, 2, 8, 4, 4, 2, 16, 16
    n_blocks = B * MB + 1
    rng = np.random.default_rng(5)
    arr = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    k_full, v_full = arr(L, n_blocks, bs, kvh, hd), arr(L, n_blocks, bs, kvh, hd)
    tables = jnp.asarray((rng.permutation(n_blocks - 1) + 1).reshape(B, MB), jnp.int32)
    pos, active = jnp.asarray([9, 20], jnp.int32), jnp.asarray([True, True])
    k1, v1, q = arr(B, 1, kvh, hd), arr(B, 1, kvh, hd), arr(B, h, hd)
    kP, vP = arr(B, P, kvh, hd), arr(B, P, kvh, hd)
    starts, valid = jnp.asarray([0, 8], jnp.int32), jnp.asarray([True, True])
    programs = {
        "write_decode_kv": lambda li: paged.write_decode_kv(
            k_full, v_full, li, k1, v1, tables, pos, active),
        "attend_decode_paged": lambda li: paged.attend_decode_paged(
            q, k_full, v_full, li, tables, pos, active, 0.25),
        "write_admission_kv": lambda li: paged.write_admission_kv(
            k_full, v_full, li, kP, vP, tables, starts, valid),
    }
    for name, f in programs.items():
        traced = jax.jit(f)
        for li in range(L):
            for got, want in zip(jax.tree.leaves(traced(jnp.int32(li))),
                                 jax.tree.leaves(f(li))):
                np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=name)


def _head_splits_of_products(program):
    """(reshapes that split the last axis of a bare dot_general's output into
    (heads, head_dim); optimization barriers over dot_generals) in the jaxpr
    of one of Llama's three paged programs, at the tiny widths."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as D

    params, cfg = _tiny()
    B, bs, MB, T = 4, 16, 8, 16
    cache = D.init_paged_cache(cfg, B, B * MB + 1, bs)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    sampling = (jnp.zeros(B, jnp.float32), i32(B), jnp.ones(B, jnp.float32), i32(B, 4))
    if program == "decode_step_slots_paged":
        jaxpr = jax.make_jaxpr(lambda *a: D.decode_step_slots_paged(*a, cfg, sampled=False))(
            params, cache, i32(B), i32(B, MB), *sampling)
    elif program == "admit_slots_paged":
        jaxpr = jax.make_jaxpr(lambda *a: D.admit_slots_paged(*a, cfg, sampled=False))(
            params, i32(B, T), i32(B), i32(B), i32(B), i32(B), jnp.zeros(B, jnp.uint32),
            cache, i32(B), i32(B, MB), *sampling)
    else:
        jaxpr = jax.make_jaxpr(lambda *a: D._forward_tokens_paged(*a, cfg))(
            params, cache["k"], cache["v"], i32(B, T), i32(B, MB), i32(B),
            jnp.ones(B, bool))
    heads = {(cfg.n_heads, cfg.head_dim), (cfg.n_kv_heads, cfg.head_dim)}
    eqns = list(_walk_eqns(jaxpr.jaxpr))
    made_by = {id(v): e.primitive.name for e in eqns for v in e.outvars}
    folded = [tuple(e.outvars[0].aval.shape) for e in eqns
              if e.primitive.name == "reshape"
              and tuple(e.outvars[0].aval.shape[-2:]) in heads
              and made_by.get(id(e.invars[0])) == "dot_general"]
    barriers = [e for e in eqns if e.primitive.name == "optimization_barrier"
                and all(made_by.get(id(v)) == "dot_general" for v in e.invars)]
    return folded, barriers


@pytest.mark.parametrize("program", ["decode_step_slots_paged", "admit_slots_paged",
                                     "_forward_tokens_paged"])
def test_paged_programs_split_heads_after_a_barrier(program, monkeypatch):
    """Lint: in each of Llama's paged programs no bare dot_general feeds a
    head reshape; the three projections pass one optimization barrier first
    (llama._qkv says what the compiler does otherwise). The
    formulation they had until PR 32 must trip the detector."""
    from ray_tpu.models import llama_decode as D

    folded, barriers = _head_splits_of_products(program)
    assert not folded, f"{program} reshapes a product into heads: {folded}"
    assert len(barriers) == 1 and len(barriers[0].invars) == 3
    monkeypatch.setattr(D, "_qkv", _qkv_folded)
    folded, barriers = _head_splits_of_products(program)
    assert len(folded) == 3 and not barriers, "the lint failed to flag the folded formulation"


def test_hybrid_macro_step_lowers_without_llamas_halves(monkeypatch):
    """The hybrid decoder brings its own two halves and its own projections:
    its lowered macro-step holds no optimization barrier and is the same
    text, byte for byte, with Llama's halves and their helper taken away
    (PR 32 changed those and nothing the hybrid lowers; compared once with
    the parent commit's text, PERF.md section 6)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import granite_hybrid as G
    from ray_tpu.models import granite_hybrid_decode as GD
    from ray_tpu.models import llama_decode as D
    from ray_tpu.models import paged
    from ray_tpu.serve._internal.sampling import MAX_STOP_TOKENS

    cfg = G.GraniteHybridConfig.tiny(dtype=jnp.float32)
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    B, bs, K, A, P, MB = 4, 16, 2, 1, 16, 8
    cache = GD.init_paged_cache(cfg, B, B * MB + 1, bs)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: jnp.zeros(shape, jnp.float32)  # noqa: E731

    def lowered():
        # a fresh jit each time: the memoized one would hand back its trace
        step = jax.jit(lambda *a: paged.macro_step_slots_paged(
            *a, chunk=2, cfg=cfg, sampled=False, admit=GD.admit_slots_paged,
            decode_step=GD.decode_step_slots_paged))
        return step.lower(
            params, cache, i32(B), i32(K), jnp.zeros(K, bool), i32(K, A, P), i32(K, A),
            i32(K, A), i32(K, A), i32(K, A), jnp.zeros((K, A), jnp.uint32), i32(K, B, MB),
            f32(K, B), i32(K, B), f32(K, B), i32(K, B, MAX_STOP_TOKENS)).as_text()

    text = lowered()
    assert "optimization_barrier" not in text

    def gone(*a, **kw):
        raise AssertionError("the hybrid's macro-step traced one of Llama's halves")

    for name in ("_qkv", "decode_step_slots_paged", "admit_slots_paged"):
        monkeypatch.setattr(D, name, gone)
    assert lowered() == text


# ------------------------------------------------- engine-level behavior
def test_paged_oversubscription_same_kv_budget():
    """THE paging win: 2x the dense config's concurrent sequences served
    to completion from the SAME KV budget. Dense budget = 2 slots x 64
    tokens = 16 blocks; paged runs 4 slots against that same 16-block
    pool (each request's full reservation is only 3 blocks)."""
    from ray_tpu.models import llama_decode as D

    import jax.numpy as jnp

    params, cfg = _tiny()
    eng = _paged_engine(params, cfg, n_slots=4, max_len=64, block_size=8,
                        n_blocks=17, prefix_cache=False)
    try:
        assert eng.n_blocks - 1 == 2 * (64 // 8)  # the dense 2-slot budget
        rng = np.random.default_rng(2)
        prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, size=8)]
                   for _ in range(8)]
        reqs = [eng.submit(p, 8) for p in prompts]
        for r in reqs:
            assert r.done.wait(300), "oversubscribed workload stalled"
            assert r.error is None, r.error
        for p, r in zip(prompts, reqs):
            want = D.generate(params, jnp.asarray([p], jnp.int32), cfg,
                              max_new_tokens=8)[0].tolist()
            assert r.tokens == want
        assert eng.metrics()["kv_blocks_total"] == 16
    finally:
        eng.shutdown()
    assert eng._alloc.check_zero(), eng._alloc.leaked()


def test_prefix_sharing_diverges_without_corruption():
    """Two requests sharing a long prefix: the second reuses the first's
    committed blocks (hit counters prove it), both decode exactly their
    solo-greedy tokens, and every non-cache refcount returns to zero."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as D

    params, cfg = _tiny()
    eng = _paged_engine(params, cfg)
    try:
        rng = np.random.default_rng(1)
        shared = [int(t) for t in rng.integers(1, cfg.vocab_size, size=16)]
        pa, pb = shared + [7, 8], shared + [9]
        ra = eng.generate(pa, 5)
        rb = eng.generate(pb, 5)
        for p, got in ((pa, ra), (pb, rb)):
            want = D.generate(params, jnp.asarray([p], jnp.int32), cfg,
                              max_new_tokens=5)[0].tolist()
            assert got == want, (p, got, want)
        m = eng.metrics()
        assert m["prefix_cache_hits"] >= 1
        assert m["reused_prefix_tokens"] >= 16
        assert m["prefix_cache_hit_rate"] > 0
    finally:
        eng.shutdown()
    # requests released their refs; cache refs drop with clear()
    eng._prefix.clear()
    assert eng._alloc.check_zero(), eng._alloc.leaked()


def test_prefix_sharing_concurrent_same_plan():
    """Same-prefix requests admitted CONCURRENTLY (same plan, possibly
    same phase): the second's lookup hits blocks the first's prefill is
    still filling inside the very same dispatch — write-then-gather
    layer ordering keeps both correct."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as D

    params, cfg = _tiny()
    eng = _paged_engine(params, cfg, n_slots=4)
    try:
        rng = np.random.default_rng(4)
        shared = [int(t) for t in rng.integers(1, cfg.vocab_size, size=16)]
        tails = [[7, 8], [9], [10, 11, 12], [13]]
        reqs = [eng.submit(shared + t, 5) for t in tails]
        for r in reqs:
            assert r.done.wait(180)
            assert r.error is None, r.error
        for t, r in zip(tails, reqs):
            want = D.generate(params, jnp.asarray([shared + t], jnp.int32),
                              cfg, max_new_tokens=5)[0].tolist()
            assert r.tokens == want, (t, r.tokens, want)
        assert eng.metrics()["prefix_cache_hits"] >= 1
    finally:
        eng.shutdown()
    eng._prefix.clear()
    assert eng._alloc.check_zero(), eng._alloc.leaked()


def test_seeded_sampling_determinism():
    """Same seed -> same tokens REGARDLESS of co-scheduling; different
    seed -> (overwhelmingly) different tokens; temperature=0 rows in the
    same plan stay exactly greedy."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as D
    from ray_tpu.serve._internal.sampling import SamplingParams

    params, cfg = _tiny()
    sp = SamplingParams(temperature=0.8, seed=123)
    eng = _paged_engine(params, cfg)
    try:
        solo = eng.generate([1, 2, 3], 8, sampling=sp)
    finally:
        eng.shutdown()
    # same request co-scheduled with noise traffic: identical tokens
    eng2 = _paged_engine(params, cfg, n_slots=4)
    try:
        noise = [eng2.submit([9, 9, 9], 12,
                             sampling=SamplingParams(temperature=1.3, seed=i))
                 for i in range(3)]
        r = eng2.submit([1, 2, 3], 8, sampling=sp)
        greedy = eng2.submit([5, 6], 6)
        assert r.done.wait(180) and greedy.done.wait(180)
        for n in noise:
            assert n.done.wait(180)
        assert r.tokens == solo, (r.tokens, solo)
        want = D.generate(params, jnp.asarray([[5, 6]], jnp.int32), cfg,
                          max_new_tokens=6)[0].tolist()
        assert greedy.tokens == want
        other = eng2.generate([1, 2, 3], 8,
                              sampling=SamplingParams(temperature=0.8, seed=7))
        assert other != solo
    finally:
        eng2.shutdown()


def test_top_k_one_equals_greedy():
    """top_k=1 at any temperature collapses to argmax — the sampling
    mask is provably reaching the device."""
    params, cfg = _tiny()
    from ray_tpu.serve._internal.sampling import SamplingParams

    eng = _paged_engine(params, cfg)
    try:
        greedy = eng.generate([3, 1, 4], 6)
        forced = eng.generate(
            [3, 1, 4], 6,
            sampling=SamplingParams(temperature=5.0, top_k=1, seed=9))
        assert forced == greedy
    finally:
        eng.shutdown()


def test_stop_token_truncates_through_macro_repair():
    """A stop token ends the request mid-plan: delivery truncates BEFORE
    the stop token, finish_reason records it, the discarded speculative
    steps are billed, and the freed slot serves new work."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as D
    from ray_tpu.serve._internal.sampling import SamplingParams

    params, cfg = _tiny()
    w = D.generate(params, jnp.asarray([[5, 6, 7]], jnp.int32), cfg,
                   max_new_tokens=12)[0].tolist()
    stop_tok = w[3]
    cut = w.index(stop_tok)  # first occurrence is where truncation lands
    eng = _paged_engine(params, cfg)
    try:
        req = eng.submit([5, 6, 7], 12, sampling=SamplingParams(stop=(stop_tok,)))
        assert req.done.wait(180)
        assert req.error is None, req.error
        assert req.tokens == w[:cut], (req.tokens, w, stop_tok)
        assert req.finish_reason == "stop"
        m = eng.metrics()
        assert m["plan_repair_waste_pct"] > 0
        # the repaired slot is reusable: a follow-up runs fine
        again = eng.generate([5, 6, 7], 4)
        assert again == w[:4]
    finally:
        eng.shutdown()
    eng._prefix.clear()
    assert eng._alloc.check_zero(), eng._alloc.leaked()


def test_timeout_cancels_and_frees_blocks():
    """generate() timeout CANCELS the request: the slot and its KV
    blocks free at the next plan boundary instead of burning decode
    steps forever, and the engine keeps serving."""
    import time

    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as D

    params, cfg = _tiny()
    eng = _paged_engine(params, cfg, n_slots=1, max_len=128, macro_phases=2)
    try:
        with pytest.raises(TimeoutError):
            eng.generate(list(range(1, 9)), 100, timeout=0.001)
        deadline = time.time() + 60
        while time.time() < deadline:
            if all(r is None for r in eng._slots) and not eng._waiting:
                held = {b: r for b, r in eng._alloc.leaked().items()}
                if len(held) <= eng._prefix.nodes:  # only cache-pinned left
                    break
            time.sleep(0.05)
        assert all(r is None for r in eng._slots), "slot never reclaimed"
        # engine is healthy: the freed slot serves the next request
        out = eng.generate([1, 2, 3], 4)
        want = D.generate(params, jnp.asarray([[1, 2, 3]], jnp.int32), cfg,
                          max_new_tokens=4)[0].tolist()
        assert out == want
    finally:
        eng.shutdown()
    eng._prefix.clear()
    assert eng._alloc.check_zero(), eng._alloc.leaked()


def test_default_engine_samples_and_stops():
    """`ContinuousBatchingEngine(params, cfg)` with no further argument is
    the engine every deployment runs: it has a block allocator, and it
    serves a seeded sampled request and a stop-token request."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as D
    from ray_tpu.serve._internal.sampling import SamplingParams
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    params, cfg = _tiny()
    greedy = D.generate(params, jnp.asarray([[1, 2]], jnp.int32), cfg,
                        max_new_tokens=6)[0].tolist()
    eng = ContinuousBatchingEngine(params, cfg)
    try:
        assert eng._alloc is not None and eng.metrics()["kv_blocks_total"] > 0
        sampled = [eng.generate([1, 2], 6, sampling=SamplingParams(
            temperature=0.9, seed=7)) for _ in range(2)]
        assert sampled[0] == sampled[1] and len(sampled[0]) == 6
        assert all(0 <= t < cfg.vocab_size for t in sampled[0])
        req = eng.submit([1, 2], 6, sampling=SamplingParams(stop=(greedy[2],)))
        assert req.done.wait(180) and req.error is None, req.error
        assert req.tokens == greedy[: greedy.index(greedy[2])]
        assert req.finish_reason == "stop"
    finally:
        eng.shutdown()


def test_sampling_params_validation():
    from ray_tpu.serve._internal.sampling import SamplingParams

    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(stop=(1, 2, 3, 4, 5))
    sp = SamplingParams(stop=(2,))
    assert sp.stop_row() == (2, -1, -1, -1)
    assert SamplingParams.from_request(None).greedy
    assert SamplingParams.from_request({"temperature": 0.5}).temperature == 0.5


def test_generate_sampled_one_dispatch():
    """Satellite: the sampled path of llama_decode.generate must run as
    ONE fused scan — never the legacy per-token host loop (which paid a
    host dispatch per token via _jitted_decode_step)."""
    import jax

    from ray_tpu.models import llama_decode as D

    params, cfg = _tiny()
    prompt = np.asarray([[1, 2, 3], [4, 5, 6]], np.int32)

    def boom(*a, **k):  # pragma: no cover - tripwire
        raise AssertionError("sampled generate fell back to per-token host loop")

    orig = D._jitted_decode_step
    D._jitted_decode_step = boom
    try:
        t1 = D.generate(params, prompt, cfg, 6, temperature=0.9,
                        rng=jax.random.PRNGKey(3))
        t2 = D.generate(params, prompt, cfg, 6, temperature=0.9,
                        rng=jax.random.PRNGKey(3))
        t3 = D.generate(params, prompt, cfg, 6, temperature=0.9,
                        rng=jax.random.PRNGKey(4))
    finally:
        D._jitted_decode_step = orig
    assert t1.shape == (2, 6)
    np.testing.assert_array_equal(t1, t2)
    assert not np.array_equal(t1, t3)
    assert ((0 <= t1) & (t1 < cfg.vocab_size)).all()


def test_seedless_sampled_requests_draw_fresh_entropy():
    """Two sampled requests that OMIT the seed must not share a token
    stream (the engine draws fresh entropy per request); explicit seeds
    — including 0 — stay reproducible."""
    from ray_tpu.serve._internal.sampling import SamplingParams

    params, cfg = _tiny()
    eng = _paged_engine(params, cfg)
    try:
        a = eng.generate([1, 2, 3], 8, sampling=SamplingParams(temperature=1.2))
        b = eng.generate([1, 2, 3], 8, sampling=SamplingParams(temperature=1.2))
        assert a != b, "seedless sampled requests shared a stream"
        z1 = eng.generate([1, 2, 3], 8,
                          sampling=SamplingParams(temperature=1.2, seed=0))
        z2 = eng.generate([1, 2, 3], 8,
                          sampling=SamplingParams(temperature=1.2, seed=0))
        assert z1 == z2
    finally:
        eng.shutdown()


def test_parse_request_missing_prompt():
    from ray_tpu.serve.llm import _parse_request

    with pytest.raises(ValueError, match="prompt"):
        _parse_request({"tokens": [1, 2], "temperature": 0.5}, 8)
    # typo'd sampling field: a named ValueError, not a dataclass TypeError
    with pytest.raises(ValueError, match="temprature"):
        _parse_request({"prompt": [1, 2], "temprature": 0.5}, 8)
    prompt, max_new, sp, rid = _parse_request(
        {"prompt": [1, 2], "temperature": 0.5, "max_new_tokens": 3,
         "request_id": "r-1"}, 8)
    assert prompt == [1, 2] and max_new == 3 and sp.temperature == 0.5
    assert rid == "r-1"


def test_failed_admission_retries_do_not_inflate_hit_rate():
    """A pool-exhausted admission retried across plan ticks counts as
    ONE lookup when it finally lands, not hundreds."""
    from ray_tpu.serve._internal.kv_blocks import BlockAllocator
    from ray_tpu.serve._internal.prefix_cache import RadixPrefixCache

    a = BlockAllocator(8, 4)
    c = RadixPrefixCache(a)
    t = a.alloc(2)
    c.insert(list(range(8)), t)
    for _ in range(50):  # engine-style unrecorded retries
        blocks, _ = c.lookup(list(range(8)) + [9], record=False)
        a.decref(blocks)
    assert c.hits == 0 and c.lookup_tokens == 0
    blocks, matched = c.lookup(list(range(8)) + [9], record=False)
    c.record_lookup(9, len(blocks))
    assert c.hits == 1 and c.hit_tokens == 8
    a.decref(blocks)
    a.decref(t)
    c.clear()
    assert a.check_zero()


def test_cancel_vs_delivery_race_single_completion():
    """cancel() hammered against normal delivery: exactly one completer
    wins, on_done fires exactly once, and a won delivery never reports
    the cancel error."""
    import threading

    params, cfg = _tiny()
    eng = _paged_engine(params, cfg)
    try:
        for i in range(6):
            fired = []
            req = eng.submit([1 + i, 2, 3], 4,
                             on_done=lambda r, f=fired: f.append(r.error))
            # cancel from another thread racing the engine's delivery
            t = threading.Thread(target=eng.cancel, args=(req, "race-cancel"))
            t.start()
            assert req.done.wait(120)
            t.join(10)
            assert len(fired) == 1, f"on_done fired {len(fired)} times"
            if req.error is None:
                assert len(req.tokens) == 4 and req.finish_reason == "length"
            else:
                assert req.error == "race-cancel"
                assert req.finish_reason == "cancelled"
    finally:
        eng.shutdown()


def test_generate_top_k_one_greedy_parity():
    """generate(top_k=1) at high temperature equals greedy generate —
    the fused sampled scan applies the same mask the engine does."""
    from ray_tpu.models import llama_decode as D

    params, cfg = _tiny()
    prompt = np.asarray([[3, 1, 4, 1, 5]], np.int32)
    greedy = D.generate(params, prompt, cfg, 6)
    forced = D.generate(params, prompt, cfg, 6, temperature=3.0, top_k=1)
    np.testing.assert_array_equal(greedy, forced)
