"""Paged KV cache tests: pool allocator invariants, prefix-cache
refcounting, paged-vs-ring token identity on the traffic grids,
shared-prefix prefill savings, copy-on-write under ring wrap, clean
backpressure on pool exhaustion, and the decode tick itself: one tick
against the ring decode, and the structure of its lowered program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.mlir import ir

from repro.configs import get_config
from repro.core import ExpertRegistry, build_matcher, train_bank
from repro.data import load_benchmark
from repro.models import build_model
from repro.serve import (EngineCore, ExpertEngine, PagePool,
                         PagePoolExhausted, PrefixCache, Request,
                         RoutedServer, hash_chain, plan_placement)

from _prop import given, settings, strategies as st


# -- allocator properties ---------------------------------------------------


@settings(max_examples=10)
@given(st.integers(1, 3), st.integers(4, 40), st.integers(1, 200))
def test_page_pool_refcount_free_list_invariants(E, n_pages, seed):
    """Random alloc/retain/release interleavings preserve the core
    invariant: every page is either free with refcount 0 or held with a
    positive refcount, exactly once — and a failed (oversized) alloc
    changes nothing."""
    rng = np.random.default_rng(seed)
    pool = PagePool(E, n_pages, page_size=8)
    held = {e: [] for e in range(E)}      # one entry per reference
    for _ in range(60):
        e = int(rng.integers(E))
        op = rng.random()
        if op < 0.45:
            n = int(rng.integers(0, n_pages + 2))
            free_before = pool.free_count(e)
            refs_before = pool.refs.copy()
            if n > free_before:
                with pytest.raises(PagePoolExhausted):
                    pool.alloc(e, n)
                # transactional: nothing moved
                assert pool.free_count(e) == free_before
                np.testing.assert_array_equal(pool.refs, refs_before)
            else:
                for p in pool.alloc(e, n):
                    held[e].append(p)
        elif op < 0.7 and held[e]:
            p = held[e][int(rng.integers(len(held[e])))]
            pool.retain(e, [p])
            held[e].append(p)
        elif held[e]:
            p = held[e].pop(int(rng.integers(len(held[e]))))
            pool.release(e, [p])
        pool.check()
        # the counters pair (sampled by --check-invariants) conserves
        # under every interleaving: free + used == E * n_pages, with
        # free agreeing with the per-expert free lists
        c = pool.counters()
        assert c["free"] + c["used"] == E * n_pages, c
        assert c["free"] == sum(pool.free_count(e2) for e2 in range(E))
        # refcounts mirror the shadow ledger exactly
        for e2 in range(E):
            want = np.bincount(held[e2], minlength=n_pages) \
                if held[e2] else np.zeros(n_pages, int)
            np.testing.assert_array_equal(pool.refs[e2], want)
    for e in range(E):
        for p in held[e]:
            pool.release(e, [p])
    pool.check()
    assert all(pool.free_count(e) == n_pages for e in range(E))


def test_pool_counters_track_residency_not_refcounts():
    """counters() counts page *residency* (off the free list), so a
    retain/release cycle on a held page must not move it — only the
    final release that returns the page to the free list does."""
    pool = PagePool(2, 10, 8)
    total = 2 * 10
    assert pool.counters() == {"free": total, "used": 0}
    a = pool.alloc(0, 3)
    b = pool.alloc(1, 5)
    assert pool.counters() == {"free": total - 8, "used": 8}
    pool.retain(0, a)                  # extra refs: residency unchanged
    assert pool.counters()["used"] == 8
    pool.release(0, a)
    assert pool.counters()["used"] == 8
    pool.release(0, a)                 # last ref: pages go free
    pool.release(1, b)
    assert pool.counters() == {"free": total, "used": 0}
    pool.check()


def test_page_pool_double_free_and_stale_retain_raise():
    pool = PagePool(1, 4, 8)
    (p,) = pool.alloc(0, 1)
    pool.release(0, [p])
    with pytest.raises(ValueError, match="double free"):
        pool.release(0, [p])
    with pytest.raises(ValueError, match="retain of free"):
        pool.retain(0, [p])


def test_prefix_cache_holds_refs_and_eviction_releases():
    pool = PagePool(1, 8, 8)
    cache = PrefixCache(pool, capacity=64)
    toks = np.arange(24, dtype=np.int32)
    chain = hash_chain(toks, 8)
    pages = pool.alloc(0, 3)
    cache.insert(0, 24, chain, pages, first_token=7)
    pool.release(0, pages)            # the "wave" retires its refs
    pool.check()
    assert pool.free_count(0) == 5    # cache still pins all three
    # adoption hands the caller its own references
    adopted = cache.adopt_prefix(0, chain)
    assert adopted == pages
    assert cache.first_token(0, 24, chain) == 7
    # a divergent second page stops the walk after the shared head
    other = toks.copy()
    other[10] = 99
    assert cache.adopt_prefix(0, hash_chain(other, 8)) == pages[:1]
    pool.release(0, pages[:1])
    # eviction releases the cache's refs; caller-held refs keep pages
    cache.evict_for(0, need=8)
    pool.check()
    pool.release(0, adopted)
    pool.check()
    assert pool.free_count(0) == 8


def test_prefix_cache_lru_eviction_under_churn():
    """Churn far past capacity: every LRU eviction must release its
    pool pin (the pool never runs dry from cache pressure alone), the
    live pin count must equal the page entries actually in the cache,
    and a full-cache cycle must return every refcount to baseline."""
    pool = PagePool(1, 32, 8)
    cache = PrefixCache(pool, capacity=8)
    baseline_free = pool.free_count(0)
    for k in range(40):                      # 40 distinct 2-page chains
        toks = np.full(16, k, np.int32)
        chain = hash_chain(toks, 8)
        pages = pool.alloc(0, 2)
        cache.insert(0, 16, chain, pages, first_token=k)
        pool.release(0, pages)               # the computing wave retires
        pool.check()
        # pinned pages == page entries currently indexed, exactly
        n_pg = sum(1 for key in cache._lru if key[0] == "pg")
        assert pool.used_count(0) == n_pg
        assert len(cache) <= 8
    assert cache.stats["evictions"] > 0
    # an entry evicted while a live row still holds the page must not
    # free it under the row
    toks = np.full(16, 99, np.int32)
    chain = hash_chain(toks, 8)
    pages = pool.alloc(0, 2)
    cache.insert(0, 16, chain, pages, first_token=1)
    cache.clear()                            # cache pin released...
    pool.check()
    assert all(pool.refs[0, p] == 1 for p in pages)  # ...row pin holds
    pool.release(0, pages)
    pool.check()
    assert pool.free_count(0) == baseline_free, \
        "refcounts did not return to baseline after a full-cache cycle"


def test_engine_rejects_unpageable_config():
    cfg = get_config("smollm-135m").reduced(name="odd-bucket")
    model = build_model(cfg)
    with pytest.raises(ValueError, match="multiple of page_size"):
        ExpertEngine(model, None, max_len=60, kv_layout="paged")
    cfg_r = get_config("rwkv6-7b").reduced(name="rwkv")
    rwkv = build_model(cfg_r)
    with pytest.raises(ValueError, match="paged KV"):
        ExpertEngine(rwkv, None, max_len=64, kv_layout="paged")


# -- serving fixtures -------------------------------------------------------


@pytest.fixture(scope="module")
def bench():
    return load_benchmark(names=["mnist", "har"], n_per_dataset=300,
                          seed=0)


@pytest.fixture(scope="module")
def matcher(bench):
    names = list(bench)
    aes, _ = train_bank([(n, bench[n]["server"][0]) for n in names],
                        epochs=8, batch_size=64)
    cents = [(bench[n]["server"][0], bench[n]["server"][1])
             for n in names]
    return build_matcher(aes, names, cents), names


@pytest.fixture(scope="module")
def shared_model():
    cfg = get_config("smollm-135m").reduced(name="paged-t")
    model = build_model(cfg)
    params = [model.init(jax.random.PRNGKey(s)) for s in (0, 1)]
    return model, params


def _server(matcher, shared_model, kv, **kw):
    m, names = matcher
    model, params = shared_model
    reg = ExpertRegistry()
    for n, p in zip(names, params):
        reg.add(n, ExpertEngine(model, p, max_len=64, kv_layout=kv, **kw))
    return RoutedServer(m, reg, max_batch=4), reg


def _traffic(bench, names, rng, n, shared=None, share_every=0):
    reqs = []
    for uid in range(n):
        nm = names[uid % 2]
        x, _ = bench[nm]["client_a"]
        if shared is not None and share_every and uid % share_every == 0:
            prompt = shared
        else:
            prompt = rng.integers(0, 100, size=int(rng.integers(1, 40)))
        reqs.append(Request(uid=uid, features=x[uid % 60], prompt=prompt,
                            max_new_tokens=int(rng.integers(1, 7))))
    return reqs


# -- token identity ---------------------------------------------------------


def test_paged_token_identical_to_ring_on_traffic_grids(matcher, bench,
                                                        shared_model):
    """The acceptance criterion: paged decode must be token-identical to
    the ring path on uniform / skewed / bursty shaped traffic (mixed
    prompt lengths, max_new, expert mixes), while the pool invariants
    hold throughout."""
    srv_r, _ = _server(matcher, shared_model, "ring")
    srv_p, reg_p = _server(matcher, shared_model, "paged")
    m, names = matcher
    uid0 = 0
    for scenario in ("uniform", "skewed", "bursty"):
        rng = np.random.default_rng(0xA0 + uid0)
        reqs = []
        for k in range(9):
            if scenario == "skewed":
                e = 0 if rng.random() < 0.8 else 1
            else:
                e = int(rng.integers(2))
            x, _ = bench[names[e]]["client_a"]
            reqs.append(Request(
                uid=uid0 + k, features=x[(uid0 + k) % 60],
                prompt=rng.integers(0, 100, size=int(rng.integers(1, 40))),
                max_new_tokens=int(rng.integers(1, 7))))
        uid0 += 9
        if scenario == "bursty":       # one burst, then drain
            got_r = srv_r.serve(reqs)
            got_p = srv_p.serve(reqs)
        else:                          # trickled submits
            got_r, got_p = [], []
            for lo in range(0, len(reqs), 3):
                got_r += srv_r.serve(reqs[lo:lo + 3])
                got_p += srv_p.serve(reqs[lo:lo + 3])
        for a, b in zip(got_r, got_p):
            assert a.uid == b.uid and a.expert == b.expert, scenario
            assert a.fine_class == b.fine_class
            np.testing.assert_array_equal(a.tokens, b.tokens,
                                          err_msg=f"{scenario}/{a.uid}")
        for e in range(2):
            reg_p[e].backend.core.pool.check()


def test_shared_prefix_cohort_prefill_savings(matcher, bench,
                                              shared_model):
    """Cohort traffic (identical prompts) must be deduplicated in-wave
    and served from the prefix cache across waves: strictly fewer
    prefill tokens computed than submitted, token-identically to ring."""
    srv_r, _ = _server(matcher, shared_model, "ring")
    srv_p, reg_p = _server(matcher, shared_model, "paged")
    m, names = matcher
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 100, size=30)     # 32-bucket, no ring wrap
    x, _ = bench[names[0]]["client_a"]
    # one feature sample for the whole cohort: routing (and therefore
    # the expert whose stats we assert on) is deterministic
    mk = lambda uid, mn: Request(uid=uid, features=x[0],
                                 prompt=shared, max_new_tokens=mn)
    # first cohort coalesces into one wave: one computed row, three dups
    reqs1 = [mk(u, 2 + u % 3) for u in range(4)]
    # second cohort arrives after the first retired: full cache hits
    reqs2 = [mk(10 + u, 2 + u % 4) for u in range(3)]
    got_p = srv_p.serve(reqs1)
    got_p += srv_p.serve(reqs2)
    got_r = srv_r.serve(reqs1)
    got_r += srv_r.serve(reqs2)
    for a, b in zip(got_r, got_p):
        np.testing.assert_array_equal(a.tokens, b.tokens,
                                      err_msg=str(a.uid))
    e = names.index(got_p[0].expert)     # the cohort's (single) expert
    st = reg_p[e].backend.stats
    assert st.prefix_dup_rows >= 3
    assert st.prefix_full_hits >= 3, st
    assert st.prefill_tokens_computed < st.prefill_tokens_submitted, st
    # the second cohort needed no prefill dispatch at all
    assert st.prefill_rows_computed == 1, st
    cache = reg_p[e].backend.core.prefix_cache
    assert cache.stats["full_hits"] >= 3


def test_wrap_forces_copy_on_write_and_stays_identical(matcher, bench,
                                                       shared_model):
    """Prompts at the 64-bucket make decode wrap into prompt pages; a
    dup row sharing those pages must get its own copies (COW) — never
    corrupt its representative's pages — and match ring exactly."""
    srv_r, _ = _server(matcher, shared_model, "ring")
    srv_p, reg_p = _server(matcher, shared_model, "paged")
    m, names = matcher
    rng = np.random.default_rng(9)
    long = rng.integers(0, 100, size=60)       # Sb = 64 = capacity
    x, _ = bench[names[0]]["client_a"]
    # identical features: the whole cohort lands on one expert
    reqs = [Request(uid=u, features=x[0], prompt=long,
                    max_new_tokens=6) for u in range(3)]
    got_r = srv_r.serve(reqs)
    got_p = srv_p.serve(reqs)
    for a, b in zip(got_r, got_p):
        np.testing.assert_array_equal(a.tokens, b.tokens,
                                      err_msg=str(a.uid))
    e = names.index(got_p[0].expert)
    st = reg_p[e].backend.stats
    assert st.pages_copied >= 2, st
    pool = reg_p[e].backend.core.pool
    pool.check()
    # COW remaps moved references between pages but conserved the books
    c = pool.counters()
    assert c["free"] + c["used"] == pool.n_experts * pool.n_pages, c


# -- exhaustion / backpressure ----------------------------------------------


def test_pool_exhaustion_requeues_cleanly(matcher, bench, shared_model):
    """A pool sized for ~one wave forces admissions to stall while
    earlier waves decode; the scheduler must requeue (never corrupt
    resident rows' pages) and still produce ring-identical tokens."""
    srv_r, _ = _server(matcher, shared_model, "ring")
    srv_t, reg_t = _server(matcher, shared_model, "paged", pool_pages=40)
    m, names = matcher
    rng = np.random.default_rng(11)
    # long prompts: a 4-row wave owns 32 pages, so a second wave cannot
    # be admitted while the first is resident (40-page pool) — the
    # stall path must trigger
    reqs = []
    for uid in range(16):
        nm = names[uid % 2]
        x, _ = bench[nm]["client_a"]
        reqs.append(Request(
            uid=uid, features=x[uid % 60],
            prompt=rng.integers(0, 100, size=int(rng.integers(33, 48))),
            max_new_tokens=int(rng.integers(2, 7))))
    got_r = srv_r.serve(reqs)
    got_t = srv_t.serve(reqs)
    for a, b in zip(got_r, got_t):
        np.testing.assert_array_equal(a.tokens, b.tokens,
                                      err_msg=str(a.uid))
    assert srv_t.scheduler.stats.kv_stalls >= 1, \
        "tiny pool never stalled — test is vacuous"
    for e in range(2):
        reg_t[e].backend.core.pool.check()
        # nothing leaked once drained (only prefix-cache pins remain)
        pool = reg_t[e].backend.core.pool
        cache_refs = sum(1 for k in reg_t[e].backend.core.prefix_cache._lru
                         if k[0] == "pg")
        assert pool.used_count(e=0) == cache_refs


def test_engine_admit_beyond_pool_raises_transactionally(shared_model):
    """An admission the pool can never host raises PagePoolExhausted
    without corrupting the resident wave's pages: the resident rows
    still decode to the same tokens as an unmolested engine."""
    model, params = shared_model
    eng = ExpertEngine(model, params[0], max_len=64, kv_layout="paged",
                       pool_pages=40)
    ref = ExpertEngine(model, params[0], max_len=64, kv_layout="ring")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 100, size=20) for _ in range(2)]
    eng.admit([0, 1], prompts, [4, 4], defer=True)
    ref.admit([0, 1], prompts, [4, 4])
    used_before = eng.core.pool.used_count(0)
    big = [rng.integers(0, 100, size=60) for _ in range(4)]
    with pytest.raises(PagePoolExhausted):
        eng.admit([2, 3, 4, 5], big, [4] * 4, defer=True)
    # transactional: the failed admission left no pages behind
    assert eng.core.pool.used_count(0) == used_before
    eng.core.pool.check()
    while eng.n_active:
        eng.tick()
    while ref.n_active:
        ref.tick()
    got, want = dict(eng.poll()), dict(ref.poll())
    for u in (0, 1):
        np.testing.assert_array_equal(got[u], want[u])


def test_rollback_with_cow_remaps_releases_everything(shared_model):
    """Regression: the dup branch's rollback-ledger entry aliased the
    row's mutable page list, so a COW remap before a mid-wave
    PagePoolExhausted corrupted the ledger — rollback double-freed the
    fresh COW page (ValueError instead of clean backpressure) and
    leaked the shared pages. Exhaustion during a COW-heavy wave must
    roll back to an empty pool."""
    model, params = shared_model
    # 9 pages: the computed row takes 8 (Sb = 64), the first dup's COW
    # takes the 9th, the second dup's COW must exhaust mid-plan
    eng = ExpertEngine(model, params[0], max_len=64, kv_layout="paged",
                       pool_pages=9)
    long = np.random.default_rng(0).integers(0, 100, size=60)
    with pytest.raises(PagePoolExhausted):
        eng.admit([0, 1, 2], [long] * 3, [6, 6, 6], defer=True)
    eng.core.pool.check()
    assert eng.core.pool.free_count(0) == 9, "rollback leaked pages"
    assert eng.n_active == 0


def test_pool_too_small_for_one_wave_surfaces(matcher, bench,
                                              shared_model):
    """When even an empty engine cannot host a wave, requeueing would
    spin forever — the scheduler must surface the configuration error."""
    srv, _ = _server(matcher, shared_model, "paged", pool_pages=4)
    m, names = matcher
    x, _ = bench[names[0]]["client_a"]
    srv.submit([Request(uid=0, features=x[0],
                        prompt=np.arange(40, dtype=np.int32),
                        max_new_tokens=4)])
    with pytest.raises(PagePoolExhausted):
        srv.scheduler.drain()


# -- banked placement -------------------------------------------------------


def test_paged_banked_matches_ring_per_engine(matcher, bench,
                                              shared_model):
    """Cross-layout x cross-placement: a paged *banked* server must be
    token-identical to the per-engine ring reference, with prefix
    sharing live inside the bank."""
    m, names = matcher
    model, params = shared_model
    srv_ref, _ = _server(matcher, shared_model, "ring")
    reg = ExpertRegistry()
    for n, p in zip(names, params):
        reg.add(n, ExpertEngine(model, p, max_len=64, kv_layout="paged"))
    plan = plan_placement(reg)
    assert plan.shards[0].banked and plan.shards[0].bank.kv_layout == \
        "paged"
    srv_b = RoutedServer(m, reg, max_batch=4, placement=plan)
    rng = np.random.default_rng(13)
    shared = rng.integers(0, 100, size=30)
    reqs = _traffic(bench, names, rng, 12, shared=shared, share_every=3)
    got_ref = srv_ref.serve(reqs)
    got_b = srv_b.serve(reqs)
    for a, b in zip(got_ref, got_b):
        assert a.expert == b.expert
        np.testing.assert_array_equal(a.tokens, b.tokens,
                                      err_msg=str(a.uid))
    assert plan.shards[0].bank.stats.prefix_dup_rows >= 1
    plan.shards[0].bank.core.pool.check()


# -- the decode tick, model level ---------------------------------------------


def _tick_core(n_experts, n_layers=2):
    """A paged engine of ``n_experts`` stacked experts (1: the unbanked
    engine ``ExpertEngine`` builds; 3: a bank) with its decode ladder."""
    cfg = get_config("smollm-135m").reduced(name="paged-tick",
                                            n_layers=n_layers)
    model = build_model(cfg)
    params = [model.init(jax.random.PRNGKey(s)) for s in range(n_experts)]
    return EngineCore(model, params, max_len=64, batch_buckets=(4,),
                      kv_layout="paged")


@pytest.mark.parametrize("n_experts", [1, 3])
def test_paged_decode_tick_matches_ring_decode_in_place(n_experts):
    """One tick of the engine's paged decode (vmapped over the experts,
    as served) against the ring decode on the same rows: logits are
    bitwise equal, and the pool afterwards equals the pool before with
    exactly the one new slot per real row per layer written — the ring
    decode's K/V at that slot. The padding row writes only the trash
    page; every other page, the row-tile padding included, is
    untouched."""
    core = _tick_core(n_experts)
    model, E, page, n = core.model, core.n_experts, core.page, core.n_logical
    cfg = model.cfg
    heads, W = (cfg.n_kv_heads, cfg.dh), cfg.n_kv_heads * cfg.dh
    Bb, C, trash = 4, n * page, core.pool.trash
    rng = np.random.default_rng(7 + E)
    shape = core.kv_pool["k"].shape                  # (E, L, rows, R)
    before = {k: rng.standard_normal(shape).astype(np.float32)
              for k in ("k", "v")}
    table = np.full((E, Bb, n), trash, np.int32)     # last row: padding
    for e in range(E):
        table[e, :Bb - 1] = rng.permutation(trash)[:(Bb - 1) * n].reshape(
            Bb - 1, n)
    t = (C // 2 + 3 + np.arange(E)).astype(np.int32)  # mid-page slots
    ar = np.arange(C)
    pos = np.where(ar[None] < t[:, None], ar[None], -1).astype(np.int32)
    tok = rng.integers(0, cfg.vocab_size, (E, Bb, 1)).astype(np.int32)

    # the ring decode on each row's dense view, read from the pool here
    views = {k: np.stack([before[k][e][:, table[e]].reshape(
        cfg.n_layers, Bb, C, *heads) for e in range(E)]) for k in before}
    ring_logits, ring = jax.jit(jax.vmap(model.decode))(
        core.params, {"k": views["k"], "v": views["v"], "pos": pos, "t": t},
        {"token": tok})

    logits, pool, pos2, t2 = core._decode_fn(Bb)(
        core.params, {k: jnp.asarray(v) for k, v in before.items()},
        jnp.asarray(table), jnp.asarray(pos), jnp.asarray(t),
        {"token": jnp.asarray(tok)})
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(ring_logits))
    np.testing.assert_array_equal(np.asarray(pos2), np.asarray(ring["pos"]))
    np.testing.assert_array_equal(np.asarray(t2), t + 1)
    for k in ("k", "v"):
        got, want = np.asarray(pool[k]), before[k].copy()
        new = np.asarray(ring[k])                    # (E, L, B, C, KV, dh)
        for e in range(E):
            slot = t[e] % C
            pg, off = table[e, :Bb - 1, slot // page], slot % page
            want[e][:, pg, off * W:(off + 1) * W] = new[e][
                :, :Bb - 1, slot].reshape(cfg.n_layers, Bb - 1, W)
        keep = np.arange(shape[2]) != trash
        np.testing.assert_array_equal(got[:, :, keep], want[:, :, keep])
        written = got[:, :, keep] != before[k][:, :, keep]
        assert written.sum() == E * cfg.n_layers * (Bb - 1) * W, k


def _hlo_ops(op):
    """Every operation nested in ``op``'s regions, depth first."""
    for region in op.regions:
        for block in region.blocks:
            for inner in block.operations:
                yield inner
                yield from _hlo_ops(inner)


def _dims(value):
    t = value.type
    return tuple(t.shape) if isinstance(t, ir.RankedTensorType) else ()


def test_paged_decode_lowering_moves_no_kv():
    """The lowered decode tick of a bank keeps its KV pool in place:
    the only operations that yield a pool-shaped value are the layer
    loop that carries it, the in-place slot scatter and the call of the
    loop body, and no value is one layer's plane of it, so the pool is
    never copied, sliced out or relaid out (the donated input aliases
    the output, checked by H001); no value is
    a layer-stacked view (L, B, C, KV, dh) or its page rows; and no
    transpose moves KV. Transposes of the bank's weights (the vmapped
    layer scan puts the layer axis before the expert axis) are outside
    this path and allowed; any other transpose must be smaller than one
    layer's KV view."""
    core = _tick_core(3, n_layers=5)
    cfg = core.model.cfg
    E, L, Bb, n, page = 3, cfg.n_layers, 4, core.n_logical, core.page
    C, KV, dh = n * page, cfg.n_kv_heads, cfg.dh
    S = jax.ShapeDtypeStruct
    i32 = jnp.int32
    p_av = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype),
                                  core.params)
    pool_av = jax.tree_util.tree_map(lambda x: S(x.shape, x.dtype),
                                     core.kv_pool)
    lowered = core._decode_fn(Bb).lower(
        p_av, pool_av, S((E, Bb, n), i32), S((E, C), i32), S((E,), i32),
        {"token": S((E, Bb, 1), i32)})
    pool_dims = tuple(pool_av["k"].shape)
    plane = pool_dims[:1] + pool_dims[2:]            # one layer's pages
    weights = {tuple(x.shape) for x in jax.tree_util.tree_leaves(p_av)}
    view = E * Bb * C * KV * dh
    stacked = [sorted(d) for d in ((L, Bb, C, KV, dh),
                                   (L, Bb, n, page * KV * dh))]

    def holds(dims, sub):
        rest = list(dims)
        for d in sub:
            if d not in rest:
                return False
            rest.remove(d)
        return True

    module = lowered.compiler_ir("stablehlo")
    makers, n_scatter = set(), 0
    for op in _hlo_ops(module.operation):
        name = op.operation.name
        for r in op.results:
            dims = _dims(r)
            assert not any(holds(dims, s) for s in stacked), (name, dims)
            assert dims != plane, name
            if dims == pool_dims:
                makers.add(name)
                n_scatter += name == "stablehlo.scatter"
        if name == "stablehlo.transpose":
            src = _dims(op.operands[0])
            assert src in weights or int(np.prod(src)) < view, src
    assert makers == {"stablehlo.while", "stablehlo.scatter",
                      "func.call"}, makers
    assert n_scatter == 2                            # K and V, per layer
