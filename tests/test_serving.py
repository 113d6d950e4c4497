"""Serving subsystem tests: scheduler/engine/router behaviour under
mixed-shape traffic, banked placement equivalence, plus
kernel-vs-reference routing parity."""
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (ExpertRegistry, MatcherConfig, build_matcher,
                        train_bank)
from repro.core.autoencoder import bank_scores
from repro.data import load_benchmark
from repro.models import build_model
from repro.serve import (BankMember, BankedEngine, ExpertEngine, Request,
                         Response, RoutedServer, bucket_for, make_buckets,
                         plan_placement)
from repro.serve.router import Router

# deterministic grid strategies (always the fallback module: the
# equivalence test samples explicitly via .sample(rng), which the real
# hypothesis API does not expose)
from _prop import strategies as grid_st


@pytest.fixture(scope="module")
def bench():
    return load_benchmark(names=["mnist", "har"], n_per_dataset=400, seed=0)


@pytest.fixture(scope="module")
def matcher(bench):
    names = list(bench)
    aes, _ = train_bank([(n, bench[n]["server"][0]) for n in names],
                        epochs=12, batch_size=64)
    cents = [(bench[n]["server"][0], bench[n]["server"][1]) for n in names]
    return build_matcher(aes, names, cents), names


def _engine(seed=0, max_len=64):
    cfg = get_config("smollm-135m").reduced(name=f"eng-{seed}")
    model = build_model(cfg)
    return ExpertEngine(model, model.init(jax.random.PRNGKey(seed)),
                        max_len=max_len)


def _server(matcher, max_batch=4):
    m, names = matcher
    reg = ExpertRegistry()
    for i, n in enumerate(names):
        reg.add(n, _engine(seed=i))
    return RoutedServer(m, reg, max_batch=max_batch), names


# -- buckets ----------------------------------------------------------------


def test_bucket_ladder():
    assert make_buckets(8, 64) == (8, 16, 32, 64)
    assert make_buckets(1, 12) == (1, 2, 4, 8, 12)
    assert bucket_for(3, (4, 8)) == 4
    assert bucket_for(9, (4, 8)) == 8  # clamps to largest


def test_make_buckets_validates_inputs():
    """lo > hi used to silently return (hi,), so ExpertEngine(max_len=4,
    min_len_bucket=8) built a ladder that ignored min_len_bucket."""
    assert make_buckets(8, 8) == (8,)
    assert make_buckets(3, 3) == (3,)
    with pytest.raises(ValueError):
        make_buckets(8, 4)
    with pytest.raises(ValueError):
        make_buckets(0, 4)
    with pytest.raises(ValueError):
        make_buckets(-2, -1)
    cfg = get_config("smollm-135m").reduced(name="buckets-smoke")
    model = build_model(cfg)
    with pytest.raises(ValueError):
        ExpertEngine(model, None, max_len=4, min_len_bucket=8)
    assert bucket_for(1, (4, 8)) == 4
    assert bucket_for(8, (4, 8)) == 8  # exact hit picks its own bucket


# -- engine -----------------------------------------------------------------


def test_admit_rejects_empty_micro_batch_and_generate_handles_zero_rows():
    """Regression: a B=0 admit crashed with a bare ValueError escaping
    from max() deep inside padding; generate() on zero rows crashed the
    same way. Empty admits are now rejected loudly and zero-row
    generate returns an empty (0, max_new) array."""
    eng = _engine(seed=13, max_len=32)
    with pytest.raises(ValueError, match="empty micro-batch"):
        eng.admit([], [], [])
    out = eng.generate(np.zeros((0, 5), np.int32), 4)
    assert out.shape == (0, 4)
    assert out.dtype == np.int32
    assert eng.n_active == 0 and not eng.has_pending
    # the engine still serves normally afterwards
    got = eng.generate(np.arange(6, dtype=np.int32)[None, :], 2)
    assert got.shape == (1, 2)


def test_compile_counters_count_executables_not_wrappers():
    """EngineStats.prefill_compiles/decode_compiles must report real
    XLA executables (per-wrapper _cache_size sums), not jit-wrapper
    creations: a wrapper that exists but never ran holds no executable,
    and a silently recompiling wrapper would count per compile."""
    from repro.serve.core import _wrapper_compiles
    eng = _engine(seed=14, max_len=32)
    # wrapper created but never called -> no executable yet (the old
    # counter charged a compile at wrapper creation)
    eng.core._prefill_fn(1, 8)
    assert len(eng.core._prefill_fns) == 1
    assert eng.stats.prefill_compiles == 0
    rng = np.random.default_rng(0)
    eng.admit([0], [rng.integers(0, 50, 5)], [2])
    assert eng.stats.prefill_compiles == 1
    assert eng.stats.decode_compiles == 0      # no decode ran yet
    eng.tick()
    assert eng.stats.decode_compiles == 1
    # same-bucket traffic mints no new executable
    eng.admit([1], [rng.integers(0, 50, 6)], [1])
    assert eng.stats.prefill_compiles == 1
    # a new length bucket does
    eng.admit([2], [rng.integers(0, 50, 20)], [1])
    assert eng.stats.prefill_compiles == 2
    # the counter is exactly the sum over wrappers of real cache sizes
    assert eng.stats.prefill_compiles == sum(
        _wrapper_compiles(f) for f in eng.core._prefill_fns.values())
    while eng.n_active:
        eng.tick()
    eng.poll()


def test_engine_rows_finish_independently():
    """A row with small max_new is harvested before its group retires."""
    eng = _engine()
    rng = np.random.default_rng(0)
    eng.admit([7, 8], [rng.integers(0, 50, 5), rng.integers(0, 50, 5)],
              max_new=[1, 6])
    early = dict(eng.poll())
    assert 7 in early and early[7].shape == (1,)   # done at prefill
    assert 8 not in early
    while eng.n_active:
        eng.tick()
    late = dict(eng.poll())
    assert late[8].shape == (6,)


def test_engine_generate_matches_seed_contract():
    eng = _engine()
    toks = np.random.default_rng(1).integers(0, 50, size=(3, 9))
    out = eng.generate(toks, 5)
    assert out.shape == (3, 5)
    assert out.dtype == np.int32


def test_generate_does_not_steal_scheduler_rows():
    """Regression: generate() used to admit rows under uids 0..B-1 and
    drain poll() wholesale — colliding with scheduler-owned uids and
    silently consuming their finished rows."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 50, 6), rng.integers(0, 50, 4)]
    gen_toks = rng.integers(0, 50, size=(2, 5))

    # reference: the scheduler-owned rows served on a pristine engine
    ref = _engine(seed=3)
    ref.admit([0, 1], prompts, max_new=[3, 4])
    while ref.n_active:
        ref.tick()
    want = dict(ref.poll())

    # same engine params, but generate() interleaves with the admitted
    # group mid-flight — scheduler uids 0..1 overlap generate's rows
    eng = _engine(seed=3)
    eng.admit([0, 1], prompts, max_new=[3, 4])
    eng.tick()
    out = eng.generate(gen_toks, 2)
    assert out.shape == (2, 2)
    while eng.n_active:
        eng.tick()
    got = dict(eng.poll())
    assert set(got) == {0, 1}, "scheduler rows were stolen by generate()"
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])

    # and generate()'s own output matches a non-interleaved call
    ref2 = _engine(seed=3)
    np.testing.assert_array_equal(out, ref2.generate(gen_toks, 2))


def test_drain_delivers_rows_finished_during_generate(matcher, bench):
    """Regression: generate() interleaved mid-decode can tick a
    scheduler group to completion and re-queue its rows; has_work must
    then still report pending output or drain() strands the response."""
    srv, names = _server(matcher)
    x, _ = bench[names[0]]["client_a"]
    srv.submit([Request(uid=7, features=x[0], prompt=np.arange(5),
                        max_new_tokens=3)])
    srv.step()                      # admitted, still decoding
    # find the engine serving uid 7 and run a long generate() on it
    sched = srv.scheduler
    eng = next(srv.registry[e].backend for e in range(len(srv.registry))
               if srv.registry[e].backend.n_active)
    eng.generate(np.arange(4)[None, :], 8)
    assert sched.has_work, "finished-but-unpolled rows must keep work"
    got = sched.drain()
    assert [r.uid for r in got] == [7]
    assert got[0].tokens.shape == (3,)
    assert not sched._meta


# -- routed server end to end ----------------------------------------------


def test_uid_mapping_out_of_order(matcher, bench):
    """Responses must map to the right uid even though execution order is
    grouped per expert / length bucket, not arrival order."""
    srv, names = _server(matcher)
    rng = np.random.default_rng(2)
    reqs, truth = [], {}
    # interleave experts and shapes so per-expert grouping reorders rows
    for uid in range(24):
        n = names[uid % 2]
        x, _ = bench[n]["client_a"]
        reqs.append(Request(
            uid=uid, features=x[uid],
            prompt=rng.integers(0, 100, size=int(rng.integers(2, 40))),
            max_new_tokens=int(rng.integers(1, 9))))
        truth[uid] = n
    resps = srv.serve(reqs)
    assert [r.uid for r in resps] == [q.uid for q in reqs]
    acc = np.mean([r.expert == truth[r.uid] for r in resps])
    assert acc > 0.8
    for r, q in zip(resps, reqs):
        assert r.tokens.shape == (q.max_new_tokens,)
        assert r.fine_class >= 0
        assert r.coarse_scores is not None


def test_jit_cache_bounded_across_50_mixed_shape_requests(matcher, bench):
    """50 requests with ~unique (prompt len, max_new) combos must compile
    a bounded executable set: buckets, not request shapes, key the cache."""
    srv, names = _server(matcher)
    rng = np.random.default_rng(3)
    reqs = []
    for uid in range(50):
        n = names[uid % 2]
        x, _ = bench[n]["client_a"]
        reqs.append(Request(
            uid=uid, features=x[uid % 100],
            prompt=rng.integers(0, 100, size=1 + (uid * 7) % 60),
            max_new_tokens=1 + uid % 12))
    resps = srv.serve(reqs)
    assert len(resps) == 50
    for e in range(len(srv.registry)):
        st = srv.registry[e].backend.stats
        n_len = len(srv.registry[e].backend.len_buckets)
        n_bat = len(srv.registry[e].backend.batch_buckets)
        assert st.prefill_compiles <= n_len * n_bat
        assert st.decode_compiles <= n_bat
        # the practical bound the ISSUE cares about: far fewer distinct
        # executables than distinct request shapes
        assert st.jit_cache_entries <= 20, st
    # and replaying the identical traffic compiles nothing new
    before = [srv.registry[e].backend.stats.jit_cache_entries
              for e in range(len(srv.registry))]
    srv.serve(reqs)
    after = [srv.registry[e].backend.stats.jit_cache_entries
             for e in range(len(srv.registry))]
    assert before == after


def test_continuous_batching_coalesces_across_submits(matcher, bench):
    """Requests from separate submit() calls join one micro-batch."""
    srv, names = _server(matcher, max_batch=8)
    x, _ = bench[names[0]]["client_a"]
    rng = np.random.default_rng(4)
    mk = lambda uid: Request(uid=uid, features=x[0],
                             prompt=rng.integers(0, 100, size=10),
                             max_new_tokens=2)
    srv.submit([mk(0), mk(1)])
    srv.submit([mk(2), mk(3)])          # second call, same expert+bucket
    while srv.scheduler.has_work:
        srv.step()
    eng = srv.registry[0].backend
    assert eng.stats.prefill_calls == 1  # one coalesced micro-batch
    assert eng.stats.rows_served == 4


def test_backpressure_prefix_admission(matcher, bench):
    srv, names = _server(matcher)
    srv.scheduler.config.max_queue = 3
    x, _ = bench[names[0]]["client_a"]
    reqs = [Request(uid=u, features=x[u], prompt=np.arange(5),
                    max_new_tokens=1) for u in range(6)]
    assert srv.submit(reqs) == 3         # prefix admitted, tail rejected
    assert srv.scheduler.stats.rejected == 3
    got, todo = {}, reqs[3:]             # resubmit only the rejected tail
    while todo or srv.scheduler.has_work:
        if todo:
            todo = todo[srv.scheduler.submit(todo):]
        for r in srv.step():
            got[r.uid] = r
    assert sorted(got) == list(range(6))


def test_sparse_bucket_age_promotion_prevents_starvation(matcher, bench):
    """Regression: admission always popped the fullest length bucket, so
    under sustained traffic concentrated in one bucket a request parked
    in a sparse bucket starved until the flood ended."""
    srv, names = _server(matcher, max_batch=4)
    srv.scheduler.config.promote_after = 2
    x, _ = bench[names[0]]["client_a"]
    rng = np.random.default_rng(6)
    # one long-prompt request lands alone in the 32-bucket...
    srv.submit([Request(uid=0, features=x[0],
                        prompt=rng.integers(0, 100, size=30),
                        max_new_tokens=1)])
    # ...while a sustained flood keeps the 8-bucket the fullest forever
    done_during_flood = set()
    uid = 1
    for _ in range(10):
        srv.submit([Request(uid=uid + k, features=x[0],
                            prompt=rng.integers(0, 100, size=7),
                            max_new_tokens=1) for k in range(4)])
        uid += 4
        for r in srv.step():
            done_during_flood.add(r.uid)
    assert 0 in done_during_flood, \
        "sparse-bucket request starved through 10 flooded rounds"
    assert srv.scheduler.stats.promotions >= 1
    # drain the rest; nothing is lost or duplicated
    rest = {r.uid for r in srv.scheduler.drain()}
    assert done_during_flood | rest == set(range(uid))
    # skip counters are pruned once their buckets drain (no lifetime
    # growth, which matters for legacy backends keyed by raw lengths)
    assert not srv.scheduler._skips


# -- router -----------------------------------------------------------------


def test_router_fingerprint_cache_consistency(matcher, bench):
    m, names = matcher
    router = Router(m)
    x, _ = bench[names[0]]["client_a"]
    r1 = router.route(x[:16])
    assert r1.cache_hits == 0
    r2 = router.route(x[:16])
    assert r2.cache_hits == 16
    np.testing.assert_array_equal(r1.coarse, r2.coarse)
    np.testing.assert_array_equal(r1.fine, r2.fine)
    np.testing.assert_allclose(r1.coarse_score, r2.coarse_score)


def test_max_batch_above_engine_bucket_is_capped(matcher, bench):
    """Scheduler max_batch larger than the engine's biggest batch bucket
    must split micro-batches instead of crashing admit()."""
    srv, names = _server(matcher, max_batch=32)
    x, _ = bench[names[0]]["client_a"]
    reqs = [Request(uid=u, features=x[0], prompt=np.arange(6),
                    max_new_tokens=1) for u in range(20)]
    resps = srv.serve(reqs)
    assert len(resps) == 20
    assert srv.registry[0].backend.stats.prefill_calls >= 2  # split


def test_none_backend_completes_and_uid_is_reusable(matcher, bench):
    m, names = matcher
    from repro.core import ExpertRegistry
    reg = ExpertRegistry()
    for n in names:
        reg.add(n, None)  # no engines at all
    srv = RoutedServer(m, reg)
    x, _ = bench[names[0]]["client_a"]
    req = Request(uid=1, features=x[0], prompt=np.arange(4),
                  max_new_tokens=3)
    r1 = srv.serve([req])
    assert r1[0].tokens.shape == (3,) and not r1[0].tokens.any()
    r2 = srv.serve([req])  # uid free again after completion
    assert r2[0].uid == 1
    assert not srv.scheduler._meta  # no in-flight leak


def test_router_chunks_oversized_batches(matcher, bench):
    """Batches beyond the largest row bucket are routed in chunks and
    still produce reference-identical decisions."""
    m, names = matcher
    small = Router(m, max_rows=16)
    ref = Router(m)
    x = bench[names[0]]["client_a"][0][:40]   # 40 rows > max_rows=16
    got = small.route(x)
    want = ref.route(x)
    np.testing.assert_array_equal(got.coarse, want.coarse)
    np.testing.assert_array_equal(got.fine, want.fine)


def test_router_lru_eviction(matcher, bench):
    m, names = matcher
    router = Router(m, cache_size=8)
    x, _ = bench[names[0]]["client_a"]
    router.route(x[:32])
    assert len(router._lru) == 8


def test_router_lru_stores_copies_not_chunk_views(matcher, bench):
    """Regression: cached (coarse, score) rows were *views* into each
    routed chunk's full (rows, top_k) arrays, pinning every chunk in
    memory for the LRU entry's lifetime. A full cache must hold only
    O(top_k)-sized owned values."""
    m, names = matcher
    router = Router(m, cache_size=64)
    xs = np.concatenate([bench[n]["client_a"][0][:24] for n in names])
    router.route(xs)
    assert len(router._lru) > 0
    top_k = m.config.top_k
    for c, s, f in router._lru.values():
        assert c.base is None and s.base is None, \
            "LRU entry is a view pinning its whole routed chunk"
        assert c.nbytes <= top_k * 8 and s.nbytes <= top_k * 8
        assert isinstance(f, int)
    # cached decisions still replay exactly
    r1 = router.route(xs[:8])
    assert r1.cache_hits == 8


# -- sharded expert placement ------------------------------------------------


def _registries(matcher, seeds=(0, 1), max_len=64):
    """Two registries with *identical* engine params: one left per-engine,
    one to be banked by plan_placement."""
    m, names = matcher
    cfg = get_config("smollm-135m").reduced(name="placed")
    model = build_model(cfg)
    params = [model.init(jax.random.PRNGKey(s)) for s in seeds]
    regs = []
    for _ in range(2):
        reg = ExpertRegistry()
        for n, p in zip(names, params):
            reg.add(n, ExpertEngine(model, p, max_len=max_len))
        regs.append(reg)
    return regs


def test_plan_placement_banks_homogeneous_experts(matcher):
    m, names = matcher
    _, reg = _registries(matcher)
    # add a heterogeneous third entry: must stay a singleton shard
    cfg = get_config("smollm-135m").reduced(name="odd", d_model=64)
    odd = build_model(cfg)
    reg.add("odd", ExpertEngine(odd, odd.init(jax.random.PRNGKey(9)),
                                max_len=64))
    plan = plan_placement(reg)
    banked = [s for s in plan.shards if s.banked]
    solo = [s for s in plan.shards if not s.banked]
    assert len(banked) == 1 and banked[0].experts == (0, 1)
    assert len(solo) == 1 and solo[0].experts == (2,)
    assert plan.shard_of == {0: banked[0].sid, 1: banked[0].sid,
                             2: solo[0].sid}
    # registry entries were rebound to BankMember handles
    for e in (0, 1):
        be = reg[e].backend
        assert isinstance(be, BankMember)
        assert be.pad_shape(3, 9) == (4, 16)
    assert isinstance(reg[2].backend, ExpertEngine)
    bank = banked[0].bank
    assert isinstance(bank, BankedEngine) and bank.n_experts == 2


def test_plan_placement_frees_replaced_engines(matcher):
    """The engines a bank replaces hold device params and KV buffers in
    reference cycles: plan_placement must free them itself, not leave
    them beside the bank's stacked copy until a later cyclic GC."""
    _, reg = _registries(matcher)
    cores = [weakref.ref(reg[e].backend.core) for e in range(len(reg))]
    gc.disable()                    # only plan_placement may collect
    try:
        plan_placement(reg)
        assert [c() for c in cores] == [None] * len(cores)
    finally:
        gc.enable()


def test_dispatch_moe_experts_stay_singleton(matcher):
    """Capacity-dispatch MoE outputs depend on the padded batch size
    (capacity ~ total tokens), so banking them would break the
    token-identical contract — the planner must leave them solo."""
    _, reg = _registries(matcher)
    cfg = get_config("mixtral-8x22b").reduced(name="moe-pair")
    assert cfg.n_experts and cfg.moe_impl == "dispatch"
    moe = build_model(cfg)
    for i in (0, 1):
        reg.add(f"moe{i}", ExpertEngine(
            moe, moe.init(jax.random.PRNGKey(20 + i)), max_len=64))
    plan = plan_placement(reg)
    banked = [s for s in plan.shards if s.banked]
    assert len(banked) == 1 and banked[0].experts == (0, 1)
    solo_experts = {s.experts[0] for s in plan.shards if not s.banked}
    assert solo_experts == {2, 3}
    assert isinstance(reg[2].backend, ExpertEngine)


def test_forgotten_placement_plan_fails_fast(matcher):
    """plan_placement rebinds registry backends; wiring that registry
    into a server *without* the plan must raise up front, not crash
    deep inside admission at serve time."""
    m, names = matcher
    _, reg = _registries(matcher)
    plan = plan_placement(reg)
    with pytest.raises(ValueError, match="placement"):
        RoutedServer(m, reg)
    with pytest.raises(ValueError, match="already bank-placed"):
        plan_placement(reg)          # re-planning a planned registry
    # and a stale plan paired with a different registry fails fast too
    _, other = _registries(matcher)
    other_plan = plan_placement(other)
    del other_plan
    with pytest.raises(ValueError, match="does not match registry"):
        RoutedServer(m, other, placement=plan)
    # a registry grown after planning is uncovered -> fail fast, not hang
    from repro.serve import Scheduler
    reg.add("late", None)
    with pytest.raises(ValueError, match="does not cover"):
        Scheduler(None, reg, placement=plan)


def test_banked_jit_cache_is_per_bank_not_per_expert(matcher, bench):
    """The bank's executable count is bounded by its own bucket ladders
    *total* — co-locating K experts must not multiply compiles by K."""
    m, names = matcher
    _, reg = _registries(matcher)
    plan = plan_placement(reg)
    srv = RoutedServer(m, reg, max_batch=4, placement=plan)
    rng = np.random.default_rng(8)
    reqs = []
    for uid in range(30):
        n = names[uid % 2]
        x, _ = bench[n]["client_a"]
        reqs.append(Request(uid=uid, features=x[uid % 80],
                            prompt=rng.integers(0, 100,
                                                size=1 + (uid * 5) % 50),
                            max_new_tokens=1 + uid % 6))
    resps = srv.serve(reqs)
    assert len(resps) == 30
    bank = plan.shards[0].bank
    n_len, n_bat = len(bank.len_buckets), len(bank.batch_buckets)
    assert bank.stats.prefill_compiles <= n_len * n_bat
    assert bank.stats.decode_compiles <= n_bat
    # replaying identical traffic compiles nothing new
    before = bank.stats.jit_cache_entries
    srv.serve([Request(uid=100 + r.uid, features=reqs[i].features,
                       prompt=reqs[i].prompt,
                       max_new_tokens=reqs[i].max_new_tokens)
               for i, r in enumerate(resps)])
    assert bank.stats.jit_cache_entries == before


def test_banked_matches_per_engine_token_identical(matcher, bench):
    """Equivalence: the banked placement must produce token-identical
    responses to the per-engine path on the same request stream —
    property-style over the deterministic _prop grids."""
    m, names = matcher
    reg_ref, reg_bank = _registries(matcher)
    # cross-executor on top of cross-placement: the per-engine reference
    # runs the blocking serial dispatch, the banked server the default
    # overlapped one — tokens must still be identical
    srv_ref = RoutedServer(m, reg_ref, max_batch=4, executor="serial")
    plan = plan_placement(reg_bank)
    assert len([s for s in plan.shards if s.banked]) == 1
    srv_bank = RoutedServer(m, reg_bank, max_batch=4, placement=plan,
                            executor="overlapped")

    n_req = grid_st.integers(3, 8)
    plen = grid_st.integers(1, 40)
    mnew = grid_st.integers(1, 6)
    rng = np.random.default_rng(0xE7)
    uid = 0
    for _ in range(6):   # six property examples over the grid
        reqs = []
        for _ in range(n_req.sample(rng)):
            n = names[uid % 2]
            x, _ = bench[n]["client_a"]
            reqs.append(Request(
                uid=uid, features=x[uid % 60],
                prompt=rng.integers(0, 100, size=plen.sample(rng)),
                max_new_tokens=mnew.sample(rng)))
            uid += 1
        got_ref = srv_ref.serve(reqs)
        got_bank = srv_bank.serve(reqs)
        for a, b in zip(got_ref, got_bank):
            assert a.uid == b.uid
            assert a.expert == b.expert
            assert a.fine_class == b.fine_class
            np.testing.assert_array_equal(a.tokens, b.tokens)
            # shard ids demux through the placement plan; the unplaced
            # server falls back to one implicit shard per expert
            assert b.shard == plan.shard_of[reg_bank.names.index(b.expert)]
            assert a.shard == reg_ref.names.index(a.expert)


# -- unified core & async dispatch -------------------------------------------


def test_engines_are_shims_over_one_core(matcher):
    """ExpertEngine and BankedEngine must share EngineCore (no parallel
    residency/bucketing/harvest implementations kept aligned by test)."""
    from repro.serve import EngineCore
    _, reg = _registries(matcher)
    solo = reg[0].backend
    assert isinstance(solo.core, EngineCore)
    assert solo.core.n_experts == 1
    plan = plan_placement(reg)
    bank = plan.shards[0].bank
    assert isinstance(bank.core, EngineCore)
    assert bank.core.n_experts == 2
    assert type(solo.core) is type(bank.core)
    # neither shim re-implements the machinery: tick/harvest/poll resolve
    # to the one core
    for shim in (solo, bank):
        for meth in ("tick", "harvest", "poll"):
            assert hasattr(shim.core, meth)


def test_deferred_dispatch_keeps_tokens_on_device_until_harvest():
    """defer=True must enqueue only: emitted planes stay device buffers
    (no host block) until harvest() moves them in one batched transfer."""
    import jax as _jax
    eng = _engine(seed=15, max_len=32)
    rng = np.random.default_rng(1)
    eng.admit([1, 2], [rng.integers(0, 50, 5), rng.integers(0, 50, 4)],
              [1, 3], defer=True)
    assert eng.poll() == [] and eng.n_active == 1
    w = eng.core._active[0]
    assert isinstance(w.tok, _jax.Array)
    assert isinstance(w.emitted[0], _jax.Array) and w.n_host == 0
    assert eng.stats.host_blocks == 0
    eng.harvest()                      # one batched transfer
    assert eng.stats.host_blocks == 1
    assert dict(eng.poll())[1].shape == (1,)
    eng.tick(defer=True)
    eng.tick(defer=True)
    assert eng.stats.host_blocks == 1  # decode ticks never blocked
    assert all(isinstance(p, _jax.Array) for p in w.emitted[w.n_host:])
    eng.harvest()
    assert eng.stats.host_blocks == 2  # one transfer for both planes
    assert dict(eng.poll())[2].shape == (3,)
    assert eng.n_active == 0


def _scenario_rounds(scenario, names, bench, rng, n_req, uid0):
    """Per-round request batches emulating the bench's traffic mixes:
    uniform (spread over experts), skewed (80% on expert 0), bursty
    (everything in one burst, then idle rounds)."""
    reqs = []
    for k in range(n_req):
        if scenario == "skewed":
            e = 0 if rng.random() < 0.8 else int(rng.integers(
                1, len(names)))
        else:
            e = int(rng.integers(len(names)))
        n = names[e]
        x, _ = bench[n]["client_a"]
        reqs.append(Request(
            uid=uid0 + k, features=x[int(rng.integers(60))],
            prompt=rng.integers(0, 100, size=int(rng.integers(1, 40))),
            max_new_tokens=int(rng.integers(1, 7))))
    if scenario == "bursty":
        return [reqs, [], []]
    return [reqs[i:i + 3] for i in range(0, len(reqs), 3)]


def _run_rounds(srv, rounds, gen_at=None):
    """Drive submit/step round by round; optionally interleave a
    blocking generate() on expert 0's engine mid-stream."""
    got, gen_out = {}, None
    for k, batch in enumerate(rounds):
        if batch:
            srv.submit(batch)
        if gen_at is not None and k == gen_at:
            gen_out = srv.registry[0].backend.generate(
                (np.arange(6)[None, :] % 50).astype(np.int32), 4)
        for r in srv.step():
            got[r.uid] = r
    for r in srv.scheduler.drain():
        got[r.uid] = r
    return got, gen_out


def test_overlapped_token_identical_to_serial_on_scenarios(matcher, bench):
    """The overlapped executor must be token-identical to the serial
    reference on the bench's uniform/skewed/bursty traffic shapes
    (property grid over prompt lengths / max_new / expert mixes), with
    an interleaved generate() call mid-stream — while issuing strictly
    fewer host-blocking syncs."""
    m, names = matcher
    reg_s, reg_o = _registries(matcher)   # identical engine params
    srv_s = RoutedServer(m, reg_s, max_batch=4, executor="serial")
    srv_o = RoutedServer(m, reg_o, max_batch=4, executor="overlapped")
    assert srv_s.scheduler.executor.name == "serial"
    assert srv_o.scheduler.executor.name == "overlapped"
    blocks = lambda reg: sum(reg[e].backend.stats.host_blocks
                             for e in range(len(reg)))
    tokens = lambda reg: sum(reg[e].backend.stats.tokens_generated
                             for e in range(len(reg)))
    uid0 = 0
    for scenario in ("uniform", "skewed", "bursty"):
        rng = np.random.default_rng(0xB0 + uid0)
        rounds = _scenario_rounds(scenario, names, bench, rng, 9, uid0)
        uid0 += 9
        got_s, gen_s = _run_rounds(srv_s, rounds, gen_at=1)
        got_o, gen_o = _run_rounds(srv_o, rounds, gen_at=1)
        assert set(got_s) == set(got_o) and len(got_s) == 9, scenario
        for uid in got_s:
            a, b = got_s[uid], got_o[uid]
            assert a.expert == b.expert, (scenario, uid)
            assert a.fine_class == b.fine_class
            np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(gen_s, gen_o)
    assert tokens(reg_s) == tokens(reg_o)
    assert blocks(reg_o) < blocks(reg_s), \
        "overlapped must host-block strictly less than serial"


def test_overlapped_host_blocks_bounded_per_step(matcher, bench):
    """The acceptance invariant: with the overlapped executor a
    scheduler step blocks the host at most once per resident wave
    (waves active before the step + waves admitted by it)."""
    m, names = matcher
    _, reg = _registries(matcher)
    srv = RoutedServer(m, reg, max_batch=4, executor="overlapped")
    sched = srv.scheduler
    blocks = lambda: sum(reg[e].backend.stats.host_blocks
                         for e in range(len(reg)))
    active = lambda: sum(reg[e].backend.n_active
                         for e in range(len(reg)))
    rng = np.random.default_rng(0xC1)
    uid, steps = 0, 0
    while uid < 18 or sched.has_work:
        if uid < 18 and steps % 2 == 0:
            reqs = []
            for k in range(3):
                n = names[(uid + k) % 2]
                x, _ = bench[n]["client_a"]
                reqs.append(Request(
                    uid=uid + k, features=x[(uid + k) % 60],
                    prompt=rng.integers(0, 100,
                                        size=int(rng.integers(2, 30))),
                    max_new_tokens=int(rng.integers(1, 6))))
            uid += srv.submit(reqs)
        b0, a0, n0 = blocks(), active(), sched.stats.batches
        srv.step()
        admitted = sched.stats.batches - n0
        assert blocks() - b0 <= a0 + admitted, \
            (f"step {steps}: {blocks() - b0} host blocks for "
             f"{a0} resident + {admitted} admitted waves")
        steps += 1
    assert not sched._meta


# -- kernel vs reference parity --------------------------------------------


def test_coarse_kernel_parity_with_trained_bn_state(matcher, bench):
    """use_kernel=True must score with the real BatchNorm statistics:
    on a trained AE bank (non-trivial BN state) the Pallas path and the
    reference bank_scores must agree (regression for the dropped
    bank_states bug)."""
    m, names = matcher
    st = np.asarray(m.bank_states["mean"])
    assert np.abs(st).max() > 1e-3, "BN state is trivial; test is vacuous"
    x, _ = bench[names[0]]["client_a"]
    x = jnp.asarray(x[:64])
    from repro.core.matcher import ExpertMatcher
    km = ExpertMatcher(m.bank_params, m.bank_states, names, m.centroids,
                       m.centroid_mask, MatcherConfig(use_kernel=True))
    got = np.asarray(km.coarse_scores(x))
    want = np.asarray(bank_scores(m.bank_params, m.bank_states, x))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
    # and the routing decision is identical
    np.testing.assert_array_equal(np.asarray(km.assign_coarse(x)),
                                  np.asarray(m.assign_coarse(x)))


def test_fine_kernel_parity_with_reference(matcher, bench):
    """Router's grouped Pallas cosine path == matcher.assign_fine."""
    m, names = matcher
    router = Router(m, use_fine_kernel=True)
    ref_router = Router(m, use_fine_kernel=False)
    xs = np.concatenate([bench[n]["client_a"][0][:20] for n in names])
    got = router.route(xs)
    want = ref_router.route(xs)
    np.testing.assert_array_equal(got.coarse, want.coarse)
    np.testing.assert_array_equal(got.fine, want.fine)
    # cross-check against the matcher's own fine path
    direct = np.asarray(m.assign_fine(
        jnp.asarray(xs), jnp.asarray(got.coarse[:, 0])))
    np.testing.assert_array_equal(got.fine, direct)
