"""Differential verification suite for speculative decoding.

The engine's speculative path (``EngineCore._verify_fn`` + the draft
models in ``serve.draft``) claims *bitwise* token identity with plain
one-token-per-tick greedy decode: the target expert scores the whole
draft window in one parallel causal pass, accepts the matched greedy
prefix, and rolls the rejected suffix back out of the KV cache. This
suite is the proof:

  * an identity grid over kv layout (ring/paged), placement
    (per-engine/banked) and ``k`` in {1, 2, 4, 8}, asserting exact
    token equality against a plain reference engine — including the
    ``k=1`` degenerate ladder and mixed per-row ``max_new`` (rows
    freeze at their caps mid-wave);
  * the adversarial ``always-wrong`` draft: zero acceptance, yet every
    verify still advances each active row by exactly one (corrected)
    token, so the wave terminates in ``max(max_new) - 1`` verifies;
  * page accounting: a retired speculative wave returns the pool to
    baseline (modulo prefix-cache pins, which evict cleanly); the
    wrap/COW geometry is gate-blocked onto the plain decode path and
    stays token-identical; a ``PagePoolExhausted`` admission rolls
    back transactionally;
  * executable budgets: ``executable_bounds()`` grows exactly one
    ``verify`` family, post-warmup compile counts are asserted exactly,
    and the L006 lint extension blesses only bucket-derived
    ``_verify_fn`` shape arguments.

Property-style grids sample through ``tests/_prop.py`` (see its module
docstring): the container has no ``hypothesis``, so grids are fixed
and seeded — fully deterministic under CI.
"""
import textwrap

import jax
import numpy as np
import pytest

from repro.analysis import lint
from repro.configs import get_config
from repro.models import build_model
from repro.serve import (BankedEngine, ExpertEngine, PagePoolExhausted)

MAX_LEN = 32


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("smollm-135m").reduced(name="spec-diff")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(7))


def _mk_engine(tiny, **kw):
    model, params = tiny
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("min_len_bucket", 8)
    kw.setdefault("batch_buckets", (1, 2, 4))
    return ExpertEngine(model, params, **kw)


def _wave_a():
    """3 rows (pads to Bb=4), prompts <= 8 (Sb=8), mixed per-row caps.
    Gate: 8 + 6 + k <= 32 for every k <= 8 — all grid cells speculate."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 100, size=n).astype(np.int32)
               for n in (5, 8, 6)]
    return prompts, [6, 4, 7]


def _run(engine, prompts, max_new, uid0=0):
    """Admit one wave on an ExpertEngine and drain it to {uid: tokens}."""
    uids = list(range(uid0, uid0 + len(prompts)))
    engine.admit(uids, list(prompts), list(max_new))
    out = {}
    while engine.has_pending:
        engine.tick()
        for uid, seq in engine.poll():
            out[uid] = seq
    return out


def _run_banked(engine, groups):
    engine.admit(groups)
    out = {}
    while engine.has_pending:
        engine.tick()
        for local, uid, seq in engine.poll():
            out[(local, uid)] = seq
    return out


@pytest.fixture(scope="module")
def plain_engine(tiny):
    """The one-token-per-tick reference every grid cell diffs against."""
    return _mk_engine(tiny)


@pytest.fixture(scope="module")
def ref_tokens(plain_engine):
    prompts, max_new = _wave_a()
    return _run(plain_engine, prompts, max_new)


# -- identity grid -----------------------------------------------------------


@pytest.mark.parametrize("kv,k", [
    ("ring", 1), ("ring", 2), ("ring", 4), ("ring", 8),
    ("paged", 2), ("paged", 4),
])
def test_speculative_identity_per_engine(tiny, ref_tokens, kv, k):
    """Every (layout, k) cell emits bitwise the reference tokens —
    including k=1, the degenerate one-draft ladder."""
    eng = _mk_engine(tiny, kv_layout=kv, speculate_k=k, draft="table")
    prompts, max_new = _wave_a()
    got = _run(eng, prompts, max_new)
    for uid, seq in ref_tokens.items():
        np.testing.assert_array_equal(got[uid], seq)
    assert eng.stats.verify_steps > 0
    assert eng.stats.spec_fallback_waves == 0
    assert eng.stats.decode_steps == eng.stats.verify_steps


def test_speculative_identity_across_waves(tiny, plain_engine):
    """An online draft keeps learning across waves; identity must hold
    on every wave shape it meets (Bb=2 then Bb=1, fresh length mix)."""
    spec = _mk_engine(tiny, speculate_k=2, draft="table")
    rng = np.random.default_rng(23)
    for uid0, caps in ((0, [5, 5]), (10, [6])):
        prompts = [rng.integers(0, 100,
                                size=int(rng.integers(3, 9))).astype(np.int32)
                   for _ in caps]
        want = _run(plain_engine, prompts, caps, uid0=uid0)
        got = _run(spec, prompts, caps, uid0=uid0)
        for uid, seq in want.items():
            np.testing.assert_array_equal(got[uid], seq)
    assert spec.stats.verify_steps > 0


@pytest.fixture(scope="module")
def banked_params(tiny):
    model, params = tiny
    return [params, model.init(jax.random.PRNGKey(8))]


def _banked_waves():
    rng = np.random.default_rng(3)
    g = lambda ns: [rng.integers(0, 100, size=n).astype(np.int32)
                    for n in ns]
    return {0: ([0, 1, 2], g((5, 8, 6)), [6, 4, 7]),
            1: ([3, 4], g((7, 4)), [5, 6])}


@pytest.fixture(scope="module")
def banked_ref(tiny, banked_params):
    model, _ = tiny
    eng = BankedEngine(model, banked_params, max_len=MAX_LEN,
                       min_len_bucket=8, batch_buckets=(1, 2, 4))
    return _run_banked(eng, _banked_waves())


@pytest.mark.parametrize("kv,k", [("ring", 2), ("paged", 4)])
def test_speculative_identity_banked(tiny, banked_params, banked_ref,
                                     kv, k):
    """Banked (E=2) speculation: one verify dispatch serves both
    experts' micro-batches and each expert's rows match its own plain
    reference. Uses the static MLP draft so all three draft models are
    exercised somewhere in the grid."""
    model, _ = tiny
    eng = BankedEngine(model, banked_params, max_len=MAX_LEN,
                       min_len_bucket=8, batch_buckets=(1, 2, 4),
                       kv_layout=kv, speculate_k=k, draft="mlp")
    got = _run_banked(eng, _banked_waves())
    for key, seq in banked_ref.items():
        np.testing.assert_array_equal(got[key], seq)
    assert eng.stats.verify_steps > 0
    assert eng.stats.spec_fallback_waves == 0


# -- adversarial draft: progress guarantee -----------------------------------


def test_always_wrong_draft_progress_guarantee(tiny, ref_tokens):
    """A draft that never matches accepts nothing — yet each verify
    emits the corrected greedy token, so rows advance exactly one per
    verify and the wave needs exactly max(max_new) - 1 verifies (the
    first token comes from prefill)."""
    eng = _mk_engine(tiny, speculate_k=2, draft="always-wrong")
    prompts, max_new = _wave_a()
    got = _run(eng, prompts, max_new)
    for uid, seq in ref_tokens.items():
        np.testing.assert_array_equal(got[uid], seq)
    st = eng.stats
    assert st.tokens_accepted == 0
    assert st.acceptance_rate == 0.0
    assert st.tokens_drafted > 0
    assert st.verify_steps == max(max_new) - 1


# -- page accounting ---------------------------------------------------------


def _evict_all(core):
    for e in range(core.pool.n_experts):
        core.prefix_cache.evict_for(e, core.pool.n_pages)


def test_spec_wave_pages_return_to_baseline(tiny):
    """After a speculative wave retires, the only live pool references
    belong to the prefix cache (registered prompt pages); evicting them
    restores the exact pre-admission counters. Optimistically-written
    then rejected suffix slots never show up as leaked pages — they
    live inside wave-owned decode pages released at retire."""
    eng = _mk_engine(tiny, kv_layout="paged", page_size=8,
                     speculate_k=2, draft="table")
    pool = eng.core.pool
    base = dict(pool.counters())
    prompts, max_new = _wave_a()
    _run(eng, prompts, max_new)
    assert eng.core.n_active == 0
    cache_pins = sum(1 for key in eng.core.prefix_cache._lru
                     if key[0] == "pg")
    assert pool.counters()["used"] == cache_pins
    _evict_all(eng.core)
    assert pool.counters() == base
    pool.check()


def test_spec_wrap_cow_wave_falls_back_identically(tiny):
    """The wrap geometry (decode overwrites prompt pages mid-page,
    COW-remapping shared ones) is exactly what the no-wrap gate keeps
    away from the verify path: the wave must fall back to plain decode,
    stay token-identical, and still settle its pages."""
    model, params = tiny
    mk = dict(max_len=16, min_len_bucket=8, batch_buckets=(1, 2))
    spec = ExpertEngine(model, params, kv_layout="paged", page_size=8,
                        speculate_k=4, draft="table", **mk)
    plain = ExpertEngine(model, params, **mk)
    p = np.random.default_rng(5).integers(0, 100, size=8).astype(np.int32)
    prompts, max_new = [p, p.copy()], [10, 10]   # Sb+steps = 17 > C=16
    want = _run(plain, prompts, max_new)
    base = dict(spec.core.pool.counters())
    got = _run(spec, prompts, max_new)
    for uid, seq in want.items():
        np.testing.assert_array_equal(got[uid], seq)
    st = spec.stats
    assert st.spec_fallback_waves == 1
    assert st.verify_steps == 0          # gate-blocked: no verify ran
    assert st.pages_copied > 0           # the dup row COW'd its wrap page
    # wrapping waves never register prefixes, so baseline needs no evict
    assert spec.core.pool.counters() == base
    spec.core.pool.check()


def test_spec_admission_pool_exhausted_rolls_back(tiny):
    """An admission that outgrows the pool raises PagePoolExhausted with
    *zero* net page movement — the transactional ledger unwinds every
    reference the partial plan took — and the identical admission
    succeeds once the resident wave retires."""
    eng = _mk_engine(tiny, kv_layout="paged", page_size=8, pool_pages=8,
                     speculate_k=2, draft="table")
    pool = eng.core.pool
    rng = np.random.default_rng(9)
    caps = [6, 4, 7]
    mk_prompts = lambda lo: [rng.integers(lo, lo + 90,
                                          size=n).astype(np.int32)
                             for n in (5, 8, 6)]
    prompts1, prompts2 = mk_prompts(0), mk_prompts(100)
    eng.admit([0, 1, 2], prompts1, caps)    # resident: 6 of 8 pages
    before = dict(pool.counters())
    with pytest.raises(PagePoolExhausted):
        eng.admit([10, 11, 12], prompts2, caps)
    assert pool.counters() == before
    pool.check()
    while eng.has_pending:                   # retire wave 1
        eng.tick()
        eng.poll()
    _evict_all(eng.core)
    got = _run(eng, prompts2, caps, uid0=10)
    assert sorted(got) == [10, 11, 12]
    assert all(len(got[10 + i]) == caps[i] for i in range(3))


# -- executable budgets ------------------------------------------------------


def test_executable_bounds_verify_family(tiny):
    spec = _mk_engine(tiny, speculate_k=2, draft="table")
    bounds = spec.core.executable_bounds()
    assert bounds["verify"] == len(spec.batch_buckets)
    plain = _mk_engine(tiny)
    assert plain.core.executable_bounds()["verify"] == 0


def test_spec_compile_counts_exact(tiny):
    """Exact post-warmup executable census: a speculative wave mints
    one prefill and one verify executable — no decode — and repeat
    traffic at the same shape mints nothing. A gate-blocked wave then
    mints exactly the fallback decode executable."""
    eng = _mk_engine(tiny, speculate_k=2, draft="table")
    prompts, max_new = _wave_a()
    _run(eng, prompts, max_new)
    st = eng.stats
    assert (st.prefill_compiles, st.decode_compiles,
            st.verify_compiles) == (1, 0, 1)
    assert st.jit_cache_entries == 2
    _run(eng, [p + 1 for p in prompts], max_new, uid0=50)
    assert st.jit_cache_entries == 2
    # steps = 31: 8 + 31 + 2 > 32 trips the no-wrap gate -> plain decode
    _run(eng, prompts, [MAX_LEN] * 3, uid0=90)
    assert st.spec_fallback_waves == 1
    assert (st.decode_compiles, st.verify_compiles) == (1, 1)
    assert st.jit_cache_entries == 3


def test_lint_blesses_only_bucket_derived_verify_shapes():
    """L006 extension: ``_verify_fn``'s shape argument must be the
    engine-fixed ``speculate_k`` (or another bucket-ladder value); a k
    read off per-request data keys unbounded executables."""
    blessed = textwrap.dedent("""
        def tick(self, w, Bb):
            out = self._verify_fn(Bb, self.speculate_k)(self.params, w)
            return out
    """)
    assert not [v for v in lint.lint_source(
        blessed, "src/repro/serve/planted.py") if v.rule == "L006"]
    planted = textwrap.dedent("""
        def tick(self, w, req):
            k = req.draft_tokens.shape[0]
            out = self._verify_fn(4, k)(self.params, w)
            return out
    """)
    vs = lint.lint_source(planted, "src/repro/serve/planted.py")
    assert any(v.rule == "L006" for v in vs), vs
