"""Chunked suffix prefill + prefill/decode disaggregation tests:
token identity of the chunk ladder against ring and monolithic paged
serving on the traffic grids, whale/short interleaving under the
per-step prefill token budget, partial-prefix suffix savings strictly
below the storage-only baseline, exhaustion backpressure that never
disturbs a partially-chunked resident wave, exact executable-count
bounds for the chunk ladder, and config validation."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ExpertRegistry, build_matcher, train_bank
from repro.data import load_benchmark
from repro.models import build_model
from repro.serve import (ExpertEngine, PagePoolExhausted, Request,
                         RoutedServer)
from repro.serve.core import EngineCore


# -- config validation ------------------------------------------------------


def test_chunk_len_validation_errors():
    cfg = get_config("smollm-135m").reduced(name="chunk-val")
    model = build_model(cfg)
    with pytest.raises(ValueError, match="requires kv_layout='paged'"):
        ExpertEngine(model, None, max_len=64, kv_layout="ring",
                     chunk_len=16)
    with pytest.raises(ValueError, match="multiple of page_size"):
        ExpertEngine(model, None, max_len=64, kv_layout="paged",
                     chunk_len=12)
    with pytest.raises(ValueError, match="multiple of chunk_len"):
        ExpertEngine(model, None, max_len=64, kv_layout="paged",
                     chunk_len=40)
    with pytest.raises(ValueError, match="itself be a length bucket"):
        ExpertEngine(model, None, max_len=96, kv_layout="paged",
                     chunk_len=24)
    # a length bucket above chunk_len that is not a chunk multiple
    # cannot tile into whole chunks
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="multiples of chunk_len"):
        EngineCore(model, [params], max_len=48,
                   len_buckets=(16, 24, 48), kv_layout="paged",
                   chunk_len=16)


# -- fixtures ---------------------------------------------------------------


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
    cfg = get_config("smollm-135m").reduced(name="chunk-t")
    model = build_model(cfg)
    params = [model.init(jax.random.PRNGKey(s)) for s in (0, 1)]
    return model, params


def _server(matcher, shared_model, kv, chunk_len=None, budget=0, **kw):
    m, names = matcher
    model, params = shared_model
    reg = ExpertRegistry()
    for n, p in zip(names, params):
        reg.add(n, ExpertEngine(model, p, max_len=64, kv_layout=kv,
                                chunk_len=chunk_len, **kw))
    return RoutedServer(m, reg, max_batch=4,
                        prefill_tokens_per_step=budget), reg


# -- token identity ---------------------------------------------------------


def test_chunked_token_identical_on_traffic_grids(matcher, bench,
                                                  shared_model):
    """The acceptance criterion: chunked suffix prefill (whale prompts
    split into chunk_len dispatches, interleaved with decode under a
    16-token/step budget) must be token-identical to the ring path on
    uniform / skewed / bursty traffic with mixed prompt lengths."""
    srv_r, _ = _server(matcher, shared_model, "ring")
    srv_c, reg_c = _server(matcher, shared_model, "paged",
                           chunk_len=16, budget=16)
    m, names = matcher
    uid0 = 0
    for scenario in ("uniform", "skewed", "bursty"):
        rng = np.random.default_rng(0xC0 + uid0)
        reqs = []
        for k in range(9):
            if scenario == "skewed":
                e = 0 if rng.random() < 0.8 else 1
            else:
                e = int(rng.integers(2))
            x, _ = bench[names[e]]["client_a"]
            reqs.append(Request(
                uid=uid0 + k, features=x[(uid0 + k) % 60],
                prompt=rng.integers(0, 100, size=int(rng.integers(1, 61))),
                max_new_tokens=int(rng.integers(1, 7))))
        uid0 += 9
        if scenario == "bursty":
            got_r = srv_r.serve(reqs)
            got_c = srv_c.serve(reqs)
        else:
            got_r, got_c = [], []
            for lo in range(0, len(reqs), 3):
                got_r += srv_r.serve(reqs[lo:lo + 3])
                got_c += srv_c.serve(reqs[lo:lo + 3])
        for a, b in zip(got_r, got_c):
            assert a.uid == b.uid and a.expert == b.expert, scenario
            np.testing.assert_array_equal(a.tokens, b.tokens,
                                          err_msg=f"{scenario}/{a.uid}")
        for e in range(2):
            reg_c[e].backend.core.pool.check()
    # whales actually went through the ladder (suffix executables live)
    assert sum(reg_c[e].backend.stats.suffix_compiles
               for e in range(2)) > 0


def test_whale_prefill_interleaves_with_decode(shared_model):
    """Disaggregation: while a whale's chunks are still pending under a
    one-chunk budget, a co-resident short wave must keep decoding (the
    whale wave is not decode-eligible until its last chunk lands), and
    every row must match the ring reference."""
    model, params = shared_model
    eng = ExpertEngine(model, params[0], max_len=64, kv_layout="paged",
                       chunk_len=16)
    ref = ExpertEngine(model, params[0], max_len=64, kv_layout="ring")
    rng = np.random.default_rng(21)
    shorts = [rng.integers(0, 100, size=10) for _ in range(2)]
    whale = rng.integers(0, 100, size=60)      # Sb = 64 -> 4 chunks
    eng.admit([0, 1], shorts, [8, 8], defer=True)
    eng.core.prefill_step(0)                   # shorts: Sb=16, one chunk
    assert not eng.core.has_pending_chunks
    eng.admit([9], [whale], [4], defer=True)
    assert eng.core.has_pending_chunks
    overlap = 0
    while eng.core.has_pending_chunks:
        advanced = eng.tick(defer=True)        # whale wave is gated out
        overlap += advanced
        eng.core.prefill_step(budget=1)        # exactly one chunk/step
        eng.harvest()
    assert overlap >= 2, "short wave never decoded while whale prefilled"
    while eng.n_active:
        eng.tick(defer=True)
        eng.harvest()
    got = dict(eng.poll())
    ref.admit([0, 1], shorts, [8, 8])
    ref.admit([9], [whale], [4])
    while ref.n_active:
        ref.tick()
    want = dict(ref.poll())
    assert set(got) == {0, 1, 9}
    for u in got:
        np.testing.assert_array_equal(got[u], want[u], err_msg=str(u))
    eng.core.pool.check()


def test_partial_prefix_suffix_savings_beats_storage_only(shared_model):
    """A cohort whale sharing a cached 32-token head must compute
    strictly fewer prefill tokens through the chunk ladder (head chunks
    are skipped, only the uncached suffix runs) than the storage-only
    paged baseline, which adopts the pages but recomputes every row in
    full — token-identically to ring."""
    model, params = shared_model
    # max_len=128 headroom: Sb=64 whales never wrap, so the head pages
    # survive in the prefix cache for the second whale to adopt
    mk = lambda cl: ExpertEngine(model, params[0], max_len=128,
                                 kv_layout="paged", chunk_len=cl)
    chunked, storage = mk(32), mk(None)
    ring = ExpertEngine(model, params[0], max_len=128, kv_layout="ring")
    rng = np.random.default_rng(33)
    head = rng.integers(0, 100, size=32)
    whales = [np.concatenate([head, rng.integers(0, 100, size=24)])
              for _ in range(2)]
    got = {}
    for name, eng in (("chunked", chunked), ("storage", storage),
                      ("ring", ring)):
        toks = {}
        for uid, w in enumerate(whales):   # sequential: cache populates
            eng.admit([uid], [w], [4])
            while eng.n_active:
                eng.tick()
            toks.update(dict(eng.poll()))
        got[name] = toks
    for u in (0, 1):
        np.testing.assert_array_equal(got["chunked"][u], got["ring"][u])
        np.testing.assert_array_equal(got["storage"][u], got["ring"][u])
    # whale 2: chunked computes only the 32-token suffix chunk; the
    # storage-only engine re-runs the full 64-token bucket
    assert chunked.stats.prefill_tokens_computed < \
        storage.stats.prefill_tokens_computed, \
        (chunked.stats, storage.stats)
    assert chunked.stats.prefix_pages_shared > 0
    chunked.core.pool.check()


# -- exhaustion while a wave is mid-chunk -----------------------------------


def test_exhaustion_preserves_partially_chunked_wave(shared_model):
    """Regression (the requeue-at-front fix): an admission that exhausts
    the pool while a resident wave still has pending prefill chunks
    must roll back without touching the partial wave's already-written
    pages — the wave finishes its remaining chunks and decodes to
    ring-identical tokens, and the retried admission then succeeds."""
    model, params = shared_model
    # Sb=64 whale: 8 prompt pages + 1 decode page = 9; a 12-page pool
    # hosts one whale but not two
    eng = ExpertEngine(model, params[0], max_len=128, kv_layout="paged",
                       chunk_len=32, pool_pages=12)
    ref = ExpertEngine(model, params[0], max_len=128, kv_layout="ring")
    rng = np.random.default_rng(44)
    w1 = rng.integers(0, 100, size=60)
    w2 = rng.integers(0, 100, size=60)
    eng.admit([0], [w1], [4], defer=True)
    assert eng.core.has_pending_chunks
    eng.core.prefill_step(budget=1)            # dispatch chunk 0 only
    assert eng.core.has_pending_chunks, "whale already fully prefilled"
    used = eng.core.pool.used_count(0)
    c = eng.core.pool.counters()
    assert c["used"] == used and c["free"] + c["used"] == 12, c
    with pytest.raises(PagePoolExhausted):
        eng.admit([1], [w2], [4], defer=True)
    # transactional: the partial wave's pages are exactly as they were
    assert eng.core.pool.used_count(0) == used
    assert eng.core.pool.counters() == c, "rollback moved the books"
    assert eng.core.has_pending_chunks and eng.n_active == 1
    eng.core.pool.check()
    eng.core.prefill_step(0)                   # finish the whale's chunks
    while eng.n_active:
        eng.tick(defer=True)
        eng.harvest()
    got = dict(eng.poll())
    eng.admit([1], [w2], [4])                  # pool has room again
    while eng.n_active:
        eng.tick()
    got.update(dict(eng.poll()))
    for uid, w in ((0, w1), (1, w2)):
        ref.admit([uid], [w], [4])
        while ref.n_active:
            ref.tick()
    want = dict(ref.poll())
    for u in (0, 1):
        np.testing.assert_array_equal(got[u], want[u], err_msg=str(u))
    eng.core.pool.check()


def test_chunked_pool_exhaustion_requeues_cleanly(matcher, bench,
                                                  shared_model):
    """Scheduler-level: whale traffic against a one-wave pool forces
    requeues while earlier waves are still chunk-pending/decoding; the
    chunked server must stall (never corrupt resident pages) and stay
    ring-identical."""
    srv_r, _ = _server(matcher, shared_model, "ring")
    srv_c, reg_c = _server(matcher, shared_model, "paged",
                           chunk_len=16, budget=16, pool_pages=40)
    m, names = matcher
    rng = np.random.default_rng(55)
    reqs = []
    for uid in range(16):
        nm = names[uid % 2]
        x, _ = bench[nm]["client_a"]
        reqs.append(Request(
            uid=uid, features=x[uid % 60],
            prompt=rng.integers(0, 100, size=int(rng.integers(33, 48))),
            max_new_tokens=int(rng.integers(2, 7))))
    got_r = srv_r.serve(reqs)
    got_c = srv_c.serve(reqs)
    for a, b in zip(got_r, got_c):
        np.testing.assert_array_equal(a.tokens, b.tokens,
                                      err_msg=str(a.uid))
    assert srv_c.scheduler.stats.kv_stalls >= 1, \
        "tiny pool never stalled — test is vacuous"
    for e in range(2):
        reg_c[e].backend.core.pool.check()


# -- bounded executables ----------------------------------------------------


def test_chunked_executable_bounds_exact(shared_model):
    """Driving the full (batch, length) ladder must mint exactly the
    executables ``executable_bounds`` predicts — monolithic prefills
    only up to chunk_len, one suffix executable per (batch bucket,
    chunk index) — and re-running the same traffic must mint none."""
    model, params = shared_model
    eng = ExpertEngine(model, params[0], max_len=64, kv_layout="paged",
                       batch_buckets=(1, 2), chunk_len=16)
    bounds = eng.core.executable_bounds()
    assert bounds == {"prefill": 4, "suffix": 6, "decode": 2,
                      "verify": 0}
    rng = np.random.default_rng(66)

    def drive():
        uid = [0]
        for nb in (1, 2):
            for sb in (8, 16, 32, 64):
                prompts = [rng.integers(0, 100, size=sb)
                           for _ in range(nb)]
                eng.admit(list(range(uid[0], uid[0] + nb)), prompts,
                          [2] * nb)
                uid[0] += nb
                while eng.n_active:
                    eng.tick()
                eng.poll()

    drive()
    st = eng.stats
    assert st.prefill_compiles == bounds["prefill"], st
    assert st.suffix_compiles == bounds["suffix"], st
    assert st.decode_compiles == bounds["decode"], st
    entries = st.jit_cache_entries
    assert entries <= sum(bounds.values())
    drive()                     # steady state: zero recompiles
    assert eng.stats.jit_cache_entries == entries
    eng.core.pool.check()


# -- banked 8-device mesh ---------------------------------------------------


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import jax
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_expert_mesh
from repro.models import build_model
from repro.serve import BankedEngine
from repro.serve.placement import _bank_submesh

assert len(jax.devices()) == 8, jax.devices()
cfg = get_config("smollm-135m").reduced(name="chunk-mesh")
model = build_model(cfg)
params = [model.init(jax.random.PRNGKey(i)) for i in range(2)]
rng = np.random.default_rng(0)
# whales and shorts: the whale rows run the suffix ladder on the mesh
groups = {0: ([0, 1], [rng.integers(0, 50, 60), rng.integers(0, 50, 9)],
              [4, 6]),
          1: ([2], [rng.integers(0, 50, 40)], [5])}

def run(mesh, chunk):
    bank = BankedEngine(model, params, max_len=64, kv_layout="paged",
                        chunk_len=16 if chunk else None, mesh=mesh)
    bank.admit(groups, defer=True)
    while bank.core.has_pending_chunks:
        bank.core.prefill_step(16)
        bank.tick(defer=True)
        bank.harvest()
    while bank.n_active:
        bank.tick(defer=True)
        bank.harvest()
    suffix = bank.stats.suffix_compiles
    return {f"{l}/{u}": t.tolist() for l, u, t in bank.poll()}, suffix

mesh = make_expert_mesh()
sub, devs = _bank_submesh(2, mesh)
assert sub is not None and dict(sub.shape) == {"expert": 2}, sub
sharded, suffix_sharded = run(sub, True)
single, _ = run(None, False)
print(json.dumps({
    "n_devices": len(jax.devices()), "bank_devices": len(devs),
    "suffix_sharded": suffix_sharded,
    "match": sharded == single}))
"""


@pytest.mark.slow
def test_chunked_banked_mesh_matches_monolithic_single_device():
    """A 2-expert paged bank sharded over a mesh expert axis, serving
    whales through the chunk ladder, must emit the same tokens as the
    unsharded monolithic-prefill bank (GSPMD numerics for the suffix
    executables' bank sharding)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT], capture_output=True,
        text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_devices"] == 8 and res["bank_devices"] == 2, res
    assert res["suffix_sharded"] > 0, res
    assert res["match"], res
