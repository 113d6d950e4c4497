"""Observability subsystem tests (``repro.obs`` + the O-rule gate).

The tentpole claims, each proven here against real serving traffic:

  * **propagation** — a trace id minted at ``Scheduler.submit`` follows
    the request through the hub lifecycle (park -> stage -> commit) and
    the engine's device spans all the way to ``request.finish``;
  * **span balance** — every ``begin_device`` handle is closed by the
    time traffic drains, including across the two rollback paths
    (``PagePoolExhausted`` requeue, speculative no-wrap fallback);
  * **zero new host blocks** — ``EngineStats.host_blocks`` is identical
    with tracing on and off, because device spans only ever close
    inside the engine's *existing* sync points;
  * **snapshot stability** — ``obs.snapshot()`` exposes one stable tree
    (scheduler / engines / kv / hub / executor) whose keys downstream
    dashboards may rely on;
  * **program spans** — a traced ``RoutedServer`` records ``step`` with
    its ``engine.enqueue``/``engine.sync`` children and ``route`` (with
    ``ahead``) with its ``route.wait`` children, linked by ``parent``,
    and puts every span on the profiler's host plane;
  * **the static gate** — planted O001/O002/O003 violations are caught,
    and the compliant idioms pass (mirrors tests/test_analysis.py).
"""
import glob
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.analysis import obs_lint
from repro.core import ExpertRegistry
from repro.configs import get_config
from repro.core import build_matcher, train_bank
from repro.data import load_benchmark
from repro.models import build_model
from repro.obs import (Counter, DEFAULT_MS_BUCKETS, Gauge, Histogram,
                       MetricsRegistry, NULL_TRACER, Tracer)
from repro.serve import (ExpertEngine, ExpertHub, Request, RoutedServer,
                         Scheduler, SchedulerConfig, SchedulerStats)


@pytest.fixture(scope="module")
def model():
    cfg = get_config("smollm-135m").reduced(name="obs-t")
    return build_model(cfg)


@pytest.fixture(scope="module")
def params2(model):
    return [model.init(jax.random.PRNGKey(s)) for s in range(2)]


@pytest.fixture(scope="module")
def routed(model, params2):
    """A matcher over two synthetic datasets (routing accuracy does not
    matter here, so it trains one epoch) and client fingerprints."""
    data = load_benchmark(names=["mnist", "har"], n_per_dataset=100,
                          seed=0)
    names = list(data)
    aes, _ = train_bank([(n, data[n]["server"][0]) for n in names],
                        epochs=1, batch_size=64)
    m = build_matcher(aes, names, [data[n]["server"] for n in names])
    feats = np.concatenate([data[n]["client_a"][0][:8] for n in names])
    return m, names, feats


def _routed_server(model, params2, routed, tracer):
    m, names, _ = routed
    reg = ExpertRegistry()
    for n, p in zip(names, params2):
        reg.add(n, ExpertEngine(model, p, max_len=32))
    return RoutedServer(m, reg, max_batch=4, tracer=tracer)


def _routed_reqs(feats, lo, n, max_new=4):
    rng = np.random.default_rng(lo)
    return [Request(uid=lo + i, features=feats[(lo + i) % len(feats)],
                    prompt=rng.integers(0, 50, size=int(rng.integers(3, 20))),
                    max_new_tokens=max_new)
            for i in range(n)]


def _reqs(rng, n, n_experts, lo=3, hi=28, max_new=(1, 5)):
    return [Request(uid=u, features=np.zeros(784, np.float32),
                    prompt=rng.integers(0, 50,
                                        size=int(rng.integers(lo, hi))),
                    max_new_tokens=int(rng.integers(*max_new)),
                    expert=int(u % n_experts))
            for u in range(n)]


def _by(recs, name):
    return [r for r in recs if r["name"] == name]


# -- metrics primitives ------------------------------------------------------


def test_metric_primitives_and_registry_tree():
    c, g = Counter(), Gauge()
    c.inc()
    c.inc(4)
    g.set(2.5)
    h = Histogram()
    assert h.snapshot()["p99"] == 0.0          # empty histogram is sane
    for v in (0.2, 0.2, 3.0, 40.0, 4000.0):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 5 and s["max"] == 4000.0
    assert s["p50"] <= s["p95"] <= s["p99"] <= 5000.0
    assert abs(s["mean"] - s["sum"] / 5) < 1e-9
    # percentiles are upper bounds from the literal bucket ladder
    assert s["p50"] in DEFAULT_MS_BUCKETS
    with pytest.raises(ValueError):
        Histogram(buckets=())
    with pytest.raises(ValueError):
        Histogram(buckets=(2.0, 1.0))

    obs = MetricsRegistry()
    obs.register("scheduler", lambda: {"submitted": c.value})
    obs.register("scheduler/latency/queue_ms", h)
    obs.register("engines/shard0", {"ticks": g})
    snap = obs.snapshot()
    assert snap["scheduler"]["submitted"] == 5
    assert snap["scheduler"]["latency"]["queue_ms"]["count"] == 5
    assert snap["engines"]["shard0"]["ticks"] == 2.5
    # re-registration replaces, not duplicates
    obs.register("engines/shard0", {"ticks": 7})
    assert obs.snapshot()["engines"]["shard0"]["ticks"] == 7


def test_null_tracer_spans_still_measure():
    """Disabled tracing must not starve stats consumers: the span's
    ``.ms`` is measured either way; only recording toggles."""
    with NULL_TRACER.span("hub.stage") as sp:
        x = sum(range(1000))
    assert x and sp.ms >= 0.0
    assert NULL_TRACER.begin_device("wave.prefill") is None
    NULL_TRACER.end_device(None)               # no-op by contract
    assert NULL_TRACER.records() == []


# -- propagation: park -> stage -> commit -> serve ---------------------------


def test_trace_id_propagates_through_hub_lifecycle(tmp_path, model,
                                                   params2):
    """One trace id per request, minted at submit, visible in the hub's
    park/stage/commit records, the engine's device spans and the finish
    event — the full cold-start chain of the acceptance criterion."""
    store = str(tmp_path / "store")
    hub = ExpertHub(model, n_slots=1, max_len=32, store=store)
    for i, p in enumerate(params2):
        hub.add_expert(f"ex{i}", p, cold=True)
    tracer = Tracer()
    srv = RoutedServer(None, hub.build_registry(), max_batch=4, hub=hub,
                       tracer=tracer)
    rng = np.random.default_rng(3)
    reqs = _reqs(rng, 6, n_experts=2)
    resps = srv.serve(reqs)
    assert len(resps) == 6
    assert srv.scheduler.stats.resident_stalls >= 1   # cold start parked

    recs = tracer.records()
    submits = _by(recs, "request.submit")
    trace_of = {r["args"]["uid"]: r["args"]["trace"] for r in submits}
    assert sorted(trace_of) == list(range(6))
    assert len(set(trace_of.values())) == 6 and 0 not in trace_of.values()

    parked = {t for r in _by(recs, "hub.park") for t in r["args"]["traces"]}
    assert parked and parked <= set(trace_of.values())
    assert _by(recs, "hub.stage"), "cold staging left no stage span"
    assert all(r["ph"] == "X" and r["dur"] > 0
               for r in _by(recs, "hub.stage"))
    commits = _by(recs, "hub.commit")
    assert commits and all(r["cat"] == "enqueue" for r in commits)

    waved = {t for r in _by(recs, "wave.prefill")
             for t in r["args"]["traces"]}
    finishes = _by(recs, "request.finish")
    assert {r["args"]["uid"] for r in finishes} == set(range(6))
    for r in finishes:
        a = r["args"]
        assert a["trace"] == trace_of[a["uid"]]
        assert a["total_ms"] >= a["queue_ms"] >= 0.0
        assert a["stalled_ms"] >= 0.0
    # at least one parked request completed the whole chain:
    # submit -> park -> (stage/commit happened) -> prefill -> finish
    assert parked & waved
    # stalled time was actually attributed to the parked rows
    stalled = {a["uid"]: a["stalled_ms"]
               for a in (r["args"] for r in finishes)}
    assert any(stalled[u] > 0.0 for u in stalled)

    assert tracer.open_device_count() == 0
    # the snapshot tree surfaces the hub's per-expert lifecycle metrics
    snap = srv.snapshot()
    ex = snap["hub"]["experts"]
    assert set(ex) == {"ex0", "ex1"}
    for row in ex.values():
        assert {"hits", "state", "pins", "misses", "stage_ms",
                "commit_ms", "resident_s"} <= set(row)
    assert any(row["stage_ms"] > 0 for row in ex.values())
    # scheduler latency histograms observed every finished request
    assert snap["scheduler"]["latency"]["queue_ms"]["count"] == 6


# -- span balance under the rollback paths -----------------------------------


def test_span_balance_under_pool_exhaustion(model, params2):
    """``PagePoolExhausted`` requeues must not leak device spans: the
    span only opens after admission succeeds, so the rollback path is
    balanced by construction — and the requeue leaves a ``kv.requeue``
    breadcrumb carrying the stalled rows' trace ids."""
    reg = ExpertRegistry()
    reg.add("ex0", ExpertEngine(model, params2[0], max_len=64,
                                kv_layout="paged", pool_pages=40))
    tracer = Tracer()
    sched = Scheduler(None, reg, config=SchedulerConfig(max_batch=4),
                      tracer=tracer)
    rng = np.random.default_rng(11)
    # 4-row waves of 33-48 token prompts own ~24 of 40 pages: wave two
    # cannot admit while wave one is resident -> the stall path fires
    reqs = [Request(uid=u, features=np.zeros(784, np.float32),
                    prompt=rng.integers(0, 100,
                                        size=int(rng.integers(33, 48))),
                    max_new_tokens=int(rng.integers(2, 7)), expert=0)
            for u in range(12)]
    sched.submit(reqs)
    out = sched.drain()
    assert len(out) == 12
    assert sched.stats.kv_stalls >= 1, \
        "tiny pool never stalled — test is vacuous"
    recs = tracer.records()
    requeues = _by(recs, "kv.requeue")
    assert requeues
    submit_traces = {r["args"]["trace"]
                     for r in _by(recs, "request.submit")}
    assert all(set(r["args"]["traces"]) <= submit_traces
               for r in requeues)
    assert tracer.open_device_count() == 0
    # every opened device span was also recorded closed
    dev = [r for r in recs if r["cat"] == "device"]
    assert len(dev) >= len(_by(recs, "wave.prefill"))
    # registry snapshot exposes the pool's exhaustion counter
    kv = sched.obs.snapshot()["kv"]["shard0"]
    assert kv["exhausted"] >= 1
    assert kv["page_allocs"] > kv["used"] >= 0


def test_span_balance_under_spec_fallback(model, params2):
    """The no-wrap gate's fallback (speculative wave demoted to plain
    decode) must stay balanced and leave a ``spec.fallback`` event:
    the wave's decode span opens lazily at the first tick, regardless
    of which path the gate chose."""
    eng = ExpertEngine(model, params2[0], kv_layout="paged", page_size=8,
                       speculate_k=4, draft="table", max_len=16,
                       min_len_bucket=8, batch_buckets=(1, 2))
    tracer = Tracer()
    eng.bind_tracer(tracer)
    p = np.random.default_rng(5).integers(0, 100, size=8).astype(np.int32)
    # Sb + steps = 17 > C = 16 trips the gate -> plain-decode fallback
    eng.admit([0, 1], [p, p.copy()], [10, 10])
    while eng.has_pending:
        eng.tick()
        eng.poll()
    assert eng.stats.spec_fallback_waves == 1
    assert eng.stats.verify_steps == 0
    recs = tracer.records()
    fb = _by(recs, "spec.fallback")
    assert len(fb) == 1
    assert _by(recs, "wave.decode"), "fallback wave left no decode span"
    assert not _by(recs, "wave.verify")   # gate-blocked: verify never ran
    assert tracer.open_device_count() == 0
    waves = {r["args"]["wave"] for r in _by(recs, "wave.prefill")}
    assert fb[0]["args"]["wave"] in waves


# -- zero new host blocks ----------------------------------------------------


def test_host_blocks_identical_with_tracing_on(model, params2):
    """The acceptance criterion's sync-safety half: the same traffic
    served with and without a live tracer performs exactly the same
    number of host-blocking syncs, and produces the same tokens."""
    rng = np.random.default_rng(7)
    reqs = _reqs(rng, 10, n_experts=2)

    def serve(tracer):
        reg = ExpertRegistry()
        for i, p in enumerate(params2):
            reg.add(f"ex{i}", ExpertEngine(model, p, max_len=32))
        sched = Scheduler(None, reg, tracer=tracer)
        sched.submit(reqs)
        out = {r.uid: r.tokens for r in sched.drain()}
        blocks = sum(reg[e].backend.stats.host_blocks for e in range(2))
        return out, blocks

    got_off, blocks_off = serve(None)
    tracer = Tracer()
    got_on, blocks_on = serve(tracer)
    assert blocks_on == blocks_off > 0
    for uid in got_off:
        np.testing.assert_array_equal(got_on[uid], got_off[uid],
                                      err_msg=str(uid))
    # and the trace really recorded the work it didn't perturb
    assert tracer.open_device_count() == 0
    assert len(_by(tracer.records(), "request.finish")) == 10


# -- program spans: the step tree, routing waits, the profiler bridge -------


def test_program_spans_form_the_step_and_route_trees(model, params2,
                                                     routed):
    """Every new span appears on a traced RoutedServer, each linked to
    the innermost span open on its thread: ``engine.enqueue`` and
    ``engine.sync`` under ``step``, ``route.wait`` under ``route``.
    ``route`` carries ``ahead``: dispatches issued and not yet covered
    by a completed sync, none once the server has drained."""
    tracer = Tracer()
    srv = _routed_server(model, params2, routed, tracer)
    feats = routed[2]
    srv.submit(_routed_reqs(feats, 0, 4))
    srv.step()                     # prefill + first tick, still in flight
    srv.submit(_routed_reqs(feats, 100, 4))
    srv.scheduler.drain()
    assert srv.scheduler._dispatches_ahead() == 0
    srv.submit(_routed_reqs(feats, 200, 2))
    assert len(srv.scheduler.drain()) == 2

    recs = tracer.records()
    by_id = {r["id"]: r for r in recs}
    assert len(by_id) == len(recs)                      # ids are unique
    for name in ("step", "engine.enqueue", "engine.sync", "route",
                 "route.wait"):
        assert _by(recs, name), f"no {name} span"
    for r in _by(recs, "step") + _by(recs, "route"):
        assert r["parent"] == 0
    for name, parent in (("engine.enqueue", "step"),
                         ("engine.sync", "step"),
                         ("route.wait", "route")):
        for r in _by(recs, name):
            p = by_id[r["parent"]]
            assert p["name"] == parent
            assert p["ts"] <= r["ts"] and \
                r["ts"] + r["dur"] <= p["ts"] + p["dur"]
    assert all(r["cat"] == "enqueue" for r in _by(recs, "engine.enqueue"))
    assert {r["args"]["kind"] for r in _by(recs, "engine.enqueue")} == \
        {"prefill", "decode"}
    # device spans and events hang off the span open where they began
    for r in recs:
        if r["cat"] == "device" or r["ph"] == "i":
            assert r["parent"] == 0 or r["parent"] in by_id
    assert any(by_id[r["parent"]]["name"] == "step"
               for r in _by(recs, "wave.prefill"))

    routes = _by(recs, "route")
    assert [r["args"]["rows"] for r in routes] == [4, 4, 2]
    assert [r["args"]["uids"] for r in routes][2] == [200, 201]
    ahead = [r["args"]["ahead"] for r in routes]
    assert ahead[0] == 0 and ahead[1] >= 2 and ahead[2] == 0
    assert tracer.open_device_count() == 0


def test_host_blocks_identical_with_tracing_on_routed(model, params2,
                                                     routed):
    """The new engine.sync spans wrap the engine's existing device_get:
    routed traffic blocks the host exactly as often, and serves the same
    tokens, with a live tracer as without one."""
    feats = routed[2]

    def serve(tracer):
        srv = _routed_server(model, params2, routed, tracer)
        srv.submit(_routed_reqs(feats, 0, 6))
        srv.step()
        srv.submit(_routed_reqs(feats, 50, 6, max_new=3))
        out = {r.uid: r.tokens for r in srv.scheduler.drain()}
        reg = srv.scheduler.registry
        return out, [reg[e].backend.stats.host_blocks for e in range(2)]

    got_off, blocks_off = serve(None)
    tracer = Tracer()
    got_on, blocks_on = serve(tracer)
    assert blocks_on == blocks_off and sum(blocks_off) > 0
    assert sorted(got_on) == sorted(got_off) and len(got_off) == 12
    for uid in got_off:
        np.testing.assert_array_equal(got_on[uid], got_off[uid],
                                      err_msg=str(uid))
    # one engine.sync per host block
    assert len(_by(tracer.records(), "engine.sync")) == sum(blocks_on)


def test_program_spans_reach_the_profiler_host_plane(tmp_path, model,
                                                     params2, routed):
    """Under ``jax.profiler.trace`` each program span of an enabled
    tracer is a host-plane event of the profile, read back with
    ``jax.profiler.ProfileData``; a span on ``NULL_TRACER`` is neither
    recorded nor annotated."""
    tracer = Tracer()
    srv = _routed_server(model, params2, routed, tracer)
    feats = routed[2]
    srv.serve(_routed_reqs(feats, 0, 2))       # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with NULL_TRACER.span("obs.null_probe") as probe:
            pass
        srv.submit(_routed_reqs(feats, 10, 4))
        srv.scheduler.drain()
    assert probe.id == 0 and NULL_TRACER.records() == []
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events}
    assert {"step", "engine.enqueue", "engine.sync", "route",
            "route.wait"} <= names
    assert "obs.null_probe" not in names
    spans = [r for r in tracer.records() if r["ph"] == "X"
             and r["cat"] != "device"]
    assert {r["name"] for r in spans} <= names


def test_null_tracer_opens_no_span():
    """The disabled path keeps no stack, mints no id and never resolves
    the profiler bridge."""
    with NULL_TRACER.span("route") as outer:
        with NULL_TRACER.enqueue_span("engine.enqueue") as inner:
            pass
    assert outer.id == inner.id == 0 and inner.parent == 0
    assert outer.ms >= inner.ms >= 0.0
    assert not hasattr(NULL_TRACER._local, "stack")
    assert NULL_TRACER.records() == []
    # an enabled tracer nests, then unwinds its stack, on error too
    t = Tracer()
    with pytest.raises(RuntimeError):
        with t.span("route"):
            with t.span("route.wait"):
                raise RuntimeError("boom")
    wait, route = t.records()
    assert wait["parent"] == route["id"] and route["parent"] == 0
    assert wait["args"]["error"] == "RuntimeError"
    assert t._stack() == []


def test_tracer_without_jax_keeps_no_bridge(monkeypatch):
    """``repro.obs`` promises to work without JAX: where
    ``jax.profiler`` cannot be imported, an enabled tracer still records
    and links its spans, and annotates nothing."""
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    t = Tracer()
    with t.span("step"):
        with t.enqueue_span("engine.enqueue", kind="decode") as sp:
            pass
    assert t._annotation is None and sp._ann is None
    inner, outer = t.records()
    assert inner["parent"] == outer["id"] != 0


# -- snapshot tree stability -------------------------------------------------


def test_snapshot_tree_keys_are_stable(model, params2):
    """Downstream consumers key off this tree: pin the top-level groups
    and the per-group leaf names so drift is a reviewed change."""
    reg = ExpertRegistry()
    reg.add("ex0", ExpertEngine(model, params2[0], max_len=32,
                                kv_layout="paged", speculate_k=2,
                                draft="table"))
    sched = Scheduler(None, reg)
    rng = np.random.default_rng(0)
    sched.submit(_reqs(rng, 4, n_experts=1, lo=3, hi=12))
    sched.drain()
    snap = sched.obs.snapshot()
    assert sorted(snap) == ["engines", "executor", "kv", "scheduler"]
    stats_keys = set(SchedulerStats().as_dict())
    assert set(snap["scheduler"]) == stats_keys | {"latency"}
    assert snap["scheduler"]["responses"] == 4
    for h in ("queue_ms", "stalled_ms"):
        assert set(snap["scheduler"]["latency"][h]) == \
            {"count", "sum", "mean", "p50", "p95", "p99", "max"}
    assert snap["scheduler"]["latency"]["queue_ms"]["count"] == 4
    eng = snap["engines"]["shard0"]
    assert {"host_blocks", "decode_steps", "spec_fallback_waves"} <= \
        set(eng)
    assert eng["draft"] == {"name": "table", "kind": "BigramTableDraft"}
    assert set(snap["kv"]["shard0"]) == {"free", "used", "page_allocs",
                                         "page_releases", "exhausted"}
    assert snap["executor"]["name"] in ("serial", "overlapped")
    # the frozen stats snapshot a caller holds does not mutate under it
    held = sched.stats
    sched.submit(_reqs(rng, 2, n_experts=1, lo=3, hi=12))
    sched.drain()
    assert held.responses == 4 and sched.stats.responses == 6
    with pytest.raises(AttributeError):
        held.responses = 0


# -- the static gate: planted O001-O003 violations ---------------------------


def test_obs_lint_catches_tracer_call_in_jitted_fn():
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def step(x, tracer):
            tracer.event("tick")     # fires at trace time only
            return x + 1
    """)
    vs = obs_lint.lint_source(src, "src/repro/serve/planted.py")
    assert any(v.rule == "O001" for v in vs), vs


def test_obs_lint_allows_host_side_tracing():
    src = textwrap.dedent("""
        import jax

        @jax.jit
        def step(x):
            return x + 1

        def drive(x, tracer):
            tracer.event("tick")
            y = step(x)
            return jax.device_get(y)
    """)
    assert not obs_lint.lint_source(src, "src/repro/serve/planted.py")


def test_obs_lint_catches_span_timing_enqueue():
    src = textwrap.dedent("""
        import jax.numpy as jnp

        def run(self, a, b):
            with self.tracer.span("wave"):
                y = jnp.dot(a, b)    # async dispatch: span sees enqueue
            return y
    """)
    vs = obs_lint.lint_source(src, "src/repro/serve/planted.py")
    assert any(v.rule == "O002" for v in vs), vs


def test_obs_lint_blesses_synced_span_and_enqueue_span():
    src = textwrap.dedent("""
        import jax.numpy as jnp
        import numpy as np

        def run_synced(self, a, b):
            with self.tracer.span("wave"):
                y = np.asarray(jnp.dot(a, b))   # sync inside the span
            return y

        def run_enqueue(self, a, b):
            # declared enqueue semantics: exempt by name
            with self.tracer.enqueue_span("hub.commit"):
                y = jnp.dot(a, b)
            return y
    """)
    assert not obs_lint.lint_source(src, "src/repro/serve/planted.py")


def test_obs_lint_catches_end_device_outside_sync_site():
    src = textwrap.dedent("""
        def harvest(self, w):
            self.tracer.end_device(w.sp_decode)   # work not done yet
            return w
    """)
    vs = obs_lint.lint_source(src, "src/repro/serve/planted.py")
    assert any(v.rule == "O002" for v in vs), vs


def test_obs_lint_blesses_end_device_at_sync_site():
    src = textwrap.dedent("""
        import jax

        def materialize(self, w):
            out = jax.device_get(w.tok)
            self.tracer.end_device(w.sp_decode)
            return out
    """)
    assert not obs_lint.lint_source(src, "src/repro/serve/planted.py")


def test_obs_lint_catches_computed_histogram_buckets():
    src = textwrap.dedent("""
        from repro.obs import Histogram

        def build(n):
            return Histogram(buckets=[10.0 ** i for i in range(n)])
    """)
    vs = obs_lint.lint_source(src, "src/repro/serve/planted.py")
    assert any(v.rule == "O003" for v in vs), vs


def test_obs_lint_blesses_literal_and_constant_buckets():
    src = textwrap.dedent("""
        from repro.obs import DEFAULT_MS_BUCKETS, Histogram

        LOCAL_BUCKETS = (1.0, 10.0, 100.0)

        def build():
            a = Histogram()                          # library default
            b = Histogram(buckets=(0.5, 5.0, 50.0))  # inline literal
            c = Histogram(buckets=DEFAULT_MS_BUCKETS)
            d = Histogram(LOCAL_BUCKETS)             # module literal
            return a, b, c, d
    """)
    assert not obs_lint.lint_source(src, "src/repro/serve/planted.py")


def test_repo_is_obs_clean():
    """The gate holds over the real tree (same entry the CI runs)."""
    assert obs_lint.run() == []
