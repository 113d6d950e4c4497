"""Serving benchmark: throughput + latency percentiles under Poisson
traffic against the continuous-batching RoutedServer.

Arrivals are *virtual-time* Poisson processes; service is real measured
compute. The event loop submits every request whose arrival time has
passed, runs one scheduler step, charges its wall-clock duration to the
virtual clock, and records per-request latency = completion - arrival.
When the system is idle the clock jumps to the next arrival, so offered
load (not Python sleep jitter) determines queueing.

Traffic scenarios (the ISSUE's acceptance matrix):
  uniform  — requests spread evenly over all experts
  skewed   — 80% of traffic hammers one expert (hot-expert queueing)
  bursty   — on/off arrivals: idle gaps, then bursts at 10x rate
  shared-prefix (``--workload shared-prefix``) — cohort traffic: groups
             of clients repeatedly send the *same* prompt (the paper's
             setting: one regional cohort, one dataset, near-identical
             queries). With ``--kv paged`` the engine deduplicates
             cohort prefills and serves repeats from the prefix cache,
             so prefill tokens *computed* drop strictly below prefill
             tokens *submitted* — the CI-asserted savings signal.
  long-prompt (``--workload long-prompt``) — mixed-length Poisson
             traffic where every few requests is a *whale* (a prompt
             near ``max_len``, far past ``chunk_len``). The bench runs
             the identical stream against a chunked server (suffix
             chunks budgeted per step via
             ``SchedulerConfig.prefill_tokens_per_step``) and a
             monolithic reference, asserts token identity, and reports
             the p99 latency of the *short* (decode-dominated)
             requests on both — the disaggregation signal: with
             chunking, decode ticks keep running while a whale
             prefills, so the short-request tail stays bounded
             (asserted, and emitted to the ``--json`` payload).
  bursty speculative (``--workload bursty --speculate-k k``) — the
             speculative-decoding comparison: one bursty decode-heavy
             stream (short prompts, 16-32 new tokens) served by a
             draft-k/verify-1 server and a plain-decode server built
             from identical params. Asserts bitwise token identity
             (greedy verification is exact), >1.5x decoded tokens/sec
             over the plain reference, zero steady-state recompiles on
             *both* servers, and (with ``--accept-floor``) a draft
             acceptance-rate floor — the CI regression signal.
  zipf (``--hub``) — the long-tail catalog workload: ``--n-experts N``
             experts served through an ExpertHub with only
             ``--resident K`` device slots (N >> K). Traffic is one
             catalog sweep (every expert cold-starts once) followed by
             Zipf-distributed arrivals, so popular experts stay
             resident while the tail churns through the slots. The
             bench runs the identical request stream against a
             fully-resident baseline hub (K = N) and asserts zero
             token divergence, evictions > 0, every expert served, and
             zero steady-state recompiles (bank jit cache + install
             executable count unchanged from post-warmup through the
             whole measured run).

crossed with two KV layouts:
  ring   — dense per-wave KV buffers (the reference)
  paged  — per-shard page pool + per-row page tables with refcounted
           shared-prefix reuse (token-identical to ring; asserted in
           tests/test_paged_kv.py)

and two placement columns:
  per-device — PR 1's path: one independent ExpertEngine per expert
  banked     — plan_placement banks homogeneous experts into one
               vmapped/sharded dispatch over a mesh ``expert`` axis
               (``--devices N`` forces N host CPU devices so the mesh
               path runs on a laptop/CI box)

and two dispatch executors:
  serial     — the blocking reference: every decode tick forces a
               device→host copy of its sampled token before the next
               shard's work is issued
  overlapped — async dispatch: all shards' prefills and decode ticks
               are enqueued before anything blocks; tokens stay on
               device and the host blocks at most once per wave per
               step (the batched harvest transfer)

Both executors are token-identical; the CI-stable signal separating
them is ``host_blocks`` (the engines' sync counter) per decoded token,
reported per scenario and in ``--json`` output.

  PYTHONPATH=src python benchmarks/serving_bench.py [--requests 60] \
      [--placement {per-device,banked}] [--devices 8] \
      [--executor {serial,overlapped}] [--kv {ring,paged}] \
      [--workload {standard,shared-prefix,long-prompt,bursty}] \
      [--chunk-len 32 --prefill-budget 32] [--json OUT.json] \
      [--speculate-k 4 --draft table --accept-floor 0.25] \
      [--hub --n-experts 64 --resident 8]

Output: one CSV-ish line per scenario,
  scenario,placement,executor,kv,n,throughput_rps,p50_ms,p95_ms,p99_ms,
  batches,prefill_compiles,host_blocks_per_tok,prefill_tok_computed,
  prefill_tok_submitted
and, with ``--json``, a machine-readable results file for CI.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, "src")

import numpy as np

DATASETS = ["mnist", "har", "reuters"]


def build_server(n_per_dataset: int, epochs: int, max_batch: int,
                 placement: str, executor: str = "overlapped",
                 kv: str = "ring", check_every: int = 0,
                 max_len: int = 64, chunk_len: "int | None" = None,
                 prefill_budget: int = 0, speculate_k: int = 0,
                 draft=None):
    import jax
    from repro.configs import get_config
    from repro.core import ExpertRegistry, build_matcher, train_bank
    from repro.data import load_benchmark
    from repro.launch.mesh import make_expert_mesh
    from repro.models import build_model
    from repro.serve import ExpertEngine, RoutedServer, plan_placement

    bench = load_benchmark(names=DATASETS, n_per_dataset=n_per_dataset,
                           seed=0)
    names = list(bench)
    aes, _ = train_bank([(n, bench[n]["server"][0]) for n in names],
                        epochs=epochs, batch_size=64)
    cents = [(bench[n]["server"][0], bench[n]["server"][1]) for n in names]
    matcher = build_matcher(aes, names, cents)
    registry = ExpertRegistry()
    for i, n in enumerate(names):
        cfg = get_config("smollm-135m").reduced(name=f"expert-{n}")
        model = build_model(cfg)
        registry.add(n, ExpertEngine(
            model, model.init(jax.random.PRNGKey(i)), max_len=max_len,
            kv_layout=kv, chunk_len=chunk_len,
            speculate_k=speculate_k, draft=draft))
    plan = None
    if placement == "banked":
        mesh = make_expert_mesh()
        plan = plan_placement(registry, mesh=mesh)
        print(f"# placement over {len(jax.devices())} device(s):",
              flush=True)
        for line in plan.describe(registry.names).splitlines():
            print(f"#   {line}", flush=True)
    server = RoutedServer(matcher, registry, max_batch=max_batch,
                          placement=plan, executor=executor,
                          check_every=check_every,
                          prefill_tokens_per_step=prefill_budget)
    return server, bench, names


def build_hub_server(n_experts: int, resident: int, max_batch: int,
                     executor: str, kv: str, store: "str | None",
                     seed: int = 0, use_mesh: bool = True,
                     max_len: int = 32, check_every: int = 0):
    """An ExpertHub-fronted server: ``n_experts`` catalogued, only
    ``resident`` device slots. Requests are pre-routed (no matcher —
    the hub bench isolates the residency subsystem), and with ``store``
    every expert is checkpointed cold so staging is real disk I/O. The
    slot bank shards over the expert mesh when the forced device count
    divides it (the fully-resident baseline passes ``use_mesh=False``:
    it is a token-identity reference, and GSPMD-compiling E = catalog
    vmapped graphs would dominate the bench for no extra signal)."""
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_expert_mesh
    from repro.models import build_model
    from repro.serve import ExpertHub, RoutedServer

    cfg = get_config("smollm-135m").reduced(name="hub-expert")
    model = build_model(cfg)
    mesh = make_expert_mesh() if (use_mesh and len(jax.devices()) > 1
                                  and resident % len(jax.devices()) == 0) \
        else None
    hub = ExpertHub(model, n_slots=resident, max_len=max_len, mesh=mesh,
                    kv_layout=kv, store=store)
    for i in range(n_experts):
        hub.add_expert(f"expert-{i:03d}",
                       model.init(jax.random.PRNGKey(seed + i)),
                       cold=store is not None)
    server = RoutedServer(None, hub.build_registry(),
                          max_batch=max_batch, hub=hub,
                          executor=executor, check_every=check_every)
    return server, hub


def zipf_requests(n: int, n_experts: int, rng: np.random.Generator,
                  alpha: float = 1.1, max_len: int = 32) -> list:
    """Long-tail catalog traffic: a catalog sweep (every expert exactly
    once — the cold-start path, and the guarantee that all N experts
    are served) followed by Zipf(alpha) arrivals over expert rank, so
    expert 0 is hottest and the tail churns through the hub's slots."""
    from repro.serve import Request
    p = 1.0 / np.arange(1, n_experts + 1) ** alpha
    p /= p.sum()
    picks = rng.choice(n_experts, size=max(n - n_experts, 0), p=p)
    experts = list(range(n_experts)) + list(picks)
    hi = max(4, 3 * max_len // 4)
    return [Request(uid=uid, features=np.zeros(784, np.float32),
                    prompt=rng.integers(0, 100,
                                        size=int(rng.integers(3, hi))),
                    max_new_tokens=int(rng.integers(2, 6)),
                    expert=int(e))
            for uid, e in enumerate(experts[:n])]


def _engine_stats(server):
    st = server.stats
    # engine stats are per ExpertEngine; bank stats are per bank (each
    # bank serves several experts but counts its executables once)
    return list(st["engines"].values()) + list(st["banks"].values())


def total_prefill_compiles(server) -> int:
    return sum(e.prefill_compiles for e in _engine_stats(server))


def total_decode_compiles(server) -> int:
    return sum(e.decode_compiles for e in _engine_stats(server))


def total_suffix_compiles(server) -> int:
    """Suffix-chunk executables (zero on unchunked/ring engines)."""
    return sum(e.suffix_compiles for e in _engine_stats(server))


def total_verify_compiles(server) -> int:
    """Speculative verify executables (zero on k=0 engines)."""
    return sum(e.verify_compiles for e in _engine_stats(server))


def total_jit_cache_entries(server) -> int:
    """Every real XLA executable across every engine — the number the
    zero-steady-state-recompile assertion pins between warmup and the
    end of a measured run."""
    return sum(e.jit_cache_entries for e in _engine_stats(server))


def total_host_blocks(server) -> int:
    """Host-blocking device→host syncs across all engines (the
    executor-sensitive counter: serial blocks once per decode tick per
    wave, overlapped at most once per wave per step)."""
    return sum(e.host_blocks for e in _engine_stats(server))


def total_tokens(server) -> int:
    return sum(e.tokens_generated for e in _engine_stats(server))


def total_prefill_tokens(server) -> "tuple[int, int]":
    """(computed, submitted) prompt-token totals across engines. With
    the paged layout, deduplicated and prefix-cached rows contribute
    nothing to computed — the shared-prefix savings signal."""
    return (sum(e.prefill_tokens_computed for e in _engine_stats(server)),
            sum(e.prefill_tokens_submitted for e in _engine_stats(server)))


def assert_bounded_compiles(server) -> None:
    """The bucket ladders bound the number of *real* XLA executables.

    Checked against the corrected compile counters (per-wrapper
    ``_cache_size`` sums): a wrapper that silently recompiled for a
    shape/dtype the bucket key didn't capture now trips this assert
    instead of hiding behind a one-count-per-wrapper scheme.
    """
    from repro.serve import ExpertEngine
    cores = [s.bank.core for s in server.scheduler.shards if s.banked]
    cores += [b.core for b in (server.registry[e].backend
                               for e in range(len(server.registry)))
              if isinstance(b, ExpertEngine)]
    bounds = [c.executable_bounds() for c in cores]
    p_bound = sum(b["prefill"] for b in bounds)
    s_bound = sum(b["suffix"] for b in bounds)
    d_bound = sum(b["decode"] for b in bounds)
    v_bound = sum(b["verify"] for b in bounds)
    got_p = total_prefill_compiles(server)
    got_s = total_suffix_compiles(server)
    got_d = total_decode_compiles(server)
    got_v = total_verify_compiles(server)
    assert (got_p <= p_bound and got_s <= s_bound and got_d <= d_bound
            and got_v <= v_bound), (
        f"compile bound violated: {got_p} prefill (bound {p_bound}), "
        f"{got_s} suffix (bound {s_bound}), {got_d} decode (bound "
        f"{d_bound}), {got_v} verify (bound {v_bound}) real executables")


def arrivals_for(scenario: str, n: int, rate: float,
                 rng: np.random.Generator) -> np.ndarray:
    """Arrival timestamps (seconds, sorted) for ``n`` requests."""
    if scenario == "bursty":
        # on/off: bursts of ~n/6 requests at 10x rate, gaps of 3/rate
        ts, t = [], 0.0
        while len(ts) < n:
            for _ in range(min(int(np.ceil(n / 6)), n - len(ts))):
                t += float(rng.exponential(1.0 / (10 * rate)))
                ts.append(t)
            t += 3.0 / rate
        return np.asarray(ts[:n])
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def expert_mix(scenario: str, n: int, n_experts: int,
               rng: np.random.Generator) -> np.ndarray:
    if scenario == "skewed":
        p = np.full(n_experts, 0.2 / max(n_experts - 1, 1))
        p[0] = 0.8
        return rng.choice(n_experts, size=n, p=p)
    return rng.integers(0, n_experts, size=n)


def cohort_requests(bench, names, n: int, rng) -> list:
    """Shared-prefix workload: cohorts of clients sending the *same*
    prompt (the paper's regional-cohort setting). Each cohort is pinned
    to one dataset/expert; prompts are 30 tokens (a 32-bucket, no ring
    wrap at max_new <= 10, so prefixes stay cacheable across waves)."""
    from repro.serve import Request
    reqs = []
    n_cohorts = max(len(names), n // 8)
    prompts = [rng.integers(0, 100, size=30) for _ in range(n_cohorts)]
    for uid in range(n):
        c = int(rng.integers(n_cohorts))
        x, _ = bench[names[c % len(names)]]["client_a"]
        reqs.append(Request(
            uid=uid, features=x[int(rng.integers(len(x)))],
            prompt=prompts[c],
            max_new_tokens=int(rng.integers(2, 11))))
    return reqs


def long_prompt_requests(bench, names, n: int, rng,
                         max_len: int = 128,
                         whale_every: int = 6) -> "tuple[list, set]":
    """Mixed-length traffic: mostly short decode-dominated requests,
    with every ``whale_every``-th request a whale prompt near
    ``max_len`` (far past ``chunk_len``, so it prefills through the
    suffix-chunk ladder). Returns (requests, whale_uids) — the bench
    reports decode-tail latency over the *non*-whale uids."""
    from repro.serve import Request
    reqs, whales = [], set()
    for uid in range(n):
        x, _ = bench[names[uid % len(names)]]["client_a"]
        if uid % whale_every == whale_every - 1:
            size = int(rng.integers(3 * max_len // 4, max_len - 7))
            max_new = int(rng.integers(2, 5))
            whales.add(uid)
        else:
            size = int(rng.integers(3, 25))
            max_new = int(rng.integers(2, 11))
        reqs.append(Request(
            uid=uid, features=x[int(rng.integers(len(x)))],
            prompt=rng.integers(0, 100, size=size),
            max_new_tokens=max_new))
    return reqs, whales


def run_scenario(scenario: str, server, bench, names,
                 n: int, rate: float, seed: int,
                 reqs: "list | None" = None,
                 collect: "dict | None" = None,
                 whale_uids: "set | None" = None) -> dict:
    """Drive one scenario. ``reqs`` overrides the generated request
    stream (the hub bench feeds both servers the identical stream);
    ``collect`` (a dict) captures uid -> (expert, tokens) for token-
    identity comparison across servers; ``whale_uids`` splits the
    latency report — the result gains ``decode_p50_ms``/
    ``decode_p99_ms`` over the non-whale uids, plus counters for how
    many steps ran with prefill chunks pending and how many of those
    also advanced a decode wave (the disaggregation signal)."""
    import jax
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    t_arr = arrivals_for("bursty" if scenario == "bursty" else "uniform",
                         n, rate, rng)
    if reqs is not None:
        assert len(reqs) == n
    elif scenario == "shared-prefix":
        reqs = cohort_requests(bench, names, n, rng)
    else:
        which = expert_mix(scenario, n, len(names), rng)
        reqs = []
        for uid in range(n):
            x, _ = bench[names[which[uid]]]["client_a"]
            reqs.append(Request(
                uid=uid, features=x[int(rng.integers(len(x)))],
                prompt=rng.integers(0, 100,
                                    size=int(rng.integers(3, 48))),
                max_new_tokens=int(rng.integers(2, 12))))

    now, busy, i, done_at = 0.0, 0.0, 0, {}
    chunk_steps, overlap_steps = 0, 0
    sched = server.scheduler
    batches0 = sched.stats.batches
    stalls0 = sched.stats.kv_stalls
    rstalls0 = sched.stats.resident_stalls
    compiles0 = total_prefill_compiles(server)
    blocks0 = total_host_blocks(server)
    tokens0 = total_tokens(server)
    pf0 = total_prefill_tokens(server)
    while i < n or sched.has_work:
        while i < n and t_arr[i] <= now:
            got = sched.submit([reqs[i]])
            if not got:    # queue full: let the scheduler make room
                break
            i += got
        if not sched.has_work:
            now = max(now, t_arr[i])  # idle: jump to next arrival
            continue
        pending_chunks = any(
            eng is not None and getattr(eng, "core", None) is not None
            and eng.core.has_pending_chunks
            for eng in map(sched._shard_engine, sched.shards))
        ticks0 = sched.stats.ticks
        t0 = time.perf_counter()
        resps = sched.step()
        # charge device completion of every harvested response to this
        # step: without the sync the clock stops at enqueue time and
        # the reported latency percentiles under-count device work
        # still in flight (rule L004). In-flight waves of *unfinished*
        # requests stay unsynced — their device time is charged to the
        # step that eventually harvests them, preserving the overlap
        # the async executor exists to provide.
        jax.block_until_ready([r.tokens for r in resps])
        dt = time.perf_counter() - t0
        now += dt
        busy += dt
        if pending_chunks:
            chunk_steps += 1
            if sched.stats.ticks > ticks0:
                overlap_steps += 1
        for r in resps:  # completed during this step
            done_at[r.uid] = now
            if collect is not None:
                collect[r.uid] = (r.expert, r.tokens.tolist())
    lat = np.asarray([done_at[u] - t_arr[u] for u in range(n)])
    toks = total_tokens(server) - tokens0
    blocks = total_host_blocks(server) - blocks0
    pf1 = total_prefill_tokens(server)
    extra = {}
    if whale_uids is not None:
        dec = np.asarray([done_at[u] - t_arr[u] for u in range(n)
                          if u not in whale_uids])
        extra = {"decode_p50_ms": float(np.percentile(dec, 50) * 1e3),
                 "decode_p99_ms": float(np.percentile(dec, 99) * 1e3),
                 "prefill_chunk_steps": chunk_steps,
                 "decode_overlap_steps": overlap_steps}
    return {**extra, "scenario": scenario, "n": n,
            "throughput_rps": n / max(now, 1e-9),
            # decode throughput over *busy* step time (idle gaps between
            # arrivals excluded) — the speculative bench's speedup metric
            "busy_s": busy,
            "decoded_tok_per_s": toks / max(busy, 1e-9),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "batches": sched.stats.batches - batches0,
            "prefill_compiles": total_prefill_compiles(server) - compiles0,
            "host_blocks": blocks,
            "tokens_generated": toks,
            "host_blocks_per_tok": blocks / max(toks, 1),
            "prefill_tokens_computed": pf1[0] - pf0[0],
            "prefill_tokens_submitted": pf1[1] - pf0[1],
            "kv_stalls": sched.stats.kv_stalls - stalls0,
            "resident_stalls": sched.stats.resident_stalls - rstalls0}


_CSV_HEADER = ("scenario,placement,executor,kv,n,throughput_rps,p50_ms,"
               "p95_ms,p99_ms,batches,prefill_compiles,"
               "host_blocks_per_tok,prefill_tok_computed,"
               "prefill_tok_submitted")


def _csv_row(r: dict, args) -> str:
    placement = "hub" if args.hub else args.placement
    return (f"{r['scenario']},{placement},{args.executor},"
            f"{args.kv},{r['n']},{r['throughput_rps']:.1f},"
            f"{r['p50_ms']:.1f},{r['p95_ms']:.1f},{r['p99_ms']:.1f},"
            f"{r['batches']},{r['prefill_compiles']},"
            f"{r['host_blocks_per_tok']:.3f},"
            f"{r['prefill_tokens_computed']},"
            f"{r['prefill_tokens_submitted']}")


def run_hub_bench(args) -> None:
    """The long-tail residency benchmark: N catalogued experts through
    K device slots, token-identity asserted against a fully-resident
    (K = N) baseline on the identical Zipf request stream.

    The whole measured run happens *after* the ladder warmup, so the
    no-recompile clause of the ``--hub`` acceptance criterion is
    direct: the bank's jit cache plus the slot-install executable must
    not grow across a run in which dozens of experts rotate through
    the slots.
    """
    import tempfile

    t0 = time.time()
    store = args.store or tempfile.mkdtemp(prefix="expert-store-")
    server, hub = build_hub_server(
        args.n_experts, args.resident, args.max_batch, args.executor,
        args.kv, store, seed=args.seed,
        check_every=args.check_invariants)
    base_srv, base_hub = build_hub_server(
        args.n_experts, args.n_experts, args.max_batch, args.executor,
        args.kv, None, seed=args.seed, use_mesh=False,
        check_every=args.check_invariants)
    print(f"# hub server up in {time.time()-t0:.1f}s "
          f"({args.n_experts} experts, {args.resident} slots, "
          f"kv={args.kv}, executor={args.executor}, "
          f"{hub.bank.mesh is not None and 'sharded' or 'unsharded'})",
          flush=True)
    import jax
    t0 = time.time()
    hub.warmup(args.max_batch)
    # warmup enqueues the whole ladder; sync before stopping the clock
    # so the reported figure is compile+execute, not enqueue (L004)
    jax.block_until_ready(hub.bank.core.params)
    jit_warm = hub.bank.stats.jit_cache_entries + hub.install_compiles
    print(f"# ladder warmup in {time.time()-t0:.1f}s "
          f"({jit_warm} executables)", flush=True)

    print(_CSV_HEADER)
    results = []
    rng = np.random.default_rng(args.seed)
    reqs = zipf_requests(args.requests, args.n_experts, rng,
                         alpha=args.alpha, max_len=hub.bank.max_len)
    got, want = {}, {}
    r = run_scenario("zipf", server, None, None, args.requests,
                     args.rate, args.seed, reqs=reqs, collect=got)
    rb = run_scenario("zipf", base_srv, None, None, args.requests,
                      args.rate, args.seed, reqs=reqs, collect=want)
    diverged = [u for u in want if got.get(u) != want[u]]
    assert not diverged, (
        f"hub diverged from the fully-resident baseline on uids "
        f"{diverged[:5]} (of {len(diverged)})")
    served = {e for e, _ in got.values()}
    assert len(served) == args.n_experts, (
        f"only {len(served)}/{args.n_experts} experts served")
    r["experts_served"] = len(served)
    r["baseline_throughput_rps"] = rb["throughput_rps"]
    results.append(r)
    print(_csv_row(r, args), flush=True)

    jit_end = hub.bank.stats.jit_cache_entries + hub.install_compiles
    hub.check()
    st = hub.stats
    print(f"# hub: {st.loads} loads, {st.evictions} evictions, "
          f"{st.resident_misses} resident misses, "
          f"stage {st.stage_ms_avg:.1f}ms avg, "
          f"commit {st.commit_ms_avg:.1f}ms avg", flush=True)
    print(f"# jit executables: {jit_warm} post-warmup -> {jit_end} "
          f"after the measured run", flush=True)
    # the ISSUE's acceptance criteria, asserted in-process so CI only
    # has to check the exit code
    assert st.evictions > 0, "no evictions: catalog fits the slots?"
    assert jit_end == jit_warm, (
        f"steady-state recompiles: {jit_warm} executables post-warmup "
        f"grew to {jit_end}")
    assert base_hub.stats.evictions == 0   # baseline truly resident
    assert_bounded_compiles(server)
    if args.json:
        payload = {"hub": True, "n_experts": args.n_experts,
                   "resident": args.resident, "alpha": args.alpha,
                   "kv": args.kv, "executor": args.executor,
                   "requests": args.requests, "rate": args.rate,
                   "seed": args.seed, "scenarios": results,
                   "hub_stats": st.as_dict(),
                   "jit_post_warmup": jit_warm,
                   "jit_after_runs": jit_end}
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", flush=True)
    if args.check_invariants:
        checks = (server.scheduler.stats.invariant_checks
                  + base_srv.scheduler.stats.invariant_checks)
        print(f"# invariants: {checks} mid-run sweeps "
              f"(every {args.check_invariants} steps), all held",
              flush=True)
    # join the staging workers: a bench that leaks its hub thread
    # would mask exactly the shutdown bugs the concurrency gate polices
    server.close()
    base_srv.close()


def run_long_prompt_bench(args) -> None:
    """The whale-prompt disaggregation benchmark: one mixed short/whale
    Poisson stream against a chunked server (suffix prefill, per-step
    chunk budget) and a monolithic reference built from identical
    params. Asserts token identity, that decode waves advanced while
    whale chunks were pending, and that the short-request (decode) p99
    stays bounded relative to the monolithic reference — the numbers
    and the bound land in the ``--json`` payload for CI."""
    from repro.serve import Request

    cl = args.chunk_len or 32
    budget = args.prefill_budget or cl
    max_len = 128
    t0 = time.time()
    server, bench, names = build_server(
        args.n_per_dataset, args.epochs, args.max_batch, args.placement,
        args.executor, "paged", check_every=args.check_invariants,
        max_len=max_len, chunk_len=cl, prefill_budget=budget)
    mono, _, _ = build_server(
        args.n_per_dataset, args.epochs, args.max_batch, args.placement,
        args.executor, "paged", check_every=args.check_invariants,
        max_len=max_len)
    print(f"# long-prompt servers up in {time.time()-t0:.1f}s "
          f"(chunk_len={cl}, prefill budget={budget} tok/step, "
          f"max_len={max_len}, placement={args.placement}, "
          f"executor={args.executor})", flush=True)

    # warm both servers' hot ladder points (one whale + one short per
    # expert) so the measured run charges the same residual compiles
    # to both sides
    wrng = np.random.default_rng(1)
    warm = []
    for k in range(len(names)):
        x = bench[names[k]]["client_a"][0]
        warm.append(Request(uid=-(2 * k + 1), features=x[k],
                            prompt=wrng.integers(0, 100, size=max_len - 8),
                            max_new_tokens=2))
        warm.append(Request(uid=-(2 * k + 2), features=x[k + 1],
                            prompt=wrng.integers(0, 100, size=12),
                            max_new_tokens=4))
    server.serve(list(warm))
    mono.serve(list(warm))
    print("# warmup done", flush=True)

    rng = np.random.default_rng(args.seed)
    reqs, whales = long_prompt_requests(bench, names, args.requests,
                                        rng, max_len=max_len)
    got, want = {}, {}
    print(_CSV_HEADER)
    r = run_scenario("long-prompt", server, bench, names, args.requests,
                     args.rate, args.seed, reqs=reqs, collect=got,
                     whale_uids=whales)
    print(_csv_row(r, args), flush=True)
    rm = run_scenario("long-prompt-mono", mono, bench, names,
                      args.requests, args.rate, args.seed, reqs=reqs,
                      collect=want, whale_uids=whales)
    print(_csv_row(rm, args), flush=True)

    diverged = [u for u in want if got.get(u) != want[u]]
    assert not diverged, (
        f"chunked server diverged from the monolithic reference on "
        f"uids {diverged[:5]} (of {len(diverged)})")
    assert r["prefill_chunk_steps"] > 0, (
        "no scheduler step ran with prefill chunks pending — whale "
        "prompts never went through the chunk ladder")
    assert r["decode_overlap_steps"] > 0, (
        "decode never advanced while a whale prefilled — the "
        "disaggregation seam is not interleaving")
    # the acceptance bound: a generous relative envelope, so the assert
    # catches a decode tail that collapsed back to whale-serialized
    # behaviour without being sensitive to CI machine noise
    bound = max(2.0 * rm["decode_p99_ms"], rm["decode_p99_ms"] + 250.0)
    assert r["decode_p99_ms"] <= bound, (
        f"short-request p99 {r['decode_p99_ms']:.1f}ms with chunking "
        f"exceeds the bound {bound:.1f}ms derived from the monolithic "
        f"reference ({rm['decode_p99_ms']:.1f}ms)")
    assert_bounded_compiles(server)
    assert_bounded_compiles(mono)
    print(f"# decode p99 while whales prefill: "
          f"{r['decode_p99_ms']:.1f}ms chunked vs "
          f"{rm['decode_p99_ms']:.1f}ms monolithic "
          f"(bound {bound:.1f}ms)", flush=True)
    print(f"# steps with chunks pending: {r['prefill_chunk_steps']}, "
          f"of which advanced decode: {r['decode_overlap_steps']}",
          flush=True)
    if args.json:
        payload = {"workload": "long-prompt",
                   "placement": args.placement,
                   "executor": args.executor, "kv": "paged",
                   "chunk_len": cl, "prefill_budget": budget,
                   "max_len": max_len, "requests": args.requests,
                   "rate": args.rate, "seed": args.seed,
                   "whales": len(whales),
                   "scenarios": [r, rm],
                   "decode_p99_ms": r["decode_p99_ms"],
                   "decode_p99_bound_ms": bound,
                   "decode_p99_bounded": bool(
                       r["decode_p99_ms"] <= bound),
                   "token_identity": True}
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", flush=True)


def speculative_requests(bench, names, n: int, rng,
                         max_len: int = 128) -> list:
    """Decode-heavy traffic for the speculative bench: short prompts
    (<= 16 tokens) with long greedy continuations (32-64 tokens), so
    wall-clock is dominated by the decode ticks speculation collapses
    — and the long tails give the online bigram draft time to converge
    on each sequence's greedy cycle. The geometry keeps every admission
    inside the no-wrap gate: Sb <= 16 and steps <= 63, so
    Sb + steps + k <= 87 < max_len for any k <= 8 — no wave is forced
    onto the fallback decode path."""
    from repro.serve import Request
    reqs = []
    for uid in range(n):
        x, _ = bench[names[uid % len(names)]]["client_a"]
        reqs.append(Request(
            uid=uid, features=x[int(rng.integers(len(x)))],
            prompt=rng.integers(0, 100, size=int(rng.integers(3, 17))),
            max_new_tokens=int(rng.integers(32, 65))))
    return reqs


def warm_full_ladder(server, rng, hi_bucket: int = 64,
                     max_new: int = 3) -> None:
    """Deterministically compile every reachable ladder point of every
    engine: one wave per (batch bucket, len bucket <= ``hi_bucket``)
    plus the decode/verify family each wave's ticks pull in.

    Scheduler admission shapes are *timing*-dependent (group sizes
    depend on how many requests arrive while a step runs), so no
    stream-driven warmup can guarantee coverage — a measured pass after
    this one is charged zero compiles by construction, which is what
    lets the bench pin ``jit_cache_entries`` exactly. Prompts are drawn
    fresh from ``rng`` so the paged prefix cache can't dedupe the
    prefill this wave exists to compile."""
    sched = server.scheduler
    for shard in sched.shards:
        eng = sched._shard_engine(shard)
        core = getattr(eng, "core", None)
        if core is None:
            continue
        for Sb in core.len_buckets:
            if Sb > hi_bucket:
                continue
            for Bb in core.batch_buckets:
                uids = [("__ladder__", Sb, Bb, i) for i in range(Bb)]
                prompts = [rng.integers(0, 100, size=Sb).astype(np.int32)
                           for _ in range(Bb)]
                core.admit_wave({0: (uids, prompts, [max_new] * Bb)})
                while core.has_pending:
                    core.tick()
                    core.harvest()
                    core.poll()


def _chain_stages(records) -> dict:
    """uid -> set of lifecycle stages observed in the trace records.
    Decode/verify spans carry only a wave id, so they are joined onto
    uids through the wave's prefill span (which lists its uids)."""
    have: dict = {}
    wave_uids: dict = {}
    decode_waves = set()
    for rec in records:
        name, a = rec["name"], rec["args"]
        if name == "request.submit":
            have.setdefault(a["uid"], set()).add("submit")
        elif name == "route":
            for u in a.get("uids", []):
                have.setdefault(u, set()).add("route")
        elif name == "request.admit":
            for u in a.get("uids", []):
                have.setdefault(u, set()).add("admit")
        elif name == "wave.prefill":
            for u in a.get("uids", []):
                have.setdefault(u, set()).add("prefill")
            wave_uids[a["wave"]] = list(a.get("uids", []))
        elif name in ("wave.decode", "wave.verify"):
            decode_waves.add(a["wave"])
        elif name == "request.finish":
            have.setdefault(a["uid"], set()).add("finish")
    for w in decode_waves:
        for u in wave_uids.get(w, []):
            have.setdefault(u, set()).add("decode")
    return have


def _stage_breakdown(records) -> dict:
    """Per-request stage table from one traced lap: queue/stalled come
    from the ``request.finish`` event's accounting, prefill/decode from
    the device spans of the waves each uid rode (decode time of a wave
    is attributed to every row in it — wave time, not per-token
    amortization). Returns p50/p95/p99 per stage in milliseconds."""
    finish: dict = {}
    prefill_ms: dict = {}
    wave_uids: dict = {}
    wave_decode_ms: dict = {}
    for rec in records:
        name, a = rec["name"], rec["args"]
        dur_ms = rec.get("dur", 0.0) / 1e3
        if name == "request.finish":
            finish[a["uid"]] = a
        elif name == "wave.prefill":
            for u in a.get("uids", []):
                prefill_ms[u] = prefill_ms.get(u, 0.0) + dur_ms
            wave_uids[a["wave"]] = list(a.get("uids", []))
        elif name in ("wave.decode", "wave.verify"):
            w = a["wave"]
            wave_decode_ms[w] = wave_decode_ms.get(w, 0.0) + dur_ms
    decode_ms: dict = {}
    for w, uids in wave_uids.items():
        for u in uids:
            decode_ms[u] = decode_ms.get(u, 0.0) + wave_decode_ms.get(
                w, 0.0)
    stages = ("queue_ms", "stalled_ms", "prefill_ms", "decode_ms",
              "total_ms")
    rows = {u: {"queue_ms": f.get("queue_ms", 0.0),
                "stalled_ms": f.get("stalled_ms", 0.0),
                "prefill_ms": prefill_ms.get(u, 0.0),
                "decode_ms": decode_ms.get(u, 0.0),
                "total_ms": f.get("total_ms", 0.0)}
            for u, f in finish.items()}
    out = {"requests": len(rows)}
    for st in stages:
        vals = np.asarray([r[st] for r in rows.values()]
                          if rows else [0.0])
        out[st] = {"p50": float(np.percentile(vals, 50)),
                   "p95": float(np.percentile(vals, 95)),
                   "p99": float(np.percentile(vals, 99))}
    return out


def _host_block_parity(spec, reqs) -> "tuple[int, int]":
    """The tentpole's sync-safety claim, asserted exactly. A *timed*
    lap cannot carry this comparison: the virtual arrival clock charges
    real step durations, so two timed laps can legitimately form
    different waves (and pay different harvest syncs) from timing noise
    alone. Instead replay the identical request list as a pure state
    machine — submit everything, drain — from a pinned starting state
    (draft table restored, prefix caches emptied), once untraced and
    once traced. Execution is then deterministic, so *any*
    ``host_blocks`` delta could only come from the tracer itself."""
    from repro.obs import Tracer
    sched = spec.scheduler
    cores = [eng.core for eng in map(sched._shard_engine, sched.shards)
             if eng is not None
             and getattr(eng, "core", None) is not None]
    saved = [c.draft_state for c in cores]   # immutable device pytrees

    def reset():
        for c, st in zip(cores, saved):
            c.draft_state = st
            if getattr(c, "prefix_cache", None) is not None \
                    and c.pool is not None:
                for e in range(c.pool.n_experts):
                    c.prefix_cache.evict_for(e, c.pool.n_pages)

    def drain_lap(tracer):
        reset()
        spec.bind_tracer(tracer)
        b0 = total_host_blocks(spec)
        try:
            sched.submit(reqs)
            while sched.has_work:
                sched.step()
        finally:
            spec.bind_tracer(None)
        return total_host_blocks(spec) - b0

    hb_off = drain_lap(None)
    parity_tracer = Tracer()
    hb_on = drain_lap(parity_tracer)
    assert parity_tracer.open_device_count() == 0, (
        f"{parity_tracer.open_device_count()} device span(s) left open "
        "after a full drain — span balance broke")
    assert hb_on == hb_off, (
        f"tracing changed the host sync count on a deterministic "
        f"replay: {hb_on} traced vs {hb_off} untraced — the tracer "
        "must close device spans only at existing sync points")
    return hb_off, hb_on


def _traced_lap(args, spec, bench, names, reqs, ref) -> dict:
    """One extra lap of the identical bursty stream on the *warm*
    speculative server with lifecycle tracing on. Same process, same
    jit caches, back to back with the tracing-off reference lap — the
    in-job comparison CI pins the <3% overhead budget against. Asserts
    the tentpole's sync-safety claim (``host_blocks`` identical on/off
    via a deterministic replay, zero device spans left open) and that
    at least one request produced a complete
    submit→route→admit→prefill→decode→finish span chain, then exports
    the Chrome trace (+ greppable JSONL sibling)."""
    from repro.obs import Tracer
    hb_off, hb_on = _host_block_parity(spec, reqs)
    tracer = Tracer()
    spec.bind_tracer(tracer)
    try:
        rt = run_scenario("bursty", spec, bench, names, args.requests,
                          args.rate, args.seed, reqs=reqs)
    finally:
        spec.bind_tracer(None)
    assert tracer.open_device_count() == 0, (
        f"{tracer.open_device_count()} device span(s) left open after "
        "a full drain — span balance broke")
    records = tracer.records()
    need = {"submit", "route", "admit", "prefill", "decode", "finish"}
    chains = [u for u, s in _chain_stages(records).items() if need <= s]
    assert chains, (
        "no request produced a complete span chain "
        "(submit→route→admit→prefill→decode→finish)")
    regression = 100.0 * (1.0 - rt["decoded_tok_per_s"]
                          / max(ref["decoded_tok_per_s"], 1e-9))
    n_events = tracer.export_chrome(args.trace)
    jsonl = args.trace + "l"  # OUT.json -> OUT.jsonl
    tracer.export_jsonl(jsonl)
    table = _stage_breakdown(records)
    print(f"# traced lap: {rt['decoded_tok_per_s']:.1f} tok/s vs "
          f"{ref['decoded_tok_per_s']:.1f} untraced "
          f"({regression:+.2f}% overhead), host_blocks "
          f"{hb_on}=={hb_off} on the deterministic replay, "
          f"{len(chains)}/{args.requests} complete span chains",
          flush=True)
    print(f"# stage breakdown (ms): " + ", ".join(
        f"{st} p50={table[st]['p50']:.1f} p99={table[st]['p99']:.1f}"
        for st in ("queue_ms", "stalled_ms", "prefill_ms",
                   "decode_ms")), flush=True)
    print(f"# wrote {args.trace} ({n_events} events) + {jsonl}",
          flush=True)
    return {"tok_per_s_off": ref["decoded_tok_per_s"],
            "tok_per_s_on": rt["decoded_tok_per_s"],
            "regression_pct": regression,
            "host_blocks_off": hb_off,
            "host_blocks_on": hb_on,
            "complete_chains": len(chains),
            "events": n_events,
            "chrome_trace": args.trace,
            "jsonl": jsonl,
            "stage_ms": table}


def run_speculative_bench(args) -> None:
    """The speculative-decoding benchmark: one bursty decode-heavy
    stream against a draft-k/verify-1 server and a plain-decode server
    built from identical params. Asserts bitwise token identity (greedy
    verification is exact by construction — this is the end-to-end
    check of that claim), a decoded-tokens/sec speedup over the plain
    reference, and that *neither* server minted a single executable
    after warmup (``jit_cache_entries`` pinned across the measured
    run — speculation must ride the bounded ladder, not grow it)."""
    k = args.speculate_k
    max_len = 128
    t0 = time.time()
    spec, bench, names = build_server(
        args.n_per_dataset, args.epochs, args.max_batch, args.placement,
        args.executor, args.kv, check_every=args.check_invariants,
        max_len=max_len, speculate_k=k, draft=args.draft)
    plain, _, _ = build_server(
        args.n_per_dataset, args.epochs, args.max_batch, args.placement,
        args.executor, args.kv, check_every=args.check_invariants,
        max_len=max_len)
    print(f"# speculative servers up in {time.time()-t0:.1f}s "
          f"(k={k}, draft={args.draft}, kv={args.kv}, "
          f"placement={args.placement}, executor={args.executor})",
          flush=True)

    # warmup, two layers: (1) compile every reachable ladder point
    # deterministically — measured-pass admission shapes are timing-
    # dependent, so only an exhaustive sweep lets the bench pin the jit
    # caches exactly; (2) two passes of the identical measured stream,
    # which converge the speculative server's engine-level draft state
    # (the online bigram table keeps learning the target experts' greedy
    # transitions across laps — drafting chains of learned successors
    # needs the *successor's* successor known too) and populate the
    # paged prefix cache both measured passes will hit the same way.
    wrng = np.random.default_rng(args.seed + 1)
    warm_full_ladder(spec, wrng, hi_bucket=16)
    warm_full_ladder(plain, wrng, hi_bucket=16)
    rng = np.random.default_rng(args.seed)
    reqs = speculative_requests(bench, names, args.requests, rng,
                                max_len=max_len)
    for _lap in range(3):
        run_scenario("bursty", spec, bench, names, args.requests,
                     args.rate, args.seed, reqs=reqs)
        run_scenario("bursty", plain, bench, names, args.requests,
                     args.rate, args.seed, reqs=reqs)
    print("# warmup done (full ladder + 3 stream laps)", flush=True)

    cache0_spec = total_jit_cache_entries(spec)
    cache0_plain = total_jit_cache_entries(plain)
    got, want = {}, {}
    print(_CSV_HEADER)
    r = run_scenario("bursty", spec, bench, names, args.requests,
                     args.rate, args.seed, reqs=reqs, collect=got)
    print(_csv_row(r, args), flush=True)
    rp = run_scenario("bursty", plain, bench, names, args.requests,
                      args.rate, args.seed, reqs=reqs, collect=want)
    rp["scenario"] = "bursty-plain"
    print(_csv_row(rp, args), flush=True)

    sstats = spec.scheduler.speculative_stats()
    speedup = (r["decoded_tok_per_s"]
               / max(rp["decoded_tok_per_s"], 1e-9))
    print(f"# decoded tok/s: {r['decoded_tok_per_s']:.1f} speculative "
          f"vs {rp['decoded_tok_per_s']:.1f} plain "
          f"({speedup:.2f}x)", flush=True)
    print(f"# acceptance: {sstats['tokens_accepted']}/"
          f"{sstats['tokens_drafted']} drafted tokens "
          f"({sstats['acceptance_rate']:.3f}) over "
          f"{sstats['verify_steps']} verify steps, "
          f"{sstats['spec_fallback_waves']} gate-blocked waves",
          flush=True)

    diverged = [u for u in want if got.get(u) != want[u]]
    assert not diverged, (
        f"speculative server diverged from plain decode on uids "
        f"{diverged[:5]} (of {len(diverged)}) — greedy verification "
        "must be bitwise exact")
    assert total_jit_cache_entries(spec) == cache0_spec, (
        f"speculative server minted executables in steady state: "
        f"{total_jit_cache_entries(spec)} != {cache0_spec}")
    assert total_jit_cache_entries(plain) == cache0_plain, (
        f"plain server minted executables in steady state: "
        f"{total_jit_cache_entries(plain)} != {cache0_plain}")
    assert_bounded_compiles(spec)
    assert_bounded_compiles(plain)
    assert speedup > 1.5, (
        f"speculative decode speedup {speedup:.2f}x <= 1.5x the plain "
        "reference on the bursty decode-heavy stream")
    if args.accept_floor > 0:
        assert sstats["acceptance_rate"] >= args.accept_floor, (
            f"draft acceptance rate {sstats['acceptance_rate']:.3f} "
            f"below the recorded floor {args.accept_floor} — the "
            "draft has regressed against the target experts")
    trace_block = None
    if args.trace:
        trace_block = _traced_lap(args, spec, bench, names, reqs, r)
    if args.json:
        payload = {"workload": "speculative",
                   "placement": args.placement,
                   "executor": args.executor, "kv": args.kv,
                   "speculate_k": k, "draft": args.draft,
                   "max_len": max_len, "requests": args.requests,
                   "rate": args.rate, "seed": args.seed,
                   "scenarios": [r, rp],
                   "speculative": sstats,
                   "speedup_decoded_tok_per_s": speedup,
                   "acceptance_floor": args.accept_floor,
                   "token_identity": True,
                   "jit_cache_stable": True}
        if trace_block is not None:
            payload["trace"] = trace_block
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="mean arrival rate, req/s of virtual time")
    ap.add_argument("--n-per-dataset", type=int, default=600)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--placement", choices=("per-device", "banked"),
                    default="per-device",
                    help="per-device: one ExpertEngine per expert (PR 1); "
                         "banked: plan_placement over a mesh expert axis")
    ap.add_argument("--executor", choices=("serial", "overlapped"),
                    default="overlapped",
                    help="serial: blocking per-tick reference dispatch; "
                         "overlapped: enqueue all shards' work, one "
                         "batched host transfer per wave per step")
    ap.add_argument("--kv", choices=("ring", "paged"), default="ring",
                    help="KV cache layout: ring = dense per-wave "
                         "buffers (reference); paged = per-shard page "
                         "pool with refcounted shared-prefix reuse")
    ap.add_argument("--workload",
                    choices=("standard", "shared-prefix", "long-prompt",
                             "bursty"),
                    default="standard",
                    help="standard: uniform/skewed/bursty grid; "
                         "shared-prefix: cohort traffic re-sending the "
                         "same prompts (asserts prefill-compute savings "
                         "when --kv paged); long-prompt: mixed traffic "
                         "with whale prompts, chunked vs monolithic "
                         "prefill (asserts token identity and a bounded "
                         "short-request decode tail; implies --kv paged); "
                         "bursty: the speculative comparison bench — one "
                         "bursty decode-heavy stream, draft-k/verify-1 "
                         "vs plain decode (asserts token identity, "
                         ">1.5x decoded tok/s, zero steady-state "
                         "recompiles; requires --speculate-k)")
    ap.add_argument("--chunk-len", type=int, default=0,
                    help="prefill chunk length for the long-prompt "
                         "workload (0 = the default 32); must divide "
                         "the length buckets above it")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="prompt tokens of pending chunks each shard "
                         "may dispatch per scheduler step (0 = one "
                         "chunk_len per step for long-prompt)")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="draft tokens proposed per wave per tick "
                         "(0 = no speculation); the target verifies "
                         "the whole k+1 window in one dispatch")
    ap.add_argument("--draft", choices=("mlp", "table", "always-wrong"),
                    default="table",
                    help="draft model for --speculate-k: mlp = the "
                         "resident MLP baseline scoring token "
                         "embeddings; table = a per-expert bigram "
                         "table distilled online from verified greedy "
                         "transitions; always-wrong = adversarial "
                         "lower bound (every draft rejected)")
    ap.add_argument("--accept-floor", type=float, default=0.0,
                    help="fail the bursty speculative bench if the "
                         "draft acceptance rate lands below this "
                         "(0 = record only); CI pins the recorded "
                         "floor here")
    ap.add_argument("--hub", action="store_true",
                    help="serve a long-tail expert catalog through an "
                         "ExpertHub: --n-experts catalogued, --resident "
                         "device slots, Zipf traffic, token-identity "
                         "asserted against a fully-resident baseline")
    ap.add_argument("--n-experts", type=int, default=64,
                    help="hub catalog size (with --hub)")
    ap.add_argument("--resident", type=int, default=8,
                    help="hub device bank slots (with --hub)")
    ap.add_argument("--alpha", type=float, default=1.1,
                    help="Zipf exponent for the hub workload")
    ap.add_argument("--store", default=None,
                    help="expert checkpoint store dir for --hub "
                         "(default: a temp dir; every expert is "
                         "checkpointed cold so staging is real I/O)")
    ap.add_argument("--json", metavar="OUT", default=None,
                    help="also write machine-readable results (per-"
                         "scenario metrics + corrected compile counts + "
                         "sync counters) to this path")
    ap.add_argument("--trace", metavar="OUT", default=None,
                    help="(bursty speculative workload) run one extra "
                         "lap of the identical stream on the warm "
                         "speculative server with lifecycle tracing on, "
                         "write a Chrome trace_event JSON to OUT (and a "
                         "greppable OUT + 'l' JSONL sibling), assert "
                         "host_blocks parity with the tracing-off lap + "
                         "one complete per-request span chain, and add "
                         "a per-request stage breakdown to --json")
    ap.add_argument("--check-invariants", type=int, default=0,
                    metavar="N",
                    help="run the concurrency-gate conservation sweep "
                         "(PagePool.check + hub state machine + pin "
                         "accounting) every N scheduler steps; 0 = off")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host CPU devices (multi-device dry-run "
                         "for the banked placement path); 0 = leave the "
                         "platform's real device count")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    if args.requests < 1:
        ap.error("--requests must be >= 1")
    if args.rate <= 0:
        ap.error("--rate must be > 0")
    if args.devices:
        # must land before jax initialises its backend (first computation
        # happens inside build_server, so this is early enough)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    if args.trace and (args.hub or args.workload != "bursty"):
        print("# --trace is wired to the bursty speculative bench "
              "only; ignoring", flush=True)
        args.trace = None

    if args.hub:
        if args.requests < args.n_experts:
            ap.error(f"--hub needs --requests >= --n-experts "
                     f"({args.n_experts}): the stream starts with a "
                     "catalog sweep so every expert is served")
        if args.resident < 1 or args.resident > args.n_experts:
            ap.error("--resident must be in [1, --n-experts]")
        run_hub_bench(args)
        return

    if args.workload == "long-prompt":
        if args.kv != "paged":
            print("# long-prompt requires the paged layout; "
                  "forcing --kv paged", flush=True)
            args.kv = "paged"
        run_long_prompt_bench(args)
        return

    if args.workload == "bursty":
        if args.speculate_k < 1:
            ap.error("--workload bursty is the speculative comparison "
                     "bench; pass --speculate-k >= 1")
        run_speculative_bench(args)
        return

    from repro.serve import Request

    t0 = time.time()
    server, bench, names = build_server(args.n_per_dataset, args.epochs,
                                        args.max_batch, args.placement,
                                        args.executor, args.kv,
                                        check_every=args.check_invariants)
    print(f"# server up in {time.time()-t0:.1f}s "
          f"({len(names)} experts, placement={args.placement}, "
          f"executor={args.executor}, kv={args.kv})", flush=True)

    # warmup: populate jit caches so scenario 1 isn't charged compiles
    rng = np.random.default_rng(1)
    warm = [Request(uid=-(k + 1),
                    features=bench[names[k % len(names)]]["client_a"][0][k],
                    prompt=rng.integers(0, 100, size=40),
                    max_new_tokens=4) for k in range(len(names))]
    server.serve(warm)
    print("# warmup done", flush=True)

    print(_CSV_HEADER)
    results = []
    scenarios = (("shared-prefix", "uniform")
                 if args.workload == "shared-prefix"
                 else ("uniform", "skewed", "bursty"))
    for scenario in scenarios:
        r = run_scenario(scenario, server, bench, names,
                         args.requests, args.rate, args.seed)
        results.append(r)
        print(_csv_row(r, args), flush=True)
    pf = total_prefill_tokens(server)
    totals = {
        # compile counts are *real* XLA executables (per-wrapper
        # _cache_size sums), not jit-wrapper creations
        "prefill_compiles": total_prefill_compiles(server),
        "decode_compiles": total_decode_compiles(server),
        "host_blocks": total_host_blocks(server),
        "tokens_generated": total_tokens(server),
        "host_blocks_per_tok": (total_host_blocks(server)
                                / max(total_tokens(server), 1)),
        "prefill_tokens_computed": pf[0],
        "prefill_tokens_submitted": pf[1],
    }
    assert_bounded_compiles(server)
    print(f"# total prefill compiles (warmup + scenarios): "
          f"{totals['prefill_compiles']}", flush=True)
    print(f"# host blocks per decoded token (warmup + scenarios): "
          f"{totals['host_blocks_per_tok']:.3f}", flush=True)
    if args.workload == "shared-prefix":
        sp = results[0]
        print(f"# shared-prefix: {sp['prefill_tokens_computed']} prefill "
              f"tokens computed for {sp['prefill_tokens_submitted']} "
              "submitted", flush=True)
        if args.kv == "paged":
            # the ISSUE's acceptance criterion: cohort prompts must be
            # prefilled once, not per request
            assert (sp["prefill_tokens_computed"]
                    < sp["prefill_tokens_submitted"]), (
                "paged KV showed no prefill savings on the "
                "shared-prefix workload")
    if args.json:
        payload = {"placement": args.placement, "executor": args.executor,
                   "kv": args.kv, "workload": args.workload,
                   "devices": args.devices, "requests": args.requests,
                   "rate": args.rate, "seed": args.seed,
                   "scenarios": results, "totals": totals}
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
