"""Admission queue + continuous micro-batching scheduler (Fig. 2 as a
serving system).

Life of a request:

  submit() -> Router.route (fingerprint LRU + Pallas scoring, shard ids
              from the placement plan)
           -> per-expert FIFO queue, sub-bucketed by prompt-length bucket
  step()   -> the dispatch executor runs one round over all shards:
              admission (per *shard*, pick one length bucket — fullest
              wins, with age-based promotion so sparse buckets can't
              starve — and admit one dispatch group; a banked shard
              prefills every member expert's micro-batch in a single
              call), then decode (every shard with resident groups
              advances one token; one ``tick`` per bank, not per
              expert), then engine harvest. With the default
              ``overlapped`` executor every prefill and decode tick is
              *enqueued* before anything blocks — sampled tokens stay
              on device and the host blocks at most once per wave, in
              the batched harvest transfer — so prefill of one shard
              overlaps decode of another. ``executor="serial"`` keeps
              the blocking per-tick reference behaviour.
           -> harvest: finished rows become Responses immediately,
              demuxed through the shard's expert list
  drain()  -> step() until all queues and engines are empty

Because queues persist across calls, requests submitted in *different*
``submit`` calls coalesce into the same micro-batch — the continuous
part — and because shapes are snapped to the engine's buckets, a mixed
traffic stream compiles a bounded set of executables no matter how many
distinct (prompt length, batch, max_new) combinations arrive.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.matcher import ExpertMatcher
from ..core.registry import ExpertRegistry
from ..obs.metrics import Counter, Histogram, MetricsRegistry
from ..obs.trace import NULL_TRACER
from .core import DispatchExecutor, get_executor
from .engine import ExpertEngine
from .hub import ExpertHub, HubMember, NotResident
from .kvcache import PagePoolExhausted
from .placement import BankMember, PlacementPlan, Shard
from .router import PrefixLRU, Router


@dataclasses.dataclass
class Request:
    uid: int
    features: np.ndarray            # (784,) matcher fingerprint
    prompt: np.ndarray              # (S,) int32 tokens
    max_new_tokens: int = 8
    expert: Optional[int] = None    # pre-routed: skip the matcher (the
    #                                 paper's repeat clients know their
    #                                 expert; also the hub bench path)


@dataclasses.dataclass
class Response:
    uid: int
    expert: str
    fine_class: int
    tokens: np.ndarray
    coarse_scores: Optional[np.ndarray] = None
    shard: int = -1                 # placement shard that served the row


@dataclasses.dataclass
class SchedulerConfig:
    max_batch: int = 16             # micro-batch row cap (per expert)
    max_queue: int = 4096           # admission queue cap (backpressure)
    promote_after: int = 4          # rounds a waiting bucket may be
    #                                 skipped before it wins admission
    check_every: int = 0            # >0: run check_invariants() every N
    #                                 steps (PagePool.check + hub state
    #                                 machine + pin conservation) — the
    #                                 sanitizer's invariants under real
    #                                 traffic (serving_bench
    #                                 --check-invariants)
    prefill_tokens_per_step: int = 0
    #                                 per-shard prompt-token budget for
    #                                 pending prefill chunks each step
    #                                 (0 = unbounded); at least one chunk
    #                                 always dispatches, so whales make
    #                                 progress while bounded budgets keep
    #                                 co-resident decode latency flat
    speculate_k: Optional[int] = None
    #                                 speculative-decoding contract:
    #                                 None inherits whatever each engine
    #                                 was built with; an int asserts
    #                                 every tickable shard engine was
    #                                 built with exactly that
    #                                 speculate_k (engines own the
    #                                 verify executables, so the
    #                                 scheduler can only validate, not
    #                                 retrofit)


@dataclasses.dataclass(frozen=True)
class SchedulerStats:
    """Immutable snapshot of the scheduler's counters (one field per
    former loose-dict key). Read it as attributes; ``as_dict()`` is the
    shape the unified metrics registry snapshots. The live counters are
    ``repro.obs`` Counters on the scheduler — this type is only ever a
    point-in-time copy, so callers can hold one across a step without
    it mutating under them."""
    submitted: int = 0
    rejected: int = 0
    batches: int = 0
    ticks: int = 0
    responses: int = 0
    promotions: int = 0
    orphaned: int = 0
    kv_stalls: int = 0
    resident_stalls: int = 0
    invariant_checks: int = 0

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _Pending:
    req: Request
    fine: int
    scores: np.ndarray
    shard: int = -1
    seq: int = 0                    # submit order, for age promotion
    prefix_key: bytes = b""         # prompt-prefix cohort key (PrefixLRU)
    expert: int = -1                # routed expert (hub demux + unpin)
    # lifecycle accounting (tracer clock, seconds): queue time is
    # submit→admit minus the stalled share; ``stall_since`` is open
    # while the row is parked on NotResident / PagePoolExhausted
    # backpressure
    trace: int = 0                  # trace id (0 when tracing is off)
    t_submit: float = 0.0
    t_admit: float = 0.0
    stalled_s: float = 0.0
    stall_since: Optional[float] = None


class Scheduler:
    """Routes, queues, batches and ticks a fleet of expert shards."""

    def __init__(self, router: Optional[Router],
                 registry: ExpertRegistry,
                 config: Optional[SchedulerConfig] = None,
                 placement: Optional[PlacementPlan] = None,
                 executor: "str | DispatchExecutor" = "overlapped",
                 hub: Optional[ExpertHub] = None,
                 tracer=None):
        self.router = router
        self.registry = registry
        self.config = config or SchedulerConfig()
        self.placement = placement
        self.hub = hub
        self.executor = get_executor(executor)
        if hub is not None:
            if placement is not None:
                raise ValueError("hub and placement are exclusive: the "
                                 "hub owns its own slot bank")
            if len(hub) != len(registry):
                raise ValueError(
                    f"hub catalog ({len(hub)} experts) does not match "
                    f"the registry ({len(registry)}); build the "
                    "registry via hub.build_registry()")
            for e in range(len(registry)):
                be = registry[e].backend
                if not (isinstance(be, HubMember) and be.hub is hub
                        and be.expert == e):
                    # same contract as the placement branch's
                    # BankMember check: a same-length foreign registry
                    # would silently serve through the hub's slots
                    # under the wrong expert names / bucket ladders
                    raise ValueError(
                        f"registry entry {e} ({registry[e].name!r}) is "
                        "not this hub's HubMember; build the registry "
                        "via hub.build_registry()")
            # one dispatch-group shard over the whole catalog: every
            # wave is served by the hub's slot bank, groups keyed by
            # device slot rather than registry index
            self.shards = [Shard(sid=0,
                                 experts=tuple(range(len(registry))),
                                 bank=hub.bank)]
        elif placement is not None:
            # the plan must describe THIS registry: plan_placement
            # rebound each banked expert's backend to a BankMember of
            # its shard's bank — a stale plan for another registry
            # would silently serve with the wrong experts' params
            missing = set(range(len(registry))) - set(placement.shard_of)
            if missing:
                raise ValueError(
                    f"placement plan does not cover experts "
                    f"{sorted(missing)} (registry grown after "
                    f"plan_placement?); re-plan on this registry")
            for shard in placement.shards:
                if not shard.banked:
                    continue
                for local, e in enumerate(shard.experts):
                    be = registry[e].backend if e < len(registry) else None
                    if not (isinstance(be, BankMember)
                            and be.bank is shard.bank
                            and be.local == local):
                        raise ValueError(
                            f"placement plan does not match registry at "
                            f"expert {e}; re-plan with plan_placement "
                            f"on this registry")
            self.shards = list(placement.shards)
        else:  # PR 1 behaviour: every expert is its own dispatch group
            for e in range(len(registry)):
                if isinstance(registry[e].backend, BankMember):
                    raise ValueError(
                        f"expert {registry[e].name!r} is bank-placed "
                        "(plan_placement rebound its backend to a "
                        "BankMember); pass that PlacementPlan via "
                        "placement=")
            self.shards = [Shard(sid=e, experts=(e,))
                           for e in range(len(registry))]
        self._shard_of = {e: s.sid for s in self.shards for e in s.experts}
        if self.config.speculate_k is not None:
            want = int(self.config.speculate_k)
            for shard in self.shards:
                eng = self._shard_engine(shard)
                if eng is None:
                    continue
                got = getattr(eng.core, "speculate_k", 0)
                if got != want:
                    raise ValueError(
                        f"SchedulerConfig.speculate_k={want} but shard "
                        f"{shard.sid} engine was built with "
                        f"speculate_k={got}; rebuild its engines with "
                        "the matching speculate_k")
        # queues[expert][len_bucket] -> FIFO of _Pending
        self.queues: Dict[int, Dict[int, collections.deque]] = \
            collections.defaultdict(lambda: collections.defaultdict(
                collections.deque))
        self.n_queued = 0
        self._seq = 0
        self._skips: Dict[Tuple[int, int], int] = \
            collections.defaultdict(int)   # (shard, bucket) skip rounds
        self._counters: Dict[str, Counter] = {
            f.name: Counter() for f in dataclasses.fields(SchedulerStats)}
        self._steps = 0
        self._done: List[Response] = []
        self._meta: Dict[int, _Pending] = {}   # uid -> routing info
        # prompt-prefix cohort detection: keyed at the page granularity
        # of the first paged engine (8 when every shard rings)
        page = next((self._shard_engine(s).core.page for s in self.shards
                     if self._paged_shard(s)), 8)
        self.prefix_lru = PrefixLRU(page=page)
        # latency attribution — always on (two perf_counter stamps per
        # request, no numpy): queue_ms excludes the stalled share so the
        # two histograms decompose wait time the way the bench's stage
        # table reports it
        self._h_queue = Histogram()
        self._h_stalled = Histogram()
        self.tracer = NULL_TRACER
        self.bind_tracer(tracer)
        self.obs = self._build_metrics()

    @property
    def stats(self) -> SchedulerStats:
        """Frozen point-in-time snapshot of the scheduler counters."""
        return SchedulerStats(**{k: c.value
                                 for k, c in self._counters.items()})

    def bind_tracer(self, tracer) -> None:
        """Install a lifecycle tracer here, on every shard engine core
        and on the hub (None restores the disabled NULL_TRACER). Safe
        between steps; rows already in flight keep trace id 0."""
        self.tracer = tracer if tracer is not None else NULL_TRACER
        for core in self._cores():
            core.bind_tracer(self.tracer)
        if self.hub is not None:
            self.hub.bind_tracer(self.tracer)
        if self.router is not None:
            self.router.tracer = self.tracer

    def _cores(self) -> List[Any]:
        """The engine core behind each shard that has one."""
        cores = (getattr(self._shard_engine(s), "core", None)
                 for s in self.shards)
        return [c for c in cores if c is not None]

    def _dispatches_ahead(self) -> int:
        """Engine dispatches not yet covered by a completed sync, summed
        over the engines: on one chip's in-order stream, an upper bound
        on what a blocking transfer issued now waits behind."""
        return sum(core.ahead for core in self._cores())

    def _build_metrics(self) -> MetricsRegistry:
        """The unified snapshot tree: scheduler counters + latency
        histograms, every shard engine's ``EngineStats``, every paged
        shard's page-pool counters, the router and (when present) the
        hub's per-expert metrics — one ``snapshot()`` call is the whole
        mesh's state."""
        obs = MetricsRegistry()
        obs.register("scheduler", lambda: self.stats.as_dict())
        obs.register("scheduler/latency/queue_ms", self._h_queue)
        obs.register("scheduler/latency/stalled_ms", self._h_stalled)
        obs.register("executor", lambda: {"name": self.executor.name})
        for shard in self.shards:
            eng = self._shard_engine(shard)
            if eng is None:
                continue
            label = f"shard{shard.sid}"
            obs.register(f"engines/{label}",
                         (lambda e=eng: e.stats.as_dict()))
            core = getattr(eng, "core", None)
            if core is not None and core.pool is not None:
                obs.register(f"kv/{label}", core.pool.telemetry)
            if core is not None and core.draft is not None:
                obs.register(f"engines/{label}/draft",
                             core.draft.describe())
        if self.router is not None:
            obs.register("router", self._router_metrics)
        if self.hub is not None:
            obs.register("hub", self.hub.metrics_snapshot)
        return obs

    def _router_metrics(self) -> Dict[str, Any]:
        r = self.router
        return {**r.stats, "expert_hits": dict(r.expert_hits),
                "prefix_lru": dict(self.prefix_lru.stats)}

    def _paged_shard(self, shard: Shard) -> bool:
        eng = self._shard_engine(shard)
        return eng is not None and getattr(eng, "kv_layout", "ring") == \
            "paged"

    def speculative_stats(self) -> Dict[str, Any]:
        """Aggregate speculative-decoding counters over every tickable
        shard — what the bench records and the CI acceptance-rate floor
        is asserted against."""
        drafted = accepted = verifies = fallback = 0
        for shard in self.shards:
            eng = self._shard_engine(shard)
            if eng is None:
                continue
            st = eng.stats
            drafted += st.tokens_drafted
            accepted += st.tokens_accepted
            verifies += st.verify_steps
            fallback += st.spec_fallback_waves
        return {"tokens_drafted": drafted, "tokens_accepted": accepted,
                "verify_steps": verifies,
                "spec_fallback_waves": fallback,
                "acceptance_rate": accepted / drafted if drafted
                else 0.0}

    # -- admission -------------------------------------------------------
    def submit(self, requests: Sequence[Request]) -> int:
        """Route and enqueue; returns how many were admitted — always a
        prefix of ``requests``, so callers can resubmit the tail later.
        Requests beyond the queue cap are rejected unrouted
        (backpressure). uids must be unique among in-flight requests —
        they key response demultiplexing.

        Requests carrying ``expert=`` are pre-routed: they skip the
        matcher (and are the only kind a router-less hub scheduler
        accepts) but still feed the popularity counter the hub's
        eviction policy reads.
        """
        if not requests:
            return 0
        batch_seen = set()
        for r in requests:
            if r.uid in self._meta or r.uid in batch_seen:
                raise ValueError(f"duplicate in-flight uid {r.uid}")
            batch_seen.add(r.uid)
        room = max(self.config.max_queue - self.n_queued, 0)
        self._counters["rejected"].inc(
            len(requests) - min(len(requests), room))
        requests = requests[:room]
        if not requests:
            return 0
        miss = [i for i, r in enumerate(requests) if r.expert is None]
        if miss and self.router is None:
            raise ValueError(
                "scheduler has no router: every request must be "
                "pre-routed (Request.expert set)")
        routed = None
        if miss:
            args = {}
            if self.tracer.enabled:
                args = {"rows": len(miss),
                        "uids": [requests[i].uid for i in miss],
                        "ahead": self._dispatches_ahead()}
            with self.tracer.span("route", **args):
                routed = self.router.route(np.stack(
                    [requests[i].features for i in miss]))
        routed_at = {i: j for j, i in enumerate(miss)}
        top_k = routed.coarse.shape[1] if routed is not None else 1
        admitted = 0
        for i, r in enumerate(requests):
            if r.expert is not None:
                e, fine = int(r.expert), 0
                if not 0 <= e < len(self.registry):
                    raise ValueError(f"pre-routed expert {e} out of "
                                     f"range [0, {len(self.registry)})")
                scores = np.zeros(top_k, np.float32)
                sid = self._shard_of.get(e, -1)
                # router.route counts its own rows; pre-routed hits go
                # through the hub's locked mutation point — the shared
                # popularity Counter races with the eviction ranking
                # otherwise (races.py R001; the sanitizer's lost-update
                # seed demonstrates the dropped increments)
                if self.hub is not None:
                    self.hub.note_hit(e)
                elif self.router is not None:
                    self.router.expert_hits[e] += 1
            else:
                j = routed_at[i]
                e = int(routed.coarse[j, 0])
                fine = int(routed.fine[j])
                scores = routed.coarse_score[j]
                # routed.shard is the placement-aware router's demux
                # contract (identical to _shard_of when both come from
                # one plan); the local map covers routers wired without
                # a placement
                sid = (int(routed.shard[j]) if routed.shard is not None
                       else self._shard_of.get(e, -1))
            engine = self.registry[e].backend
            sb = (engine.pad_shape(1, len(r.prompt))[1]
                  if hasattr(engine, "pad_shape") else len(r.prompt))
            self._seq += 1
            p = _Pending(r, fine, scores, shard=sid, seq=self._seq,
                         prefix_key=self.prefix_lru.observe(r.prompt),
                         expert=e, t_submit=self.tracer.now())
            if self.tracer.enabled:
                p.trace = self.tracer.next_id()
                self.tracer.bind_uid(r.uid, p.trace)
                self.tracer.event("request.submit", uid=r.uid,
                                  trace=p.trace, expert=e, shard=sid,
                                  prompt_len=len(r.prompt),
                                  max_new=int(r.max_new_tokens))
            self.queues[e][sb].append(p)
            self._meta[r.uid] = p
            self.n_queued += 1
            admitted += 1
        self._counters["submitted"].inc(admitted)
        return admitted

    # -- one scheduling round -------------------------------------------
    def step(self) -> List[Response]:
        # enqueue_span: the round ends when the host may go on, not when
        # the ticks it enqueued complete; its engine.enqueue and
        # engine.sync children split it into dispatch and device wait
        with self.tracer.enqueue_span("step"):
            self.executor.run_step(self)
            self._harvest()
        out, self._done = self._done, []
        self._counters["responses"].inc(len(out))
        self._steps += 1
        if (self.config.check_every
                and self._steps % self.config.check_every == 0):
            self.check_invariants()
        return out

    def drain(self) -> List[Response]:
        out: List[Response] = []
        while self.has_work:
            out.extend(self.step())
        return out

    @property
    def has_work(self) -> bool:
        if self.n_queued:
            return True
        # has_pending, not n_active: an interleaved generate() call may
        # tick a scheduler group to completion and park its rows in the
        # engine's finished buffer — they still need a harvest step
        return any(eng is not None and eng.has_pending
                   for eng in map(self._shard_engine, self.shards))

    def check_invariants(self) -> None:
        """The sanitizer's conservation invariants, under real traffic:
        page-pool refcount books balance (``PagePool.check``), the hub
        catalog/slot state machine is legal (``ExpertHub.check``), and
        residency pins conserve — every pin is held by exactly one
        in-flight admitted row, so pins == in-flight - queued. Enabled
        every N steps via ``SchedulerConfig.check_every`` (the bench's
        ``--check-invariants`` flag)."""
        for shard in self.shards:
            eng = self._shard_engine(shard)
            if eng is not None and \
                    getattr(eng, "kv_layout", "ring") == "paged":
                eng.core.pool.check()
        if self.hub is not None:
            self.hub.check()
            pins = self.hub.total_pins()
            in_flight = len(self._meta) - self.n_queued
            assert pins == in_flight, (
                f"pin conservation broke: hub holds {pins} pins but "
                f"{in_flight} rows are admitted and unharvested")
        self._counters["invariant_checks"].inc()

    def close(self) -> None:
        """Shut down background machinery (the hub's staging worker);
        idempotent, safe without a hub."""
        if self.hub is not None:
            self.hub.close()

    # -- internals -------------------------------------------------------
    def _shard_engine(self, shard: Shard):
        """The tickable engine behind a shard (bank or ExpertEngine);
        None for stub/legacy backends that complete at admission."""
        if shard.banked:
            return shard.bank
        engine = self.registry[shard.experts[0]].backend
        return engine if isinstance(engine, ExpertEngine) else None

    def _pick_bucket(self, shard: Shard) -> Optional[int]:
        """Length bucket this shard admits this round.

        Fullest bucket (summed over member experts) wins — best padding
        efficiency — unless a non-empty bucket has been skipped
        ``promote_after`` rounds in a row: then the starving bucket with
        the oldest waiting head wins. Without promotion, sustained
        traffic concentrated in one bucket starves sparse buckets
        indefinitely (the fullest-first rule never lets them drain).
        """
        counts: Dict[int, int] = collections.defaultdict(int)
        oldest: Dict[int, int] = {}
        for e in shard.experts:
            for sb, q in self.queues[e].items():
                if q:
                    counts[sb] += len(q)
                    oldest[sb] = min(oldest.get(sb, q[0].seq), q[0].seq)
        # prune drained buckets' counters: legacy backends key queues by
        # raw prompt length, so without pruning _skips would grow one
        # permanent entry per distinct length for the server's lifetime
        for key in [k for k in self._skips if k[0] == shard.sid
                    and k[1] not in counts]:
            del self._skips[key]
        if not counts:
            return None
        starving = [sb for sb in counts
                    if self._skips[(shard.sid, sb)]
                    >= self.config.promote_after]
        if starving:
            sb = min(starving, key=lambda b: oldest[b])
            self._counters["promotions"].inc()
        else:
            sb = max(counts, key=lambda b: (counts[b], -oldest[b]))
        for other in counts:
            if other != sb:
                self._skips[(shard.sid, other)] += 1
        self._skips.pop((shard.sid, sb), None)
        return sb

    def _pop(self, e: int, sb: int, cap: int,
             prefix_group: bool = False) -> List[_Pending]:
        """Take up to ``cap`` rows from one bucket queue.

        Plain FIFO normally; with ``prefix_group`` (paged shards) the
        head's prompt-prefix cohort is pulled forward so prefix-sharing
        rows land in the *same wave* — that co-residency is what lets
        the paged engine deduplicate their prefill and share pages.
        Non-matching rows keep their relative order and still fill any
        remaining capacity, and bucket-level age promotion bounds how
        long a displaced row can wait.
        """
        q = self.queues[e][sb]
        if prefix_group and len(q) > 1 and cap > 1:
            key = q[0].prefix_key
            idxs = [i for i, p in enumerate(q)
                    if p.prefix_key == key][:cap]
            if len(idxs) < cap:
                fill = [i for i, p in enumerate(q)
                        if p.prefix_key != key][:cap - len(idxs)]
                idxs = sorted(idxs + fill)
            picked = set(idxs)
            take = [q[i] for i in idxs]
            rest = [q[i] for i in range(len(q)) if i not in picked]
            q.clear()
            q.extend(rest)
        else:
            take = [q.popleft() for _ in range(min(len(q), cap))]
        self.n_queued -= len(take)
        if not q:
            # drop drained buckets: legacy backends key them by raw
            # prompt length, so keeping empties would grow the dict (and
            # _pick_bucket's scan) for the server's lifetime
            del self.queues[e][sb]
        return take

    def _requeue(self, e: int, sb: int, take: List[_Pending]) -> None:
        """Put popped rows back at the queue front (order preserved) —
        the page pool could not host their wave this round."""
        q = self.queues[e][sb]
        for p in reversed(take):
            q.appendleft(p)
        self.n_queued += len(take)

    def _note_stall(self, event: str, e: int, sb: int) -> None:
        """Open the stall clock on every parked row in queue (e, sb)
        that isn't already stalled, and emit one ``event`` (``hub.park``
        or ``kv.requeue``) covering exactly those rows — so a row parked
        across many rounds produces one event and one stall interval,
        not one per round."""
        q = self.queues[e].get(sb)
        if not q:
            return
        t = self.tracer.now()
        fresh = [p for p in q if p.stall_since is None]
        for p in fresh:
            p.stall_since = t
        if fresh and self.tracer.enabled:
            self.tracer.event(event, expert=e, rows=len(fresh),
                              uids=[p.req.uid for p in fresh],
                              traces=[p.trace for p in fresh])

    def _mark_admitted(self, takes: Sequence[List[_Pending]], sid: int,
                       sb: int) -> None:
        """Close stall clocks and stamp admission time on every row of
        a successfully admitted dispatch group."""
        t = self.tracer.now()
        rows = [p for take in takes for p in take]
        for p in rows:
            if p.stall_since is not None:
                p.stalled_s += t - p.stall_since
                p.stall_since = None
            p.t_admit = t
        if rows and self.tracer.enabled:
            self.tracer.event("request.admit", shard=sid, bucket=sb,
                              uids=[p.req.uid for p in rows],
                              traces=[p.trace for p in rows])

    def _finish_row(self, p: _Pending) -> None:
        """Close the row's lifecycle accounting at response emission:
        fold any still-open stall, decompose the wait into the
        queue/stalled histograms (milliseconds) and emit
        ``request.finish`` + release the uid→trace binding."""
        t = self.tracer.now()
        if p.stall_since is not None:
            p.stalled_s += t - p.stall_since
            p.stall_since = None
        admit = p.t_admit if p.t_admit else t
        queue_s = max(admit - p.t_submit - p.stalled_s, 0.0)
        self._h_queue.observe(queue_s * 1e3)
        self._h_stalled.observe(p.stalled_s * 1e3)
        if self.tracer.enabled:
            self.tracer.event(
                "request.finish", uid=p.req.uid, trace=p.trace,
                expert=p.expert, queue_ms=queue_s * 1e3,
                stalled_ms=p.stalled_s * 1e3,
                total_ms=(t - p.t_submit) * 1e3)
            self.tracer.release_uid(p.req.uid)

    def _service_hub(self) -> None:
        """Drive the expert hub's lifecycle one round (no-op without a
        hub): poll staged checkpoints, commit wanted experts into bank
        slots, kick prefetch. Runs at the *head* of every executor
        step, so with the overlapped executor the slot-install
        dispatches are enqueued before this step's decode ticks and
        checkpoint staging overlaps device compute. When nothing is
        resident (no decode to overlap with) the hub blocks on staging
        instead of busy-spinning the drain loop."""
        if self.hub is None:
            return
        idle = not any(eng is not None and eng.n_active
                       for eng in map(self._shard_engine, self.shards))
        self.hub.service(block=idle)

    def _admit_batches(self, *, defer: bool = False) -> None:
        """Issue one dispatch group per shard. With ``defer`` the
        prefills are only enqueued (tokens stay on device; the executor
        harvests once at the end of the step)."""
        for shard in self.shards:
            sb = self._pick_bucket(shard)
            if sb is None:
                continue
            if self.hub is not None:
                self._admit_hub(shard, sb, defer=defer)
            elif shard.banked:
                self._admit_banked(shard, sb, defer=defer)
            else:
                self._admit_single(shard.experts[0], sb, defer=defer)

    def _admit_hub(self, shard: Shard, sb: int, *,
                   defer: bool = False) -> None:
        """One dispatch group over the hub's slot bank: resident
        experts' micro-batches ride the wave keyed by *device slot*;
        a non-resident expert's rows park in their queue (the
        ``NotResident`` outcome — the residency analogue of
        ``PagePoolExhausted`` backpressure) while the hub stages and
        commits it in the background."""
        hub, bank = self.hub, shard.bank
        paged = self._paged_shard(shard)
        cap = min(self.config.max_batch, bank.batch_buckets[-1])
        groups, popped = {}, {}
        stalled = 0
        for e in shard.experts:
            if not self.queues[e].get(sb):
                continue
            try:
                slot = hub.acquire(e)
            except NotResident:
                stalled += 1        # rows stay parked in their queue
                self._note_stall("hub.park", e, sb)
                continue
            take = self._pop(e, sb, cap, prefix_group=paged)
            if not take:
                continue
            hub.pin(e, len(take))
            popped[e] = take
            groups[slot] = ([p.req.uid for p in take],
                            [p.req.prompt for p in take],
                            [p.req.max_new_tokens for p in take])
        if stalled:
            self._counters["resident_stalls"].inc(stalled)
        if not groups:
            return
        try:
            bank.admit(groups, defer=defer)
        except PagePoolExhausted:
            # unwind pops and pins on BOTH exits: the fatal re-raise
            # (pool too small for even one wave) must not strand rows
            # out of their queues or leave residency pins that would
            # make the experts permanently unevictable
            for e, take in popped.items():
                self._requeue(e, sb, take)
                hub.unpin(e, len(take))
            if not bank.n_active:
                raise            # pool too small for even one wave
            self._counters["kv_stalls"].inc()
            for e in popped:
                self._note_stall("kv.requeue", e, sb)
            return
        self._counters["batches"].inc()
        self._mark_admitted(list(popped.values()), shard.sid, sb)

    def _admit_banked(self, shard: Shard, sb: int, *,
                      defer: bool = False) -> None:
        """One dispatch group: every member expert's micro-batch from the
        chosen bucket rides a single BankedEngine prefill. A paged bank
        whose pool cannot host the wave requeues the rows (clean
        backpressure) instead of corrupting resident pages."""
        bank = shard.bank
        paged = self._paged_shard(shard)
        cap = min(self.config.max_batch, bank.batch_buckets[-1])
        groups, popped = {}, {}
        for local, e in enumerate(shard.experts):
            take = self._pop(e, sb, cap, prefix_group=paged)
            if take:
                popped[local] = take
                groups[local] = ([p.req.uid for p in take],
                                 [p.req.prompt for p in take],
                                 [p.req.max_new_tokens for p in take])
        if not groups:
            return
        try:
            bank.admit(groups, defer=defer)
        except PagePoolExhausted:
            if not bank.n_active:
                # no resident wave will ever free pages: the pool is
                # simply too small for a single wave — surface it
                raise
            for local, e in enumerate(shard.experts):
                if local in popped:
                    self._requeue(e, sb, popped[local])
                    self._note_stall("kv.requeue", e, sb)
            self._counters["kv_stalls"].inc()
            return
        self._counters["batches"].inc()
        self._mark_admitted(list(popped.values()), shard.sid, sb)

    def _admit_single(self, e: int, sb: int, *,
                      defer: bool = False) -> None:
        engine = self.registry[e].backend
        name = self.registry[e].name
        cap = self.config.max_batch
        paged = isinstance(engine, ExpertEngine) and \
            engine.kv_layout == "paged"
        if isinstance(engine, ExpertEngine):
            cap = min(cap, engine.batch_buckets[-1])
        take = self._pop(e, sb, cap, prefix_group=paged)
        if not take:
            return
        if isinstance(engine, ExpertEngine):
            try:
                engine.admit([p.req.uid for p in take],
                             [p.req.prompt for p in take],
                             [p.req.max_new_tokens for p in take],
                             defer=defer)
            except PagePoolExhausted:
                if not engine.n_active:
                    raise      # pool too small for even one wave
                self._requeue(e, sb, take)
                self._note_stall("kv.requeue", e, sb)
                self._counters["kv_stalls"].inc()
                return
            self._counters["batches"].inc()
            self._mark_admitted([take], self._shard_of.get(e, -1), sb)
        elif engine is None:
            self._counters["batches"].inc()
            for p in take:
                self._meta.pop(p.req.uid, None)
                self._done.append(self._response(
                    p, name, np.zeros(p.req.max_new_tokens, np.int32)))
                self._finish_row(p)
        else:
            # legacy blocking engines: one padded batch call
            self._counters["batches"].inc()
            m = max(len(p.req.prompt) for p in take)
            toks = np.zeros((len(take), m), np.int32)
            for i, p in enumerate(take):
                toks[i, :len(p.req.prompt)] = p.req.prompt
            gen = np.asarray(engine.generate(
                toks, max(p.req.max_new_tokens for p in take)))
            for i, p in enumerate(take):
                self._meta.pop(p.req.uid, None)
                self._done.append(self._response(
                    p, name, gen[i, :p.req.max_new_tokens]))
                self._finish_row(p)

    def _prefill_chunks(self) -> None:
        """Issue pending prefill chunks of partially-prefilled waves,
        bounded per shard by ``SchedulerConfig.prefill_tokens_per_step``
        (0 = drain). Runs between admission and decode ticks — the
        disaggregation point: a whale prompt admitted with deferred
        chunks spends at most the budget per step, and the decode ticks
        that follow run every step regardless of how much prefill work
        is still queued. A wave only becomes decode-eligible once its
        last chunk lands (chunk cursor tracked FIFO on the wave)."""
        budget = self.config.prefill_tokens_per_step
        for shard in self.shards:
            eng = self._shard_engine(shard)
            if eng is not None and getattr(eng, "core", None) is not None \
                    and eng.core.has_pending_chunks:
                eng.core.prefill_step(budget)

    def _tick_engines(self, *, defer: bool = False) -> None:
        """Advance every shard's resident waves one token. With
        ``defer`` the decode dispatches are only enqueued — no shard's
        tick blocks the host before the next shard's work is issued."""
        for shard in self.shards:
            eng = self._shard_engine(shard)
            if eng is not None and eng.n_active:
                eng.tick(defer=defer)
                self._counters["ticks"].inc()

    def _harvest_engines(self) -> None:
        """One batched device→host transfer per wave (at most): emit
        finished rows into each engine's poll buffer."""
        for shard in self.shards:
            eng = self._shard_engine(shard)
            if eng is not None:
                eng.harvest()

    def _harvest(self) -> None:
        for shard in self.shards:
            eng = self._shard_engine(shard)
            if eng is None:
                continue
            for item in eng.poll():
                if shard.banked:
                    local, uid, toks = item
                else:
                    uid, toks = item
                    local = 0
                if uid not in self._meta and isinstance(uid, tuple):
                    # generate()'s private tuple namespace: a call that
                    # raised mid-flight leaves its group resident, and
                    # its rows eventually surface here with no owner —
                    # drop them (with a stat). Unknown *int* uids stay
                    # a loud KeyError: that's a demux bug, not litter.
                    self._counters["orphaned"].inc()
                    continue
                p = self._meta.pop(uid)
                if self.hub is not None:
                    # hub waves key groups by device slot, whose owner
                    # changes over time — demux through the pending
                    # row's routed expert and release its residency pin
                    # (the slot is evictable once its last pin drops)
                    name = self.registry[p.expert].name
                    self.hub.unpin(p.expert)
                elif shard.banked:
                    name = self.registry[shard.experts[local]].name
                else:
                    name = self.registry[shard.experts[0]].name
                self._done.append(self._response(
                    p, name, toks[:p.req.max_new_tokens]))
                self._finish_row(p)

    def _response(self, p: _Pending, name: str,
                  tokens: np.ndarray) -> Response:
        return Response(uid=p.req.uid, expert=name, fine_class=p.fine,
                        tokens=tokens, coarse_scores=p.scores,
                        shard=p.shard)


class RoutedServer:
    """ExpertMatcher in front of a fleet of expert shards.

    Seed-compatible façade over Router + Scheduler: ``serve`` is
    submit-then-drain, returning responses in request order. Incremental
    users call ``submit``/``step`` directly for continuous batching.
    Pass ``placement`` (from ``serve.placement.plan_placement``) to
    serve banked multi-expert shards instead of one engine per expert,
    and ``executor`` (``"overlapped"`` — the default async dispatch —
    or ``"serial"``, the blocking reference) to pick how each step
    drives its shards; both executors are token-identical.

    Pass ``hub`` (an ``ExpertHub`` whose ``build_registry()`` produced
    ``registry``) for dynamic expert residency: the catalog may be far
    larger than the hub's device slots, non-resident experts park their
    rows while checkpoints stage in the background, and the router's
    per-expert hit counts drive the hub's eviction policy. With a hub,
    ``matcher=None`` is allowed when every request is pre-routed
    (``Request.expert``) — the long-tail bench path.
    """

    def __init__(self, matcher: Optional[ExpertMatcher],
                 registry: ExpertRegistry,
                 *, max_batch: int = 16, route_cache_size: int = 4096,
                 use_fine_kernel: bool = True,
                 placement: Optional[PlacementPlan] = None,
                 executor: "str | DispatchExecutor" = "overlapped",
                 hub: Optional[ExpertHub] = None,
                 check_every: int = 0,
                 prefill_tokens_per_step: int = 0,
                 speculate_k: Optional[int] = None,
                 tracer=None):
        self.matcher = matcher
        self.registry = registry
        self.placement = placement
        self.hub = hub
        if matcher is None:
            if hub is None:
                raise ValueError("matcher=None requires a hub serving "
                                 "pre-routed requests")
            self.router = None
        else:
            assert len(registry) == matcher.n_experts, \
                "registry/bank mismatch"
            self.router = Router(
                matcher, cache_size=route_cache_size,
                use_fine_kernel=use_fine_kernel,
                shard_of=placement.shard_of if placement else None)
        if hub is not None and self.router is not None:
            # routing decisions feed residency: the eviction policy
            # reads the very Counter route() increments — which makes
            # that Counter cross-thread state, so the router's own
            # increments take the hub lock from here on (hits_lock)
            hub.bind_popularity(self.router.expert_hits,
                                router=self.router)
        self.scheduler = Scheduler(
            self.router, registry,
            SchedulerConfig(max_batch=max_batch, check_every=check_every,
                            prefill_tokens_per_step=prefill_tokens_per_step,
                            speculate_k=speculate_k),
            placement=placement, executor=executor, hub=hub,
            tracer=tracer)
        #: the unified metrics registry — ``obs.snapshot()`` is the
        #: whole mesh's state as one nested dict
        self.obs = self.scheduler.obs

    def bind_tracer(self, tracer) -> None:
        """Install (or, with None, disable) a lifecycle tracer across
        the scheduler, every engine core and the hub."""
        self.scheduler.bind_tracer(tracer)

    def snapshot(self) -> Dict[str, Any]:
        """Resolve the unified metrics tree (scheduler / engines / kv /
        router / hub) into one nested dict."""
        return self.obs.snapshot()

    def close(self) -> None:
        """Join background threads (hub staging worker); idempotent."""
        self.scheduler.close()

    def __enter__(self) -> "RoutedServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def submit(self, requests: Sequence[Request]) -> int:
        return self.scheduler.submit(requests)

    def step(self) -> List[Response]:
        return self.scheduler.step()

    def serve(self, requests: Sequence[Request]) -> List[Response]:
        if not requests:
            return []
        got: Dict[int, Response] = {}
        todo = list(requests)
        while todo or self.scheduler.has_work:
            if todo:
                todo = todo[self.scheduler.submit(todo):]
            for r in self.scheduler.step():
                got[r.uid] = r
        return [got[r.uid] for r in requests]

    @property
    def stats(self) -> Dict:
        engines = {self.registry[e].name: self.registry[e].backend.stats
                   for e in range(len(self.registry))
                   if isinstance(self.registry[e].backend, ExpertEngine)}
        banks = {}
        for shard in self.scheduler.shards:
            if not shard.banked:
                continue
            if self.hub is not None:
                label = "hub(%d experts/%d slots)" % (
                    len(self.registry), self.hub.n_slots)
            else:
                label = "bank%d(%s)" % (shard.sid, ",".join(
                    self.registry[e].name for e in shard.experts))
            banks[label] = shard.bank.stats
        out = {"scheduler": self.scheduler.stats,
               "router": self.router.stats if self.router else {},
               "engines": engines, "banks": banks,
               "executor": self.scheduler.executor.name}
        if self.hub is not None:
            out["hub"] = self.hub.stats
        return out
