"""EngineCore: the shared residency / bucketed-jit / harvest machinery
behind every expert engine, plus the dispatch executors.

PR 2 left ``ExpertEngine`` and ``BankedEngine`` as two parallel
implementations of the same machinery (bucket snapping, resident
groups, per-row harvest, bounded jit caches), kept aligned only by the
equivalence tests — and both forced a device→host copy of the sampled
token on *every* decode tick, blocking JAX's async dispatch before the
next shard's work could even be issued. This module unifies and
de-syncs that hot path:

  * ``EngineCore`` serves E >= 1 experts whose params are stacked on a
    leading ``expert`` axis; prefill/decode are ``vmap`` over that axis
    (optionally GSPMD-sharded over a 1-D ``expert`` mesh), jitted once
    per (batch bucket, len bucket) for the whole core. ``ExpertEngine``
    is the E=1 shim, ``BankedEngine`` the E=K shim — one implementation,
    no equivalence-by-test.
  * a tick **enqueues** device work and keeps the sampled token on
    device: ``wave.tok`` stays a ``jnp.ndarray`` and emitted columns
    accumulate as device buffers. Nothing blocks until ``harvest()``,
    which materialises all planes a completable row needs in **one**
    batched device→host transfer per wave per step (instead of one per
    tick per group).
  * every host-blocking materialisation increments
    ``EngineStats.host_blocks`` — the CI-stable sync counter the bench
    and tests assert against (overlapped must block strictly less often
    per decoded token than serial).
  * ``EngineStats.prefill_compiles`` / ``decode_compiles`` count real
    XLA executables via each jit wrapper's ``_cache_size()``, not
    wrapper creations — a wrapper that silently recompiled (shape/dtype
    drift inside one bucket) now shows up in the bounded-compile
    invariant instead of hiding behind a stale Python-side counter.

The dispatch executors decide *when* the host blocks:

  * ``SerialExecutor`` — the reference: each tick materialises its
    token immediately (today's per-tick sync), shard after shard.
  * ``OverlappedExecutor`` — issues prefills and decode ticks for all
    shards before blocking on anything, then runs one batched harvest;
    prefill of one shard overlaps decode of another on the device
    queue.

Both orders produce token-identical results (the compute graph is the
same; only sync placement differs) — asserted property-style in
``tests/test_serving.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.trace import NULL_TRACER
from ..sharding import leading_sharding
from .draft import build_draft
from .kvcache import PagePool, PagePoolExhausted, PrefixCache, hash_chain


# ---------------------------------------------------------------------------
# Shape buckets
# ---------------------------------------------------------------------------


def make_buckets(lo: int, hi: int) -> Tuple[int, ...]:
    """Power-of-two ladder covering [lo, hi] (hi always included).

    Raises instead of silently returning ``(hi,)`` when ``lo > hi`` —
    that shape used to make ``ExpertEngine(max_len=4, min_len_bucket=8)``
    build a ladder that ignored ``min_len_bucket`` entirely.
    """
    lo, hi = int(lo), int(hi)
    if lo < 1:
        raise ValueError(f"make_buckets: lo must be >= 1, got {lo}")
    if lo > hi:
        raise ValueError(f"make_buckets: lo {lo} > hi {hi}")
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(out)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n, clamped to the largest bucket."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------


def _wrapper_compiles(fn) -> int:
    """Real XLA executables behind one jit wrapper.

    ``_cache_size()`` is the C++ pjit cache entry count — it grows when
    a wrapper recompiles for a signature the Python-side bucket key
    didn't capture (cache dtype/shape drift), which a
    one-count-per-wrapper scheme silently missed. It is a private jax
    API: this helper is its one call site (lint rule L003).
    """
    return int(fn._cache_size())


class EngineStats:
    """Serving counters for one ``EngineCore``.

    ``prefill_compiles`` / ``decode_compiles`` are *live* properties
    summing real executable counts over the core's jit wrappers (see
    ``_wrapper_compiles``); the rest are plain counters.
    ``host_blocks`` counts host-blocking device→host materialisations —
    the sync counter the overlapped-dispatch invariants assert against.
    """

    def __init__(self, core: Optional["EngineCore"] = None):
        self._core = core
        self.prefill_calls = 0
        self.decode_steps = 0
        self.rows_served = 0
        self.rows_padded = 0
        self.tokens_generated = 0
        self.host_blocks = 0
        # prefill-compute accounting (the shared-prefix savings signal):
        # submitted counts every prompt token clients sent; computed
        # counts Sb per row that actually went through a prefill
        # dispatch — rows deduplicated in-wave or fully served from the
        # prefix cache contribute zero
        self.prefill_tokens_submitted = 0
        self.prefill_tokens_computed = 0
        self.prefill_rows_computed = 0
        self.prefix_full_hits = 0       # rows skipped via cross-wave cache
        self.prefix_dup_rows = 0        # rows deduplicated inside a wave
        self.prefix_pages_shared = 0    # page refs shared instead of built
        self.pages_copied = 0           # copy-on-write page copies
        # speculative decoding: drafted counts k per verified row,
        # accepted counts the matched greedy prefix (<= k); fallback
        # waves wanted to speculate but hit the no-wrap/chunk gate
        self.verify_steps = 0
        self.tokens_drafted = 0
        self.tokens_accepted = 0
        self.spec_fallback_waves = 0

    @property
    def prefill_compiles(self) -> int:
        if self._core is None:
            return 0
        return sum(_wrapper_compiles(fn)
                   for fn in self._core._prefill_fns.values())

    @property
    def decode_compiles(self) -> int:
        if self._core is None:
            return 0
        return sum(_wrapper_compiles(fn)
                   for fn in self._core._decode_fns.values())

    @property
    def suffix_compiles(self) -> int:
        if self._core is None:
            return 0
        return sum(_wrapper_compiles(fn)
                   for fn in self._core._suffix_fns.values())

    @property
    def verify_compiles(self) -> int:
        if self._core is None:
            return 0
        return sum(_wrapper_compiles(fn)
                   for fn in self._core._verify_fns.values())

    @property
    def acceptance_rate(self) -> float:
        if not self.tokens_drafted:
            return 0.0
        return self.tokens_accepted / self.tokens_drafted

    @property
    def jit_cache_entries(self) -> int:
        return (self.prefill_compiles + self.suffix_compiles
                + self.decode_compiles + self.verify_compiles)

    def as_dict(self) -> Dict[str, Any]:
        """Every counter plus the live compile properties — the shape
        the unified metrics registry snapshots (one engine = one leaf
        group in the tree)."""
        return {
            "prefill_calls": self.prefill_calls,
            "decode_steps": self.decode_steps,
            "rows_served": self.rows_served,
            "rows_padded": self.rows_padded,
            "tokens_generated": self.tokens_generated,
            "host_blocks": self.host_blocks,
            "prefill_tokens_submitted": self.prefill_tokens_submitted,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "prefill_rows_computed": self.prefill_rows_computed,
            "prefix_full_hits": self.prefix_full_hits,
            "prefix_dup_rows": self.prefix_dup_rows,
            "prefix_pages_shared": self.prefix_pages_shared,
            "pages_copied": self.pages_copied,
            "verify_steps": self.verify_steps,
            "tokens_drafted": self.tokens_drafted,
            "tokens_accepted": self.tokens_accepted,
            "acceptance_rate": self.acceptance_rate,
            "spec_fallback_waves": self.spec_fallback_waves,
            "prefill_compiles": self.prefill_compiles,
            "suffix_compiles": self.suffix_compiles,
            "decode_compiles": self.decode_compiles,
            "verify_compiles": self.verify_compiles,
            "jit_cache_entries": self.jit_cache_entries,
        }

    def __repr__(self) -> str:
        return (f"EngineStats(prefill_compiles={self.prefill_compiles}, "
                f"decode_compiles={self.decode_compiles}, "
                f"prefill_calls={self.prefill_calls}, "
                f"decode_steps={self.decode_steps}, "
                f"rows_served={self.rows_served}, "
                f"rows_padded={self.rows_padded}, "
                f"tokens_generated={self.tokens_generated}, "
                f"host_blocks={self.host_blocks}, "
                f"prefill_tokens={self.prefill_tokens_computed}/"
                f"{self.prefill_tokens_submitted}, "
                f"prefix_hits={self.prefix_full_hits}+"
                f"{self.prefix_dup_rows}dup)")


# ---------------------------------------------------------------------------
# Core
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Wave:
    """One admitted (E, Bb) micro-batch wave resident in the core.

    ``emitted`` holds one (E, Bb) token plane per generated step; planes
    start life as device buffers and are swapped for host arrays by
    ``_materialize`` — ``n_host`` is the already-materialised prefix.

    Ring waves own a dense ``cache``; paged waves instead carry a page
    ``table`` into the core's shared pool plus the wave's ``pos``/``t``
    tracking (lockstep rows share positions, only physical storage is
    per-row), the pages each row must release at retirement, and the
    prefix chains to register in the cross-wave cache.
    """
    uids: Dict[int, List[Any]]          # local expert -> row uids
    per_row_new: Dict[int, List[int]]
    done: Dict[int, List[bool]]
    cache: Any
    tok: Optional[jnp.ndarray]          # (E, Bb, 1) last sampled token;
    #   None while prefill chunks are still pending (decode is gated)
    emitted: List[Any]                  # (E, Bb) planes, device or host
    steps_left: int
    n_host: int = 0                     # emitted[:n_host] are host arrays
    # paged-layout fields (None / empty on ring waves)
    table: Optional[jnp.ndarray] = None      # (E, Bb, n_logical) int32
    pos: Optional[jnp.ndarray] = None        # (E, C) slot positions
    t: Optional[jnp.ndarray] = None          # (E,) next write position
    pages_held: Dict[int, List[List[int]]] = \
        dataclasses.field(default_factory=dict)
    register: List[Tuple[int, int, int, List[bytes], List[int]]] = \
        dataclasses.field(default_factory=list)
    #   ^ (local, row, padded_len, chain, pages) to insert at retirement
    # chunked-prefill fields (empty / None on unchunked waves): each
    # pending descriptor is one not-yet-dispatched prefill chunk; the
    # chunk cursor is implicit — descriptors are dispatched FIFO, and
    # the wave's first token (and decode eligibility) materialises only
    # when the last chunk lands (see EngineCore._finalize_wave)
    pending_chunks: List[Dict[str, Any]] = \
        dataclasses.field(default_factory=list)
    finalize: Optional[Dict[str, Any]] = None
    _tok_c: Optional[jnp.ndarray] = None     # last chunk's packed argmax
    # speculative-decoding fields (inert on plain waves). Spec waves
    # advance rows at *different* rates, so they carry per-row
    # ``row_pos``/``row_t`` instead of the shared pos/t planes; ``cap``
    # freezes a row once it has written every token it must emit; each
    # verify tick appends an (emit, adv, acc) device triple to
    # ``spec_pending``, drained by ``_materialize_spec`` into the host
    # per-row token buffer ``host_buf`` (column 0 is the prefill token).
    spec: bool = False
    row_pos: Optional[jnp.ndarray] = None    # (E, Bb, C) per-row slots
    row_t: Optional[jnp.ndarray] = None      # (E, Bb) per-row write pos
    cap: Optional[jnp.ndarray] = None        # (E, Bb) freeze position
    spec_pending: List[Any] = dataclasses.field(default_factory=list)
    host_buf: Optional[np.ndarray] = None    # (E, Bb, 1 + steps) int32
    host_fill: Optional[np.ndarray] = None   # (E, Bb) tokens in host_buf
    spec_seeded: bool = False                # host_buf column 0 written
    # tracing (inert under NULL_TRACER): the wave's id in the trace and
    # the open device-span handles, begun at enqueue and ended only
    # inside _materialize/_materialize_spec — the existing sync sites —
    # so tracing never adds a host block (rule O002)
    wave_id: int = 0
    sp_prefill: Any = None
    sp_decode: Any = None
    # sequence number of the wave's latest dispatch (EngineCore.ahead)
    last_seq: int = 0


class EngineCore:
    """E homogeneous experts: bucketed executables, resident waves,
    device-side token state, batched harvest.

    Admission and decode *enqueue* work; the only host-blocking points
    are ``_materialize`` calls — per tick in sync mode (``defer=False``,
    the serial reference and the seed-compatible blocking API), or one
    batched transfer per wave inside ``harvest()`` in deferred mode.
    """

    def __init__(self, model, params_list: Sequence[Any], *,
                 max_len: int = 256, min_len_bucket: int = 8,
                 len_buckets: Optional[Sequence[int]] = None,
                 batch_buckets: Optional[Sequence[int]] = None,
                 mesh: Optional[Mesh] = None,
                 kv_layout: str = "ring", page_size: int = 8,
                 pool_pages: Optional[int] = None,
                 prefix_cache_size: int = 1024,
                 chunk_len: Optional[int] = None,
                 speculate_k: int = 0, draft=None):
        if not params_list:
            raise ValueError("EngineCore needs at least one expert")
        if kv_layout not in ("ring", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}; expected "
                             "'ring' or 'paged'")
        self.model = model
        self.n_experts = len(params_list)
        self.max_len = max_len
        self.len_buckets = tuple(len_buckets) if len_buckets else \
            make_buckets(min_len_bucket, max_len)
        self.batch_buckets = tuple(batch_buckets or make_buckets(1, 16))
        if mesh is not None and (
                "expert" not in mesh.shape
                or self.n_experts % mesh.shape["expert"]):
            raise ValueError(
                f"mesh expert axis {dict(mesh.shape)} must divide the "
                f"bank's {self.n_experts} experts")
        self.mesh = mesh if (mesh is not None
                             and mesh.shape.get("expert", 1) > 1) else None
        self.stats = EngineStats(self)
        # lifecycle tracing; rebound by the scheduler (bind_tracer) when
        # the server carries a live tracer. Under NULL_TRACER every
        # call below is a no-op (begin_device returns None).
        self.tracer = NULL_TRACER
        # dispatches issued, and the newest of them a completed sync
        # covered: on an in-order device stream everything issued up to
        # the dispatch that produced a materialised plane is done
        self._issued = 0
        self._covered = 0
        self._active: List[_Wave] = []
        self._finished: List[Tuple[int, Any, np.ndarray]] = []
        # shape-keyed jit wrappers; real executable counts come from
        # each wrapper's _cache_size() (see EngineStats)
        self._prefill_fns: Dict[Tuple[int, int], Any] = {}
        self._suffix_fns: Dict[Tuple[int, int], Any] = {}  # (Bb, chunk k)
        self._decode_fns: Dict[int, Any] = {}
        self._verify_fns: Dict[Tuple[int, int], Any] = {}  # (Bb, k)
        self._copy_fns: Dict[int, Any] = {}     # COW page-copy, by count
        params = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                        *params_list)
        if self.mesh is not None:
            sh = leading_sharding(params, "expert", self.mesh)
            params = jax.device_put(params, sh)
        self.params = params
        # -- paged KV state (None in ring layout) ------------------------
        self.kv_layout = kv_layout
        self.pool: Optional[PagePool] = None
        self.prefix_cache: Optional[PrefixCache] = None
        self.kv_pool = None                  # {k, v}: (E, L, P1, ...)
        if kv_layout == "paged":
            if not model.supports_paged_kv:
                raise ValueError(
                    f"model family {model.cfg.family!r} does not "
                    "implement the paged KV cache protocol; use "
                    "kv_layout='ring'")
            self.page = int(page_size)
            bad = [b for b in (*self.len_buckets, self.max_len)
                   if b % self.page]
            if bad:
                raise ValueError(
                    f"paged layout needs every length bucket to be a "
                    f"multiple of page_size={self.page}; offending "
                    f"buckets {bad} (prefills must fill whole pages so "
                    "prefix-shared pages are never partially written)")
            self.n_logical = self.max_len // self.page
            per_expert = int(pool_pages) if pool_pages else \
                3 * self.batch_buckets[-1] * self.n_logical
            self.pool = PagePool(self.n_experts, per_expert, self.page)
            self.prefix_cache = PrefixCache(self.pool,
                                            capacity=prefix_cache_size)
            shape = jax.eval_shape(
                lambda: model.init_paged_pool(per_expert, self.page))
            kv = jax.tree_util.tree_map(
                lambda s: jnp.zeros((self.n_experts,) + s.shape, s.dtype),
                shape)
            if self.mesh is not None:
                kv = jax.device_put(
                    kv, leading_sharding(kv, "expert", self.mesh))
            self.kv_pool = kv
        # -- chunked prefill geometry (paged only) -----------------------
        self.chunk_len: Optional[int] = None
        if chunk_len is not None:
            cl = int(chunk_len)
            if kv_layout != "paged":
                raise ValueError("chunk_len requires kv_layout='paged' "
                                 "(suffix prefill attends over pool pages)")
            if cl % self.page:
                raise ValueError(
                    f"chunk_len={cl} must be a multiple of "
                    f"page_size={self.page}")
            if self.max_len % cl:
                raise ValueError(
                    f"max_len={self.max_len} must be a multiple of "
                    f"chunk_len={cl} (the suffix ladder tiles max_len)")
            if cl not in self.len_buckets:
                raise ValueError(
                    f"chunk_len={cl} must itself be a length bucket "
                    f"(got buckets {self.len_buckets}) — chunk 0 reuses "
                    "the monolithic prefill executable at that bucket")
            bad = [b for b in self.len_buckets if b > cl and b % cl]
            if bad:
                raise ValueError(
                    f"length buckets above chunk_len must be multiples "
                    f"of chunk_len={cl}; offending buckets {bad} (every "
                    "padded prompt must split into whole chunks)")
            self.chunk_len = cl
        # -- speculative decoding ----------------------------------------
        self.speculate_k = int(speculate_k)
        self.draft = None
        self.draft_name: Optional[str] = None
        self.draft_state = None
        if self.speculate_k < 0:
            raise ValueError(f"speculate_k must be >= 0, got "
                             f"{self.speculate_k}")
        if self.speculate_k:
            if not model.supports_verify:
                raise ValueError(
                    f"model family {model.cfg.family!r} does not "
                    "implement the speculative verify protocol; use "
                    "speculate_k=0")
            d = draft if draft is not None else "mlp"
            if isinstance(d, str):
                d = build_draft(d, int(model.cfg.padded_vocab))
            self.draft = d
            self.draft_name = d.name
            # draft state is ENGINE-level (leading E axis, bank-sharded
            # like params): it threads through every verify dispatch, so
            # an online draft keeps learning across waves
            st = d.init_state(jax.random.PRNGKey(0), self.n_experts)
            if self.mesh is not None:
                st = jax.device_put(
                    st, leading_sharding(st, "expert", self.mesh))
            self.draft_state = st
        elif draft is not None:
            raise ValueError("draft requires speculate_k > 0")

    # -- sharded/bucketed executables -----------------------------------
    def _bank_sharding(self):
        """Prefix sharding for any expert-leading pytree (or None)."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P("expert"))

    def _prefill_fn(self, Bb: int, Sb: int):
        key = (Bb, Sb)
        if key not in self._prefill_fns:
            s = self._bank_sharding()
            if self.kv_layout == "paged":
                # (params, {tokens}, kv_pool, scatter_tbl) ->
                # (logits, kv_pool'); the pool buffers are donated so
                # XLA scatters the new pages in place
                fn = jax.vmap(
                    lambda p, b, pool, tbl: self.model.paged_prefill(
                        p, b, pool, tbl, page=self.page,
                        capacity=self.max_len)[:2])
                if s is not None:
                    jitted = jax.jit(fn, in_shardings=(s, s, s, s),
                                     out_shardings=(s, s),
                                     donate_argnums=(2,))
                else:
                    jitted = jax.jit(fn, donate_argnums=(2,))
            else:
                fn = jax.vmap(lambda p, b: self.model.prefill(
                    p, b, capacity=self.max_len))
                if s is not None:
                    jitted = jax.jit(fn, in_shardings=(s, s),
                                     out_shardings=(s, s))
                else:
                    jitted = jax.jit(fn)
            self._prefill_fns[key] = jitted
        return self._prefill_fns[key]

    def _suffix_fn(self, Bb: int, k: int):
        """Suffix-prefill executable for chunk index ``k >= 1``: computes
        exactly ``chunk_len`` tokens at static offset ``k * chunk_len``,
        attending over the prefix pages already resident in the pool.
        Keyed (Bb, k) so the ladder is bounded by
        ``(max(len_buckets) // chunk_len - 1) * len(batch_buckets)``."""
        key = (Bb, k)
        if key not in self._suffix_fns:
            s = self._bank_sharding()
            offset = k * self.chunk_len
            # (params, {tokens}, kv_pool, prefix_tbl, scatter_tbl) ->
            # (logits, kv_pool'); pool donated as in _prefill_fn
            fn = jax.vmap(
                lambda p, b, pool, ptbl, stbl:
                self.model.paged_prefill_suffix(
                    p, b, pool, ptbl, stbl, offset=offset,
                    page=self.page))
            if s is not None:
                jitted = jax.jit(fn, in_shardings=(s, s, s, s, s),
                                 out_shardings=(s, s),
                                 donate_argnums=(2,))
            else:
                jitted = jax.jit(fn, donate_argnums=(2,))
            self._suffix_fns[key] = jitted
        return self._suffix_fns[key]

    def bind_tracer(self, tracer) -> None:
        """Install a lifecycle tracer (None restores NULL_TRACER). The
        core only *opens* device spans at enqueue points and closes
        them inside its existing sync sites, so binding a live tracer
        cannot change ``stats.host_blocks``."""
        self.tracer = tracer if tracer is not None else NULL_TRACER

    @property
    def ahead(self) -> int:
        """Prefill, chunk, decode and verify dispatches issued after the
        last one a completed ``device_get`` covered — what a blocking
        transfer issued now would wait behind on an in-order stream."""
        return self._issued - self._covered

    def executable_bounds(self) -> Dict[str, int]:
        """Steady-state executable-count bound per wrapper family.

        With chunking enabled, monolithic prefill executables only exist
        for length buckets <= chunk_len (longer prompts go through the
        chunk ladder), and the suffix ladder adds one executable per
        (batch bucket, chunk index >= 1) pair. The H004 gate and the
        serving bench assert the live counts against exactly this."""
        nB = len(self.batch_buckets)
        if self.chunk_len:
            prefill = nB * sum(1 for b in self.len_buckets
                               if b <= self.chunk_len)
            # deepest reachable chunk index: prompts snap to len_buckets,
            # so the largest bucket (not max_len, which may exceed it)
            # caps the ladder
            suffix = nB * (max(self.len_buckets) // self.chunk_len - 1)
        else:
            prefill = nB * len(self.len_buckets)
            suffix = 0
        # the verify ladder is keyed (Bb, k) with k fixed per engine, so
        # it adds at most one executable per batch bucket; engines that
        # never speculate must build none
        return {"prefill": prefill, "suffix": suffix, "decode": nB,
                "verify": nB if self.speculate_k else 0}

    def _decode_fn(self, Bb: int):
        if Bb not in self._decode_fns:
            s = self._bank_sharding()
            if self.kv_layout == "paged":
                # (params, kv_pool, table, pos, t, {token}) ->
                # (logits, kv_pool', pos', t')
                fn = jax.vmap(
                    lambda p, pool, tbl, pos, t, b: self.model.paged_decode(
                        p, pool, tbl, pos, t, b, page=self.page))
                if s is not None:
                    jitted = jax.jit(fn,
                                     in_shardings=(s, s, s, s, s, s),
                                     out_shardings=(s, s, s, s),
                                     donate_argnums=(1,))
                else:
                    jitted = jax.jit(fn, donate_argnums=(1,))
            else:
                fn = jax.vmap(self.model.decode)
                if s is not None:
                    jitted = jax.jit(fn, in_shardings=(s, s, s),
                                     out_shardings=(s, s),
                                     donate_argnums=(1,))
                else:
                    jitted = jax.jit(fn, donate_argnums=(1,))
            self._decode_fns[Bb] = jitted
        return self._decode_fns[Bb]

    def _verify_fn(self, Bb: int, k: int):
        """Fused draft-k/verify-1 executable for one batch bucket.

        One dispatch per wave per tick: the draft proposes ``k`` tokens
        from each row's last emitted token, the target scores the whole
        (Bb, k+1) window through ``model.verify`` (k+1 chained
        single-token decode steps — bitwise identical to the plain
        decode ladder, see models/dense.py), the matched greedy prefix
        is accepted, per-row positions advance by ``adv``, the rejected
        suffix's optimistically written slots roll back to pos == -1,
        and the draft observes the verified transitions. Rows frozen at
        ``cap`` (done emitting) get adv == 0 and acc == -1.

        Returns (emit (E,Bb,k+1) greedy tokens — the host keeps the
        first ``adv`` per row, adv (E,Bb), acc (E,Bb) accepted draft
        count or -1, tok' (E,Bb) next feed token, kv', row_pos',
        row_t', draft_state')."""
        key = (Bb, k)
        if key not in self._verify_fns:
            s = self._bank_sharding()
            K1 = k + 1
            draft = self.draft
            model = self.model

            def accept(window, greedy, row_pos, row_t, tok, cap, dstate):
                # accepted prefix: drafts matching the greedy chain
                match = (window[:, 1:] == greedy[:, :-1])
                j = jnp.cumprod(match.astype(jnp.int32), axis=1) \
                    .sum(axis=1)                       # (Bb,) <= k
                remaining = jnp.maximum(cap - row_t, 0)
                adv = jnp.minimum(j + 1, remaining)    # >= 1 while active
                active = remaining > 0
                acc = jnp.where(active, j, -1).astype(jnp.int32)
                # roll back the rejected suffix: written slots past the
                # accepted prefix return to pos == -1 (they were -1 on
                # entry — the admit gate guarantees slots t..t+k are
                # unused and never wrap onto live context)
                C = row_pos.shape[1]
                offs = row_t[:, None] + jnp.arange(K1)[None, :]
                keep = jnp.arange(K1)[None, :] < adv[:, None]
                rowsB = jnp.arange(Bb)[:, None]
                new_pos = row_pos.at[rowsB, offs % C].set(
                    jnp.where(keep, offs, -1).astype(row_pos.dtype))
                new_t = row_t + adv
                tok2 = jnp.where(
                    active,
                    jnp.take_along_axis(
                        greedy, jnp.maximum(adv - 1, 0)[:, None],
                        axis=1)[:, 0],
                    tok)
                dstate2 = draft.observe(dstate, window, greedy, adv)
                return (greedy, adv.astype(jnp.int32), acc, tok2,
                        new_pos, new_t, dstate2)

            if self.kv_layout == "paged":
                # (params, kv_pool, table, row_pos, row_t, tok, cap,
                #  dstate) -> (emit, adv, acc, tok', kv_pool', row_pos',
                #  row_t', dstate')
                def one(p, pool, tbl, row_pos, row_t, tok, cap, dstate):
                    drafts = draft.propose(dstate, tok, k)
                    window = jnp.concatenate([tok[:, None], drafts], 1)
                    greedy, pool = model.paged_verify(
                        p, pool, tbl, row_pos, row_t,
                        {"tokens": window}, page=self.page)
                    (emit, adv, acc, tok2, new_pos, new_t,
                     dstate2) = accept(window, greedy, row_pos, row_t,
                                       tok, cap, dstate)
                    return emit, adv, acc, tok2, pool, new_pos, new_t, \
                        dstate2

                fn = jax.vmap(one)
                if s is not None:
                    jitted = jax.jit(
                        fn, in_shardings=(s,) * 8,
                        out_shardings=(s,) * 8, donate_argnums=(1,))
                else:
                    jitted = jax.jit(fn, donate_argnums=(1,))
            else:
                # (params, cache, row_pos, row_t, tok, cap, dstate) ->
                # (emit, adv, acc, tok', cache', row_pos', row_t',
                #  dstate')
                def one(p, cache, row_pos, row_t, tok, cap, dstate):
                    drafts = draft.propose(dstate, tok, k)
                    window = jnp.concatenate([tok[:, None], drafts], 1)
                    greedy, cache = model.verify(
                        p, cache, row_pos, row_t, {"tokens": window})
                    (emit, adv, acc, tok2, new_pos, new_t,
                     dstate2) = accept(window, greedy, row_pos, row_t,
                                       tok, cap, dstate)
                    return emit, adv, acc, tok2, cache, new_pos, \
                        new_t, dstate2

                fn = jax.vmap(one)
                if s is not None:
                    jitted = jax.jit(
                        fn, in_shardings=(s,) * 7,
                        out_shardings=(s,) * 8, donate_argnums=(1,))
                else:
                    jitted = jax.jit(fn, donate_argnums=(1,))
            self._verify_fns[key] = jitted
        return self._verify_fns[key]

    def _copy_pages_fn(self, m: int):
        """Jitted COW page copier for ``m`` (expert, src, dst) triples.
        The pool is donated so XLA scatters the copied pages in place —
        an eager ``.at[].set`` would materialise a full copy of the
        engine's largest device buffer per call. ``m`` is snapped to a
        power-of-two ladder (padding copies trash -> trash, a no-op),
        so the wrapper count stays bounded under arbitrary traffic."""
        if m not in self._copy_fns:
            # pool planes are layer-major, (E, L, P1, ...): a page is
            # copied in every layer, one (layer, page) row per index —
            # batching over the layer axis would relay out the pool
            def fn(pool, es, srcs, dsts):
                out = {}
                for k, v in pool.items():
                    ls = jnp.arange(v.shape[1])
                    out[k] = v.at[es[:, None], ls, dsts[:, None]].set(
                        v[es[:, None], ls, srcs[:, None]])
                return out
            s = self._bank_sharding()
            if s is not None:
                jitted = jax.jit(fn, in_shardings=(s, None, None, None),
                                 out_shardings=s, donate_argnums=(0,))
            else:
                jitted = jax.jit(fn, donate_argnums=(0,))
            self._copy_fns[m] = jitted
        return self._copy_fns[m]

    def _copy_pages(self, copies: Mapping[int, Sequence[Tuple[int, int]]]
                    ) -> None:
        """Apply copy-on-write page copies: flatten every expert's
        (src, dst) pairs into one padded, jitted, donated dispatch."""
        triples = [(local, s_, d) for local, pairs in copies.items()
                   for s_, d in pairs]
        if not triples:
            return
        m = 1
        while m < len(triples):
            m *= 2
        trash = self.pool.trash
        triples += [(0, trash, trash)] * (m - len(triples))
        es, srcs, dsts = (np.asarray(col, np.int32)
                          for col in zip(*triples))
        self.kv_pool = self._copy_pages_fn(m)(
            self.kv_pool, jnp.asarray(es), jnp.asarray(srcs),
            jnp.asarray(dsts))

    # -- admission -------------------------------------------------------
    def pad_shape(self, n_rows: int, prompt_len: int) -> Tuple[int, int]:
        """(batch bucket, length bucket) this admission would snap to."""
        return (bucket_for(n_rows, self.batch_buckets),
                bucket_for(prompt_len, self.len_buckets))

    def _make_spec_wave(self, uids, per_row, done, Bb: int, Sb: int,
                        steps: int, *, cache=None, tok=None,
                        row_pos=None, row_t=None, table=None,
                        pages_held=None, register=None) -> _Wave:
        """Assemble a speculative wave: per-row position planes, the
        per-row freeze position ``cap`` (a row stops once it has written
        its last emitted token; padding rows freeze immediately), the
        host-side token buffer, and the sharding commit — every
        wave-carried array must enter the first verify with the bank
        sharding or pjit mints one executable per sharding combination
        (see the commit comment in ``_admit_paged``)."""
        E = self.n_experts
        cap = np.full((E, Bb), Sb, np.int32)
        for local, ms in per_row.items():
            for i, m in enumerate(ms):
                cap[local, i] = Sb + m - 1
        cap = jnp.asarray(cap)
        s = self._bank_sharding()
        if s is not None:
            row_pos, row_t, tok, cap = jax.device_put(
                (row_pos, row_t, tok, cap), s)
            if table is not None:
                table = jax.device_put(table, s)
        return _Wave(uids=uids, per_row_new=per_row, done=done,
                     cache=cache, tok=tok, emitted=[tok[..., 0]],
                     steps_left=steps, table=table,
                     pages_held=pages_held if pages_held is not None
                     else {},
                     register=register if register is not None else [],
                     spec=True, row_pos=row_pos, row_t=row_t, cap=cap,
                     host_buf=np.zeros((E, Bb, steps + 1), np.int32),
                     host_fill=np.zeros((E, Bb), np.int32))

    def admit_wave(self, groups: Mapping[int, Tuple[Sequence[Any],
                                                    Sequence[np.ndarray],
                                                    Sequence[int]]],
                   *, defer: bool = False) -> bool:
        """Prefill one (E, Bb, Sb) wave: every member expert's micro-batch
        in a single dispatch. Returns False when no group has rows.

        ``groups`` maps local expert index -> (uids, prompts, max_new);
        experts without traffic this wave ride along as zero rows.
        Prompts are right-truncated to the largest length bucket (keeping
        the most recent tokens) and zero-padded to the common bucket; the
        batch dim is zero-padded to its bucket. Decoding past cache
        capacity is safe: the cache is a position-tracked ring, so the
        oldest context is evicted rather than corrupted.

        With ``defer=True`` the prefill (and the first sampled token)
        stays enqueued on device — call ``harvest()`` to materialise and
        emit. With ``defer=False`` the first token plane is materialised
        and harvested before returning (the blocking reference path).
        """
        rows_max, len_max = 0, 1
        for local, (uids, prompts, max_new) in groups.items():
            if not 0 <= local < self.n_experts:
                raise ValueError(f"local expert {local} out of range")
            if len(uids) != len(prompts) or len(uids) != len(max_new):
                raise ValueError("uids/prompts/max_new length mismatch")
            if len(prompts) > self.batch_buckets[-1]:
                raise ValueError(
                    f"micro-batch of {len(prompts)} rows exceeds the "
                    f"largest batch bucket {self.batch_buckets[-1]}")
            rows_max = max(rows_max, len(prompts))
            len_max = max(len_max, max((len(p) for p in prompts),
                                       default=1))
        if rows_max == 0:
            return False
        groups = {l: g for l, g in groups.items() if g[0]}
        Bb = bucket_for(rows_max, self.batch_buckets)
        Sb = bucket_for(len_max, self.len_buckets)
        E = self.n_experts
        toks = np.zeros((E, Bb, Sb), np.int32)
        uids: Dict[int, List[Any]] = {}
        per_row: Dict[int, List[int]] = {}
        done: Dict[int, List[bool]] = {}
        n_rows, n_submitted = 0, 0
        for local, (u, prompts, max_new) in groups.items():
            for i, p in enumerate(prompts):
                p = np.asarray(p, np.int32)[-Sb:]
                toks[local, i, :len(p)] = p
                n_submitted += len(p)
            uids[local] = list(u)
            per_row[local] = [max(1, int(m)) for m in max_new]
            done[local] = [False] * len(u)
            n_rows += len(u)
        fb0 = self.stats.spec_fallback_waves
        issued0 = self._issued
        if self.kv_layout == "paged":
            # may raise PagePoolExhausted with no state changed — the
            # scheduler requeues the rows as backpressure; the device
            # span below opens only after admission succeeds, so span
            # balance holds trivially across the rollback
            w = self._admit_paged(toks, uids, per_row, done, Bb, Sb)
        else:
            with self.tracer.enqueue_span("engine.enqueue",
                                          kind="prefill"):
                logits, cache = self._prefill_fn(Bb, Sb)(
                    self.params, {"tokens": jnp.asarray(toks)})
                tok = jnp.argmax(logits, axis=-1).astype(
                    jnp.int32)[..., None]
            self._issued += 1
            self.stats.prefill_calls += 1
            self.stats.prefill_rows_computed += n_rows
            self.stats.prefill_tokens_computed += n_rows * Sb
            steps = max(m for ms in per_row.values() for m in ms) - 1
            sk = self.speculate_k
            # no-wrap gate: every slot a verify may optimistically write
            # (up to Sb + steps - 1 + k) must fit the ring without
            # wrapping onto live context
            if sk and steps > 0 and Sb + steps + sk <= self.max_len:
                w = self._make_spec_wave(
                    uids, per_row, done, Bb, Sb, steps,
                    cache={"k": cache["k"], "v": cache["v"]}, tok=tok,
                    row_pos=jnp.broadcast_to(
                        cache["pos"][:, None], (E, Bb, self.max_len)),
                    row_t=jnp.broadcast_to(cache["t"][:, None], (E, Bb)))
            else:
                if sk:
                    self.stats.spec_fallback_waves += 1
                w = _Wave(uids=uids, per_row_new=per_row, done=done,
                          cache=cache, tok=tok, emitted=[tok[..., 0]],
                          steps_left=steps)
        if self._issued > issued0:
            w.last_seq = self._issued
        self.stats.rows_served += n_rows
        self.stats.rows_padded += E * Bb - n_rows
        self.stats.prefill_tokens_submitted += n_submitted
        if self.tracer.enabled:
            w.wave_id = self.tracer.next_id()
            flat = [u for us in uids.values() for u in us]
            w.sp_prefill = self.tracer.begin_device(
                "wave.prefill", wave=w.wave_id, Bb=Bb, Sb=Sb,
                rows=n_rows, spec=w.spec, chunks=len(w.pending_chunks),
                uids=flat,
                traces=[self.tracer.trace_of(u) for u in flat])
            if self.stats.spec_fallback_waves > fb0:
                self.tracer.event("spec.fallback", wave=w.wave_id)
        self._active.append(w)
        if not defer:
            # blocking reference: drain the wave's prefill chunks (a
            # no-op on unchunked waves) before materialising the first
            # token — callers of the sync API see a fully-prefilled row
            while w.pending_chunks:
                self._dispatch_chunk(w)
            self._materialize(w, 1)
            self.harvest()
        return True

    # -- paged admission -------------------------------------------------
    def _alloc_pages(self, local: int, n: int,
                     ledger: List[Tuple[int, List[int]]]) -> List[int]:
        """Pool allocation with prefix-cache eviction as the fallback;
        every page taken is recorded in ``ledger`` for rollback."""
        try:
            pages = self.pool.alloc(local, n)
        except PagePoolExhausted:
            self.prefix_cache.evict_for(local, n)
            pages = self.pool.alloc(local, n)
        ledger.append((local, pages))
        return pages

    def _admit_paged(self, toks: np.ndarray, uids, per_row, done,
                     Bb: int, Sb: int) -> _Wave:
        """Plan page tables for one wave, sharing prefixes, then prefill
        only the rows no cached/duplicated prefix covers.

        Host phase (transactional): every row is classified as

          * ``cached`` — its full padded prompt's pages are in the
            cross-wave prefix cache and the greedy first token is known:
            the row adopts the pages (refcount++) and skips prefill
            compute entirely;
          * ``dup`` — an earlier row in this wave carries the identical
            padded prompt: share its pages, take its first token;
          * ``computed`` — adopt whatever cached prefix exists (those
            pages are scattered to trash — storage shared, compute not),
            allocate fresh pages for the rest, and join the packed
            prefill batch.

        Rows that wrap (Sb + steps > capacity) overwrite prompt pages
        during decode, so shared pages in the write range are
        copy-on-write remapped to fresh copies before the first tick.
        If the pool cannot cover the wave even after evicting cache
        entries, every reference taken so far is rolled back and
        ``PagePoolExhausted`` propagates with the pool untouched.

        Device phase: computed rows are packed into a (E, Bbc, Sb)
        prefill — Bbc buckets the *computed* row count, which is where
        the measured prefill-compute saving comes from — followed by the
        COW page copies and the first-token plane assembly (gather from
        packed logits + cached-token overrides), all enqueued without a
        host block.
        """
        E, page, nlp, C = self.n_experts, self.page, self.n_logical, \
            self.max_len
        npp = Sb // page
        trash = self.pool.trash
        steps = max(m for ms in per_row.values() for m in ms) - 1
        # chunked geometry: prompts longer than chunk_len split into
        # n_chunks dispatches; partial-prefix adoption snaps DOWN to a
        # chunk boundary so every dispatched chunk is fully uncached,
        # and is capped at npp - ppc so the last chunk always computes
        # (its logits carry every computed row's first token)
        chunked = self.chunk_len is not None and Sb > self.chunk_len
        ppc = (self.chunk_len // page) if chunked else npp
        start_chunk: Dict[Tuple[int, int], int] = {}
        # speculative gate: the last verify of a row may start at
        # Sb + steps - 1 and optimistically write k slots past it, so
        # the whole write window [Sb, Sb + steps + k) must fit without
        # wrapping — which also keeps every speculative write inside
        # wave-owned decode pages (never a shared/prompt page) and COW
        # out of the picture. Chunked whale waves fall back to plain
        # decode (still token-identical, just unaccelerated).
        sk = self.speculate_k
        spec_ok = bool(sk) and steps > 0 and Sb + steps + sk <= C \
            and not chunked
        if sk and not spec_ok:
            self.stats.spec_fallback_waves += 1
        slack = sk if spec_ok else 0
        wr_pages = sorted({(s % C) // page
                           for s in range(Sb, Sb + steps + slack)})
        wr_prompt = [lp for lp in wr_pages if lp < npp]
        wr_decode = [lp for lp in wr_pages if lp >= npp]
        register_ok = not wr_prompt      # decode never clobbers a prefix

        table = np.full((E, Bb, nlp), trash, np.int32)
        ledger: List[Tuple[int, List[int]]] = []    # refs for rollback
        to_release: List[Tuple[int, List[int]]] = []  # COW'd-out pages
        copies: Dict[int, List[Tuple[int, int]]] = {}  # local -> (src, dst)
        scatter: Dict[Tuple[int, int], List[int]] = {}  # computed rows
        cached_tok: Dict[Tuple[int, int], int] = {}
        dup_src: Dict[Tuple[int, int], int] = {}    # row -> computed row
        register: List[Tuple[int, int, int, List[bytes], List[int]]] = []
        n_cached = n_dup = n_shared = 0
        try:
            for local, row_uids in uids.items():
                seen: Dict[bytes, int] = {}       # full-prompt key -> row
                for i in range(len(row_uids)):
                    chain = hash_chain(toks[local, i], page)
                    key = chain[-1]
                    prow: List[int]
                    if key in seen:
                        # only computed rows enter ``seen`` (a row equal
                        # to a cache-hit row takes the cached branch
                        # itself), so a dup's first token always comes
                        # from its representative's packed logits
                        rep = seen[key]
                        prow = list(table[local, rep, :npp])
                        self.pool.retain(local, prow)
                        # ledger entries must own their page lists: the
                        # COW remap below mutates prow in place, and an
                        # aliased entry would double-free the fresh COW
                        # page on rollback while leaking the shared one
                        ledger.append((local, list(prow)))
                        dup_src[(local, i)] = rep
                        n_dup += 1
                        n_shared += npp
                    else:
                        adopted = self.prefix_cache.adopt_prefix(local,
                                                                 chain)
                        if adopted:
                            ledger.append((local, list(adopted)))
                        ftok = None
                        if len(adopted) == npp:
                            ftok = self.prefix_cache.first_token(
                                local, Sb, chain)
                        if ftok is not None:
                            prow = list(adopted)
                            cached_tok[(local, i)] = ftok
                            n_cached += 1
                            n_shared += npp
                        else:
                            if wr_prompt and adopted:
                                # a wrapping row must own its wrapped
                                # prompt pages; trash the adoption and
                                # compute everything into fresh pages
                                self.pool.release(local, adopted)
                                ledger.pop()
                                adopted = []
                            d = len(adopted)
                            if chunked and d:
                                # snap adoption to the chunk grid: kept
                                # pages are compute-shared (their chunks
                                # are skipped, not re-run-to-trash)
                                keep = min((d // ppc) * ppc, npp - ppc)
                                if keep < d:
                                    self.pool.release(local,
                                                      adopted[keep:])
                                    if keep:
                                        ledger[-1] = (local,
                                                      list(adopted[:keep]))
                                    else:
                                        ledger.pop()
                                    adopted = adopted[:keep]
                                    d = keep
                            fresh = self._alloc_pages(local, npp - d,
                                                      ledger)
                            prow = list(adopted) + fresh
                            scatter[(local, i)] = [trash] * d + fresh
                            if chunked:
                                start_chunk[(local, i)] = d // ppc
                            n_shared += d
                            if register_ok:
                                register.append((local, i, Sb, chain,
                                                 list(prow)))
                            seen[key] = i
                    # copy-on-write: shared pages decode will overwrite
                    for lp in wr_prompt:
                        if self.pool.shared(local, prow[lp]):
                            new = self._alloc_pages(local, 1, ledger)[0]
                            copies.setdefault(local, []).append(
                                (prow[lp], new))
                            to_release.append((local, [prow[lp]]))
                            prow[lp] = new
                    decode_pages = self._alloc_pages(
                        local, len(wr_decode), ledger)
                    table[local, i, :npp] = prow
                    for lp, pg in zip(wr_decode, decode_pages):
                        table[local, i, lp] = pg
        except PagePoolExhausted:
            for local, pages in ledger:
                self.pool.release(local, pages)
            raise
        # commit: COW'd-out shared pages lose this wave's reference
        # (rollback above must NOT see these as held, hence deferred)
        for local, pages in to_release:
            self.pool.release(local, pages)
        pages_held = {
            local: [[int(p) for p in table[local, i] if p != trash]
                    for i in range(len(row_uids))]
            for local, row_uids in uids.items()}

        # device phase: packed prefill over computed rows only
        computed = sorted(scatter)                 # [(local, i), ...]
        per_local: Dict[int, List[int]] = {}
        for local, i in computed:
            per_local.setdefault(local, []).append(i)
        n_computed = len(computed)
        use_chunks = chunked and n_computed > 0
        mask = vals = None
        if cached_tok:
            mask = np.zeros((E, Bb), bool)
            vals = np.zeros((E, Bb), np.int32)
            for (local, i), ft in cached_tok.items():
                mask[local, i] = True
                vals[local, i] = ft
        tok = None
        pending: List[Dict[str, Any]] = []
        fin: Optional[Dict[str, Any]] = None
        if use_chunks:
            # plan (don't dispatch) one descriptor per chunk: chunk k
            # packs every computed row whose adopted prefix doesn't
            # already cover it; chunk 0 reuses the monolithic prefill
            # executable at the chunk_len bucket, chunks >= 1 go through
            # the suffix ladder. Dispatch happens in _dispatch_chunk —
            # immediately (blocking admit) or interleaved with decode
            # ticks under the executor's token budget (deferred admit).
            cl = self.chunk_len
            for k in range(Sb // cl):
                rows_k = [(l, i) for (l, i) in computed
                          if start_chunk[(l, i)] <= k]
                if not rows_k:
                    continue
                pl_k: Dict[int, List[int]] = {}
                for l, i in rows_k:
                    pl_k.setdefault(l, []).append(i)
                Bbk = bucket_for(max(len(v) for v in pl_k.values()),
                                 self.batch_buckets)
                toks_k = np.zeros((E, Bbk, cl), np.int32)
                stbl_k = np.full((E, Bbk, ppc), trash, np.int32)
                # padding rows read the trash page through their prefix
                # table — finite garbage, outputs discarded
                ptbl_k = np.full((E, Bbk, k * ppc), trash, np.int32)
                slot_of_k: Dict[Tuple[int, int], int] = {}
                for l, rows in pl_k.items():
                    for c, i in enumerate(rows):
                        toks_k[l, c] = toks[l, i, k * cl:(k + 1) * cl]
                        stbl_k[l, c] = \
                            scatter[(l, i)][k * ppc:(k + 1) * ppc]
                        if k:
                            ptbl_k[l, c] = table[l, i, :k * ppc]
                        slot_of_k[(l, i)] = c
                pending.append({"k": k, "toks": toks_k, "stbl": stbl_k,
                                "ptbl": ptbl_k, "rows": len(rows_k),
                                "slot_of": slot_of_k})
            # every computed row rides the last chunk (adoption is
            # capped at npp - ppc), so its packed logits carry every
            # first token; dups resolve through their representative
            last = pending[-1]["slot_of"]
            src = np.zeros((E, Bb), np.int32)
            for local, row_uids in uids.items():
                for i in range(len(row_uids)):
                    src[local, i] = last.get(
                        (local, i),
                        last.get((local, dup_src.get((local, i), -1)),
                                 0))
            fin = {"src": src, "mask": mask, "vals": vals,
                   "copies": copies}
        else:
            if n_computed:
                Bbc = bucket_for(max(len(v) for v in per_local.values()),
                                 self.batch_buckets)
                toks_c = np.zeros((E, Bbc, Sb), np.int32)
                stbl = np.full((E, Bbc, npp), trash, np.int32)
                slot_of: Dict[Tuple[int, int], int] = {}
                for local, rows in per_local.items():
                    for c, i in enumerate(rows):
                        toks_c[local, c] = toks[local, i]
                        stbl[local, c] = scatter[(local, i)]
                        slot_of[(local, i)] = c
                with self.tracer.enqueue_span("engine.enqueue",
                                              kind="prefill"):
                    logits, self.kv_pool = self._prefill_fn(Bbc, Sb)(
                        self.params, {"tokens": jnp.asarray(toks_c)},
                        self.kv_pool, jnp.asarray(stbl))
                    tok_c = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                self._issued += 1
                self.stats.prefill_calls += 1
                src = np.zeros((E, Bb), np.int32)
                for local, row_uids in uids.items():
                    for i in range(len(row_uids)):
                        src[local, i] = slot_of.get(
                            (local, i),
                            slot_of.get((local,
                                         dup_src.get((local, i), -1)),
                                        0))
                tok = jnp.take_along_axis(tok_c, jnp.asarray(src),
                                          axis=1)
            if mask is not None:
                tok = jnp.asarray(vals) if tok is None else \
                    jnp.where(jnp.asarray(mask), jnp.asarray(vals), tok)
            assert tok is not None, "wave with rows but no token source"
            # COW copies read post-prefill pages (a dup's source may
            # have been written by this very wave's scatter)
            self._copy_pages(copies)
            self.stats.pages_copied += sum(len(p)
                                           for p in copies.values())
            self.stats.prefill_tokens_computed += n_computed * Sb

        self.stats.prefill_rows_computed += n_computed
        self.stats.prefix_full_hits += n_cached
        self.stats.prefix_dup_rows += n_dup
        self.stats.prefix_pages_shared += n_shared
        pos = np.where(np.arange(C) < Sb, np.arange(C), -1).astype(
            np.int32)
        table_dev = jnp.asarray(table)
        pos_dev = jnp.asarray(np.broadcast_to(pos, (E, C)).copy())
        t_dev = jnp.full((E,), Sb, jnp.int32)
        s = self._bank_sharding()
        if use_chunks:
            if s is not None:
                # same sharding-commit reasoning as below; tok commits
                # separately in _finalize_wave once the last chunk lands
                table_dev, pos_dev, t_dev = jax.device_put(
                    (table_dev, pos_dev, t_dev), s)
            return _Wave(uids=uids, per_row_new=per_row, done=done,
                         cache=None, tok=None, emitted=[],
                         steps_left=steps,
                         table=table_dev, pos=pos_dev, t=t_dev,
                         pages_held=pages_held, register=register,
                         pending_chunks=pending, finalize=fin)
        tok = tok[..., None]
        if spec_ok:
            # per-row position planes (rows advance at different rates);
            # _make_spec_wave performs the sharding commit
            return self._make_spec_wave(
                uids, per_row, done, Bb, Sb, steps, cache=None, tok=tok,
                row_pos=jnp.broadcast_to(pos_dev[:, None], (E, Bb, C)),
                row_t=jnp.broadcast_to(t_dev[:, None], (E, Bb)),
                table=table_dev, pages_held=pages_held,
                register=register)
        if s is not None:
            # commit every wave-carried array to the bank sharding now:
            # tick 1 must present the decode executable with the same
            # input shardings as every later tick (whose pos/t/tok come
            # out of the decode itself via out_shardings), or pjit mints
            # one executable per sharding combination and the
            # bounded-compile invariant breaks
            table_dev, pos_dev, t_dev, tok = jax.device_put(
                (table_dev, pos_dev, t_dev, tok), s)
        return _Wave(uids=uids, per_row_new=per_row, done=done,
                     cache=None, tok=tok, emitted=[tok[..., 0]],
                     steps_left=steps,
                     table=table_dev, pos=pos_dev, t=t_dev,
                     pages_held=pages_held, register=register)

    # -- chunked prefill dispatch ----------------------------------------
    def _dispatch_chunk(self, w: _Wave) -> int:
        """Issue the wave's next pending prefill chunk (FIFO). Chunk 0
        goes through the monolithic prefill executable at the chunk_len
        bucket; later chunks attend over the pages earlier chunks (or an
        adopted prefix) already wrote. When the last chunk is issued the
        wave is finalized — its first-token plane is assembled and it
        becomes decode-eligible. Returns prompt tokens dispatched (real
        rows x chunk_len, the budget currency)."""
        d = w.pending_chunks.pop(0)
        k = d["k"]
        Bbk = d["toks"].shape[1]
        with self.tracer.enqueue_span("engine.enqueue", kind="chunk"):
            if k == 0:
                logits, self.kv_pool = self._prefill_fn(
                    Bbk, self.chunk_len)(
                    self.params, {"tokens": jnp.asarray(d["toks"])},
                    self.kv_pool, jnp.asarray(d["stbl"]))
            else:
                logits, self.kv_pool = self._suffix_fn(Bbk, k)(
                    self.params, {"tokens": jnp.asarray(d["toks"])},
                    self.kv_pool, jnp.asarray(d["ptbl"]),
                    jnp.asarray(d["stbl"]))
        self._issued += 1
        w.last_seq = self._issued
        self.stats.prefill_calls += 1
        spent = d["rows"] * self.chunk_len
        self.stats.prefill_tokens_computed += spent
        self.tracer.event("wave.chunk", wave=w.wave_id, chunk=k,
                          tokens=spent,
                          remaining=len(w.pending_chunks))
        if not w.pending_chunks:
            w._tok_c = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self._finalize_wave(w)
        return spent

    def _finalize_wave(self, w: _Wave) -> None:
        """Last chunk landed: gather every row's first token from the
        final chunk's packed logits (cached rows overlay their known
        token), apply the deferred COW copies, and commit the token
        plane to the bank sharding — the wave is now decode-eligible."""
        f = w.finalize
        w.finalize = None
        tok = jnp.take_along_axis(w._tok_c, jnp.asarray(f["src"]),
                                  axis=1)
        w._tok_c = None
        if f["mask"] is not None:
            tok = jnp.where(jnp.asarray(f["mask"]),
                            jnp.asarray(f["vals"]), tok)
        # COW copies must read fully-written prompt pages, so they wait
        # for the last chunk (the unchunked path runs them post-prefill
        # for the same reason)
        self._copy_pages(f["copies"])
        self.stats.pages_copied += sum(len(p)
                                       for p in f["copies"].values())
        tok = tok[..., None]
        s = self._bank_sharding()
        if s is not None:
            tok = jax.device_put(tok, s)
        w.tok = tok
        w.emitted.append(tok[..., 0])

    def prefill_step(self, budget: int = 0) -> int:
        """Dispatch pending prefill chunks FIFO across active waves —
        at least one chunk per call so whales always make progress —
        stopping once ``budget`` prompt tokens (0 = unbounded) have been
        issued. The executor calls this between admission and decode
        ticks, which is the disaggregation: a whale's remaining chunks
        interleave with co-resident waves' decode steps instead of
        monopolising the dispatch slot. Returns tokens dispatched."""
        spent = 0
        for w in list(self._active):
            while w.pending_chunks:
                spent += self._dispatch_chunk(w)
                if budget and spent >= budget:
                    return spent
        return spent

    @property
    def has_pending_chunks(self) -> bool:
        return any(w.pending_chunks for w in self._active)

    # -- decoding --------------------------------------------------------
    def tick(self, *, defer: bool = False) -> int:
        """Advance every active wave one decode step — one dispatch per
        wave covers all member experts. Returns waves advanced.

        ``defer=False`` (the blocking reference) materialises each
        wave's new token plane immediately — one host block per wave —
        and harvests before returning. ``defer=True`` only enqueues:
        ``wave.tok`` feeds the next decode without ever leaving the
        device, and the host blocks once per wave at ``harvest()``.
        """
        advanced = 0
        for w in list(self._active):
            # a wave with prefill chunks still pending has no sampled
            # token yet — decode only admits it once its last chunk
            # lands (w.tok set in _finalize_wave)
            if w.tok is None:
                continue
            if w.steps_left > 0:
                Bb = w.tok.shape[1]
                if w.sp_decode is None and self.tracer.enabled:
                    # covers every tick enqueued until the next harvest
                    # sync closes it (one span per materialise window)
                    w.sp_decode = self.tracer.begin_device(
                        "wave.verify" if w.spec else "wave.decode",
                        wave=w.wave_id, Bb=Bb)
                if w.spec:
                    self._spec_tick(w, Bb)
                    advanced += 1
                    if not defer:
                        self._materialize_spec(w)
                    continue
                with self.tracer.enqueue_span("engine.enqueue",
                                              kind="decode"):
                    if self.kv_layout == "paged":
                        # the pool buffers thread through every wave's
                        # tick (donated each dispatch); pos/t stay
                        # per-wave
                        logits, self.kv_pool, w.pos, w.t = \
                            self._decode_fn(Bb)(
                                self.params, self.kv_pool, w.table,
                                w.pos, w.t, {"token": w.tok})
                    else:
                        logits, w.cache = self._decode_fn(Bb)(
                            self.params, w.cache, {"token": w.tok})
                    w.tok = jnp.argmax(logits, axis=-1).astype(
                        jnp.int32)[..., None]
                self._issued += 1
                w.last_seq = self._issued
                w.emitted.append(w.tok[..., 0])
                w.steps_left -= 1
                self.stats.decode_steps += 1
                advanced += 1
                if not defer:
                    self._materialize(w, len(w.emitted))
        if not defer:
            self.harvest()
        return advanced

    def _spec_tick(self, w: _Wave, Bb: int) -> None:
        """One verify dispatch for a speculative wave: every active row
        advances by at least one token (the corrected greedy token when
        all drafts miss), so the wave finishes in at most ``steps``
        ticks and usually far fewer. ``steps_left`` stays the plain
        tick-count upper bound; harvest zeroes it early once every row
        has its tokens."""
        args = (w.row_pos, w.row_t, w.tok[..., 0], w.cap,
                self.draft_state)
        with self.tracer.enqueue_span("engine.enqueue", kind="verify"):
            if self.kv_layout == "paged":
                (emit, adv, acc, tok2, self.kv_pool, w.row_pos, w.row_t,
                 self.draft_state) = self._verify_fn(
                    Bb, self.speculate_k)(
                    self.params, self.kv_pool, w.table, *args)
            else:
                (emit, adv, acc, tok2, w.cache, w.row_pos, w.row_t,
                 self.draft_state) = self._verify_fn(
                    Bb, self.speculate_k)(self.params, w.cache, *args)
        self._issued += 1
        w.last_seq = self._issued
        w.tok = tok2[..., None]
        w.spec_pending.append((emit, adv, acc))
        w.steps_left -= 1
        self.stats.decode_steps += 1
        self.stats.verify_steps += 1

    # -- harvest ---------------------------------------------------------
    def _materialize(self, w: _Wave, upto: int) -> None:
        """Bring ``emitted[:upto]`` to host in one blocking transfer."""
        upto = min(upto, len(w.emitted))
        if upto <= w.n_host:
            return
        with self.tracer.span("engine.sync"):
            host = jax.device_get(w.emitted[w.n_host:upto])
        if upto == len(w.emitted):
            # the newest plane came out of the wave's latest dispatch
            self._covered = max(self._covered, w.last_seq)
        for k, plane in enumerate(host):
            w.emitted[w.n_host + k] = np.asarray(plane)
        w.n_host = upto
        self.stats.host_blocks += 1
        # blessed sync site: the device_get above completed everything
        # enqueued for this wave, so its open device spans close here —
        # tracing rides the sync the engine already pays for (O002)
        if w.sp_prefill is not None:
            self.tracer.end_device(w.sp_prefill, planes=upto)
            w.sp_prefill = None
        if w.sp_decode is not None:
            self.tracer.end_device(w.sp_decode, planes=upto)
            w.sp_decode = None

    def _materialize_spec(self, w: _Wave) -> None:
        """Drain a speculative wave's pending (emit, adv, acc) verify
        triples (plus the prefill token plane the first time) to host in
        one batched transfer, advancing each row's token buffer by its
        *actual* accepted count — the host learns real progress, which
        is what lets harvest retire the wave after ~steps/E[adv] ticks
        instead of steps."""
        if w.spec_seeded and not w.spec_pending:
            return
        with self.tracer.span("engine.sync"):
            first, triples = jax.device_get((w.emitted[0],
                                             w.spec_pending))
        self._covered = max(self._covered, w.last_seq)
        self.stats.host_blocks += 1
        # blessed sync site (the speculative twin of _materialize)
        if w.sp_prefill is not None:
            self.tracer.end_device(w.sp_prefill)
            w.sp_prefill = None
        if w.sp_decode is not None:
            self.tracer.end_device(w.sp_decode,
                                   verifies=len(triples))
            w.sp_decode = None
        if not w.spec_seeded:
            w.emitted[0] = np.asarray(first)
            w.n_host = max(w.n_host, 1)
            w.host_buf[:, :, 0] = w.emitted[0]
            np.maximum(w.host_fill, 1, out=w.host_fill)
            w.spec_seeded = True
        k = self.speculate_k
        for emit, adv, acc in triples:
            emit, adv, acc = (np.asarray(x) for x in (emit, adv, acc))
            for local, row_uids in w.uids.items():
                for i in range(len(row_uids)):
                    a = int(adv[local, i])
                    if a > 0:
                        f = int(w.host_fill[local, i])
                        w.host_buf[local, i, f:f + a] = emit[local, i, :a]
                        w.host_fill[local, i] = f + a
                    c = int(acc[local, i])
                    if c >= 0:
                        self.stats.tokens_drafted += k
                        self.stats.tokens_accepted += c
        w.spec_pending = []

    def _harvest_spec(self, w: _Wave) -> None:
        """Emit every speculative row whose token buffer is full; once
        all rows are done, zero ``steps_left`` so the wave retires now
        instead of burning its remaining tick budget.

        The device transfer is gated the same way plain waves gate
        ``_materialize`` (``need > n_host``): each verify advances a row
        by at most ``k + 1`` tokens, so until the pending triples could
        arithmetically complete some unfinished row there is nothing to
        emit and the sync is skipped — without this, speculative waves
        host-block every harvest and give back much of the verify win.
        """
        if w.spec_pending and w.steps_left > 0:
            bound = (len(w.spec_pending) * (self.speculate_k + 1)
                     + (0 if w.spec_seeded else 1))
            if not any(not w.done[local][i]
                       and w.host_fill[local, i] + bound
                       >= w.per_row_new[local][i]
                       for local, row_uids in w.uids.items()
                       for i in range(len(row_uids))):
                return
        self._materialize_spec(w)
        for local, row_uids in w.uids.items():
            for i, uid in enumerate(row_uids):
                if w.done[local][i]:
                    continue
                n = w.per_row_new[local][i]
                if w.host_fill[local, i] >= n:
                    seq = np.array(w.host_buf[local, i, :n], np.int32)
                    self._finished.append((local, uid, seq))
                    self.stats.tokens_generated += n
                    w.done[local][i] = True
        if all(all(d) for d in w.done.values()):
            w.steps_left = 0
        if w.steps_left <= 0 and all(all(d) for d in w.done.values()):
            self._active.remove(w)
            if self.kv_layout == "paged":
                self._retire_paged(w)

    def harvest(self) -> None:
        """Emit every row whose ``max_new`` tokens are all available and
        retire fully-done waves.

        Per wave, all planes any completable row needs are materialised
        in a single batched device→host transfer (at most one host
        block per wave per call) — the per-tick sync of the old engines
        is gone from the deferred path entirely.
        """
        for w in list(self._active):
            if w.spec:
                self._harvest_spec(w)
                continue
            have = len(w.emitted)
            need = 0
            for local, row_uids in w.uids.items():
                for i in range(len(row_uids)):
                    if (not w.done[local][i]
                            and w.per_row_new[local][i] <= have):
                        need = max(need, w.per_row_new[local][i])
            if need > w.n_host:
                self._materialize(w, need)
            for local, row_uids in w.uids.items():
                for i, uid in enumerate(row_uids):
                    if w.done[local][i] or w.per_row_new[local][i] > have:
                        continue
                    seq = np.asarray(
                        [w.emitted[t][local, i] for t in
                         range(w.per_row_new[local][i])], np.int32)
                    self._finished.append((local, uid, seq))
                    self.stats.tokens_generated += len(seq)
                    w.done[local][i] = True
            if w.steps_left <= 0 and all(all(d) for d in w.done.values()):
                self._active.remove(w)
                if self.kv_layout == "paged":
                    self._retire_paged(w)

    def _retire_paged(self, w: _Wave) -> None:
        """Register computed prefixes in the cross-wave cache (the
        first-token plane is host-side by now, so registration costs no
        sync), then release every page the wave's rows held."""
        for local, i, padded_len, chain, pages in w.register:
            self.prefix_cache.insert(local, padded_len, chain, pages,
                                     int(w.emitted[0][local, i]))
        for local, rows in w.pages_held.items():
            for pages in rows:
                self.pool.release(local, pages)
        w.pages_held = {}
        w.register = []

    def poll(self) -> List[Tuple[int, Any, np.ndarray]]:
        """Drain finished (local expert, uid, tokens) triples."""
        out, self._finished = self._finished, []
        return out

    @property
    def n_active(self) -> int:
        return len(self._active)

    @property
    def has_pending(self) -> bool:
        """Active waves or finished rows not yet polled."""
        return bool(self._active or self._finished)


# ---------------------------------------------------------------------------
# Dispatch executors
# ---------------------------------------------------------------------------


class DispatchExecutor:
    """How one scheduler step drives its shards.

    ``run_step`` first drives the expert hub's lifecycle (a no-op on
    hubless schedulers), then issues every shard's prefill, then every
    shard's decode tick, then harvests — the ``defer`` flag decides
    whether each dispatch blocks on its own device→host copy (serial,
    the reference) or whether nothing blocks until the single batched
    harvest transfer per wave (overlapped). Because both orders run the
    identical compute graph, they are token-identical by construction;
    only ``EngineStats.host_blocks`` differs. Hub slot installs ride
    the same ordering: with the overlapped executor they are enqueued
    ahead of the step's decode ticks, so checkpoint staging (a worker
    thread) and the install scatter overlap in-flight decode.
    """

    name = "base"
    defer = False

    def run_step(self, sched) -> None:
        sched._service_hub()
        sched._admit_batches(defer=self.defer)
        # prefill/decode disaggregation: pending chunks of partially-
        # prefilled waves are issued here, bounded per step by
        # SchedulerConfig.prefill_tokens_per_step, so the decode ticks
        # below run every step even while a whale prompt prefills (on
        # the blocking path admission already drained its chunks and
        # this is a no-op)
        sched._prefill_chunks()
        sched._tick_engines(defer=self.defer)
        sched._harvest_engines()


class SerialExecutor(DispatchExecutor):
    """Reference behaviour: every admit/tick materialises its sampled
    token immediately, blocking the host once per tick per wave before
    the next shard's work is issued."""

    name = "serial"
    defer = False


class OverlappedExecutor(DispatchExecutor):
    """Async dispatch: prefills and decode ticks for *all* shards are
    enqueued before anything blocks; tokens stay on device and the host
    blocks at most once per wave per step, inside the batched harvest.
    Prefill of one shard overlaps decode of another on the device
    queue."""

    name = "overlapped"
    defer = True


def get_executor(executor) -> DispatchExecutor:
    """Resolve ``'serial'`` / ``'overlapped'`` / an instance."""
    if isinstance(executor, DispatchExecutor):
        return executor
    if executor == "serial":
        return SerialExecutor()
    if executor == "overlapped":
        return OverlappedExecutor()
    raise ValueError(f"unknown executor {executor!r}; expected 'serial', "
                     "'overlapped' or a DispatchExecutor instance")
