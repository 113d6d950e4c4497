"""Routing front-end: ExpertMatcher + Pallas kernels + fingerprint cache.

The seed server jitted ``matcher.route`` wholesale, which (a) re-encoded
every sample under *all* K expert AEs for fine assignment and (b) left
the Pallas ``cosine_scores`` kernel dead. This front-end:

  * snaps routing batches to power-of-two row buckets, so the jit cache
    of the scoring functions stays bounded under arbitrary traffic;
  * runs fine assignment per routed-expert *group* — each sample is
    encoded only under its own expert, and the group's (z, centroids,
    mask) triple goes through the fused ``cosine_scores`` kernel
    (Mosaic on a TPU, the Pallas interpreter on the CPU — chosen by
    the backend in ``repro.kernels.mode``);
  * memoizes routing decisions per client fingerprint in an LRU: clients
    in the paper's setting re-query with the same dataset fingerprint,
    so repeat routes cost a dict lookup instead of K AE forwards.

The coarse metric honours ``MatcherConfig``: ``use_kernel=True`` scores
through the fused Pallas expert-score kernel (with real BN statistics —
see ``ExpertMatcher.coarse_scores``), otherwise the vmapped reference.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import hashlib
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autoencoder as ae
from ..core.matcher import ExpertMatcher
from ..obs.trace import NULL_TRACER
from .engine import bucket_for, make_buckets


@dataclasses.dataclass
class RouteResult:
    coarse: np.ndarray        # (B, top_k) expert indices, best first
    coarse_score: np.ndarray  # (B, top_k) scores (lower = better)
    fine: np.ndarray          # (B,) class index within the top-1 expert
    shard: Optional[np.ndarray] = None  # (B,) placement shard ids
    cache_hits: int = 0


class PrefixLRU:
    """Prompt-prefix index: the fingerprint-LRU idiom applied to prompt
    pages instead of client features.

    The paper's cohorts re-query the server with near-identical prompts;
    ``observe`` fingerprints the first KV page of each prompt (shorter
    prompts hash whole) and returns a grouping key. The scheduler uses
    equal keys to co-admit prefix-sharing rows into one wave, which is
    what lets the paged engine deduplicate their prefill and share
    pages; the LRU's repeat counter is the cohort-detection signal
    surfaced in routing stats.
    """

    def __init__(self, page: int = 8, capacity: int = 4096):
        self.page = page
        self.capacity = capacity
        self._lru: "collections.OrderedDict[bytes, int]" = \
            collections.OrderedDict()
        self.stats = {"observed": 0, "repeats": 0}

    def observe(self, prompt: np.ndarray) -> bytes:
        head = np.ascontiguousarray(
            np.asarray(prompt, np.int32)[:self.page]).tobytes()
        key = hashlib.blake2b(head, digest_size=16).digest()
        self.stats["observed"] += 1
        seen = self._lru.pop(key, 0)
        if seen:
            self.stats["repeats"] += 1
        self._lru[key] = seen + 1
        while len(self._lru) > self.capacity:
            self._lru.popitem(last=False)
        return key


class Router:
    """Batch router with bounded jit shapes and a fingerprint LRU.

    ``shard_of`` (expert index -> shard id, from a ``PlacementPlan``)
    makes every ``RouteResult`` carry the shard serving each row, so the
    scheduler can plan per-shard dispatch groups and responses demux
    back through the right bank. Shard ids are derived from the top-1
    expert *after* the LRU, so cached decisions stay placement-agnostic.
    """

    def __init__(self, matcher: ExpertMatcher, *, cache_size: int = 4096,
                 use_fine_kernel: bool = True, max_rows: int = 256,
                 shard_of: Optional[Dict[int, int]] = None):
        self.matcher = matcher
        self.shard_of = dict(shard_of) if shard_of is not None else None
        self.use_fine_kernel = use_fine_kernel and \
            matcher.centroids is not None
        self.row_buckets = make_buckets(1, max_rows)
        self._lru: "collections.OrderedDict[bytes, tuple]" = \
            collections.OrderedDict()
        self.cache_size = cache_size
        self.stats = {"routed": 0, "cache_hits": 0, "score_calls": 0}
        # per-expert top-1 hit counts — the popularity signal the expert
        # hub's eviction policy reads (ExpertHub.bind_popularity shares
        # this very Counter, so routing decisions feed residency).
        # Once hub-bound the Counter is cross-thread shared state:
        # bind_popularity(..., router=self) installs the hub lock here
        # and route() increments under it (rule R001)
        self.expert_hits: collections.Counter = collections.Counter()
        self.hits_lock: Optional[threading.Lock] = None
        # the scheduler's tracer (Scheduler.bind_tracer): each blocking
        # device-to-host transfer below is a route.wait span
        self.tracer = NULL_TRACER
        self._coarse = jax.jit(matcher.assign_coarse_topk)
        self._fine_ref = jax.jit(matcher.assign_fine)
        # encode a group under ONE expert's AE (params sliced by index)
        self._encode_at = jax.jit(self._encode_at_impl)

    def _encode_at_impl(self, x, e):
        params = jax.tree_util.tree_map(lambda a: a[e],
                                        self.matcher.bank_params)
        state = jax.tree_util.tree_map(lambda a: a[e],
                                       self.matcher.bank_states)
        z, _ = ae.encode(params, state, x, train=False)
        return z

    # ------------------------------------------------------------------
    def _pad_rows(self, x: np.ndarray) -> Tuple[jnp.ndarray, int]:
        n = len(x)
        nb = bucket_for(n, self.row_buckets)
        if nb > n:
            x = np.concatenate([x, np.zeros((nb - n,) + x.shape[1:],
                                            x.dtype)])
        return jnp.asarray(x), n

    def _fine_grouped(self, x: np.ndarray,
                      coarse_top1: np.ndarray) -> np.ndarray:
        """Per-expert-group fine assignment through the cosine kernel."""
        from ..kernels import ops as kops
        m = self.matcher
        fine = np.zeros(len(x), np.int64)
        for e in np.unique(coarse_top1):
            rows = np.nonzero(coarse_top1 == e)[0]
            xg, n = self._pad_rows(x[rows])
            z = self._encode_at(xg, jnp.int32(e))
            sim = kops.cosine_scores(z, m.centroids[int(e)],
                                     m.centroid_mask[int(e)])
            best = jnp.argmax(sim, axis=-1)
            with self.tracer.span("route.wait"):
                fine[rows] = np.asarray(best)[:n]
            self.stats["score_calls"] += 1
        return fine

    # ------------------------------------------------------------------
    def route(self, feats: np.ndarray) -> RouteResult:
        """feats: (B, 784) float32 fingerprints -> routing decisions."""
        feats = np.asarray(feats, np.float32)
        B = len(feats)
        top_k = self.matcher.config.top_k
        coarse = np.zeros((B, top_k), np.int64)
        score = np.zeros((B, top_k), np.float32)
        fine = np.zeros(B, np.int64)

        keys = [f.tobytes() for f in feats]
        miss = []
        hits = 0
        for i, k in enumerate(keys):
            got = self._lru.get(k)
            if got is not None:
                coarse[i], score[i], fine[i] = got
                self._lru.move_to_end(k)
                hits += 1
            else:
                miss.append(i)

        # chunk misses to the largest row bucket so batches beyond it
        # can't mint fresh executable shapes
        step = self.row_buckets[-1]
        for lo in range(0, len(miss), step):
            chunk = miss[lo:lo + step]
            xm = feats[chunk]
            xp, n = self._pad_rows(xm)
            c, s = self._coarse(xp)
            with self.tracer.span("route.wait"):
                c = np.asarray(c)[:n]
                s = np.asarray(s)[:n]
            if self.use_fine_kernel:
                f = self._fine_grouped(xm, c[:, 0])
            elif self.matcher.centroids is not None:
                f = self._fine_ref(xp, jnp.asarray(
                    np.pad(c[:, 0], (0, len(xp) - n))))
                with self.tracer.span("route.wait"):
                    f = np.asarray(f)[:n]
            else:
                f = np.zeros(n, np.int64)
            for j, i in enumerate(chunk):
                coarse[i], score[i], fine[i] = c[j], s[j], f[j]
                self._remember(keys[i], (c[j], s[j], f[j]))

        self.stats["routed"] += B
        self.stats["cache_hits"] += hits
        with (self.hits_lock if self.hits_lock is not None
              else contextlib.nullcontext()):
            for e in coarse[:, 0]:
                self.expert_hits[int(e)] += 1
        shard = None
        if self.shard_of is not None:
            shard = np.asarray([self.shard_of.get(int(e), -1)
                                for e in coarse[:, 0]], np.int64)
        return RouteResult(coarse, score, fine, shard=shard,
                           cache_hits=hits)

    def _remember(self, key: bytes, value) -> None:
        # copy: the (c, s) rows arrive as views into a whole routed
        # chunk's (rows, top_k) arrays — caching the views would pin
        # every chunk's full arrays in the LRU for their lifetime
        c, s, f = value
        self._lru[key] = (np.array(c, np.int64), np.array(s, np.float32),
                          int(f))
        if len(self._lru) > self.cache_size:
            self._lru.popitem(last=False)
