"""Sharded expert placement: banked multi-expert engines on a mesh.

PR 1's serving stack instantiates one independent ``ExpertEngine`` per
expert on a single implicit device: K experts mean K separate jit
caches (K x ``len(batch_buckets) * len(len_buckets)`` executables), K
serial prefill dispatches per scheduler step, and no use of the mesh
machinery at all. This module makes placement first-class:

  * ``plan_placement`` walks an ``ExpertRegistry``, groups *homogeneous*
    experts (same architecture config and bucket ladders) and rebinds
    each group to one ``BankedEngine``; heterogeneous or legacy backends
    keep their own singleton shard. The result is a ``PlacementPlan``
    the scheduler and router consume (shard ids ride through
    ``RouteResult`` / ``Response``).
  * ``BankedEngine`` is the E>1 view of the shared ``EngineCore``
    (``serve.core``): the params of its member experts are stacked
    along a leading ``expert`` axis and *every* member's micro-batch is
    served by a single jitted dispatch — ``vmap`` over the expert axis,
    optionally partitioned across devices by GSPMD via a 1-D ``expert``
    mesh (``launch.mesh.make_expert_mesh``). Because the bank reuses one
    bucket ladder, the executable count is bounded at
    ``len(batch_buckets) * len(len_buckets)`` prefills +
    ``len(batch_buckets)`` decode steps *total* — not per expert.

On CPU the expert mesh is driven by a forced host device count
(``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before backend
init); on a TPU slice the same code places banks across real chips.
"""
from __future__ import annotations

import dataclasses
import gc
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np
from jax.sharding import Mesh

from ..core.registry import ExpertSpec
from .core import EngineCore, EngineStats
from .engine import ExpertEngine


# ---------------------------------------------------------------------------
# Banked engine
# ---------------------------------------------------------------------------


class BankedEngine:
    """E homogeneous experts served by one vmapped/sharded dispatch —
    the E>1 shim over ``EngineCore``.

    Params are stacked on a leading expert axis; prefill/decode are
    ``vmap`` over that axis, jitted once per (batch bucket, len bucket)
    for the *whole bank*. With ``mesh`` (1-D over ``"expert"``, size
    dividing ``n_experts``) the stacked params, caches and token planes
    are sharded over devices, so each device runs only its resident
    experts' slices of the single executable.
    """

    def __init__(self, model, params_list: Sequence[Any], *,
                 max_len: int = 256, min_len_bucket: int = 8,
                 len_buckets: Optional[Sequence[int]] = None,
                 batch_buckets: Optional[Sequence[int]] = None,
                 mesh: Optional[Mesh] = None,
                 kv_layout: str = "ring", page_size: int = 8,
                 pool_pages: Optional[int] = None,
                 chunk_len: Optional[int] = None,
                 speculate_k: int = 0, draft=None):
        if not params_list:
            raise ValueError("BankedEngine needs at least one expert")
        self.core = EngineCore(model, params_list, max_len=max_len,
                               min_len_bucket=min_len_bucket,
                               len_buckets=len_buckets,
                               batch_buckets=batch_buckets, mesh=mesh,
                               kv_layout=kv_layout, page_size=page_size,
                               pool_pages=pool_pages, chunk_len=chunk_len,
                               speculate_k=speculate_k, draft=draft)
        self.model = model
        self.n_experts = self.core.n_experts
        self.mesh = self.core.mesh
        self.max_len = self.core.max_len
        self.len_buckets = self.core.len_buckets
        self.batch_buckets = self.core.batch_buckets
        self.kv_layout = self.core.kv_layout

    @property
    def params(self):
        """The stacked (E, ...) params pytree — read through the core,
        which the expert hub may swap under us (a slot install donates
        the previous stacked buffer, so a cached reference would be a
        dead array)."""
        return self.core.params

    @property
    def stats(self) -> EngineStats:
        return self.core.stats

    def bind_tracer(self, tracer) -> None:
        """Install a lifecycle tracer on the core (None disables).
        Device spans open at admit/tick and close only at the core's
        harvest sync points — tracing adds no host blocks."""
        self.core.bind_tracer(tracer)

    # -- admission -------------------------------------------------------
    def pad_shape(self, n_rows: int, prompt_len: int) -> Tuple[int, int]:
        """(batch bucket, length bucket) this admission would snap to."""
        return self.core.pad_shape(n_rows, prompt_len)

    def admit(self, groups: Mapping[int, Tuple[Sequence[Any],
                                               Sequence[np.ndarray],
                                               Sequence[int]]],
              *, defer: bool = False) -> None:
        """Prefill one (E, Bb, Sb) wave: every member expert's micro-batch
        in a single dispatch. A wave with no rows at all is a no-op (the
        scheduler only calls with traffic; ``ExpertEngine.admit`` by
        contrast rejects empties loudly). See ``EngineCore.admit_wave``
        for padding rules and the ``defer`` contract.
        """
        self.core.admit_wave(groups, defer=defer)

    # -- decoding --------------------------------------------------------
    def tick(self, *, defer: bool = False) -> int:
        """Advance every active wave one decode step — one dispatch per
        wave covers all member experts. Returns waves advanced."""
        return self.core.tick(defer=defer)

    def harvest(self) -> None:
        """Materialise (one batched transfer per wave) and emit finished
        rows; retire fully-done waves."""
        self.core.harvest()

    def poll(self) -> List[Tuple[int, Any, np.ndarray]]:
        """Drain finished (local expert, uid, tokens) triples."""
        return self.core.poll()

    @property
    def n_active(self) -> int:
        return self.core.n_active

    @property
    def has_pending(self) -> bool:
        """Active waves or finished rows not yet polled."""
        return self.core.has_pending


@dataclasses.dataclass
class BankMember:
    """Registry-facing handle: one expert's slot inside a BankedEngine."""
    bank: BankedEngine
    local: int

    def pad_shape(self, n_rows: int, prompt_len: int) -> Tuple[int, int]:
        return self.bank.pad_shape(n_rows, prompt_len)

    @property
    def batch_buckets(self) -> Tuple[int, ...]:
        return self.bank.batch_buckets

    @property
    def kv_layout(self) -> str:
        return self.bank.kv_layout

    @property
    def stats(self) -> EngineStats:
        return self.bank.stats


# ---------------------------------------------------------------------------
# Placement planning
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Shard:
    """One dispatch group: either a bank of co-located experts or a
    singleton wrapping whatever backend the registry already had."""
    sid: int
    experts: Tuple[int, ...]            # global registry indices
    bank: Optional[BankedEngine] = None
    devices: Tuple[Any, ...] = ()

    @property
    def banked(self) -> bool:
        return self.bank is not None


@dataclasses.dataclass
class PlacementPlan:
    shards: List[Shard]
    shard_of: Dict[int, int]            # expert index -> shard id
    mesh: Optional[Mesh] = None

    def describe(self, names: Optional[Sequence[str]] = None) -> str:
        lines = []
        for s in self.shards:
            label = ", ".join(names[e] if names else str(e)
                              for e in s.experts)
            dev = (f" on {len(s.devices)} device(s)" if s.devices else "")
            kind = "bank" if s.banked else "solo"
            lines.append(f"shard {s.sid} [{kind}]{dev}: {label}")
        return "\n".join(lines)


# Bank grouping is keyed on ``ExpertSpec`` (core/registry.py) — the one
# catalog entry type the hub, router metadata and this planner share.
# Equal specs mean identical shapes, identical executables (a paged
# member's spec additionally carries its page-pool geometry, since the
# bank stacks pools on the expert axis); ``spec.bankable`` excludes
# capacity-dispatch MoE, whose outputs depend on batch padding.


def _bank_submesh(n_experts: int, mesh: Optional[Mesh], offset: int = 0):
    """Largest-divisor slice of the expert mesh this bank can shard over.

    ``offset`` rotates the device pool so successive banks land on
    *disjoint* slices (wrapping once the pool is exhausted) instead of
    all piling onto the mesh's first devices.
    """
    if mesh is None or "expert" not in mesh.shape:
        return None, ()
    devs = np.roll(np.asarray(mesh.devices).reshape(-1),
                   -(offset % max(mesh.shape["expert"], 1)))
    for d in range(min(len(devs), n_experts), 0, -1):
        if n_experts % d == 0:
            if d == 1:
                return None, ()   # unsharded: params stay wherever jax
                #                   puts them, claim no device
            sub = Mesh(devs[:d], axis_names=("expert",))
            return sub, tuple(devs[:d])
    return None, ()


def plan_placement(registry, *, mesh: Optional[Mesh] = None,
                   min_bank: int = 2) -> PlacementPlan:
    """Group homogeneous ``ExpertEngine`` backends into ``BankedEngine``s
    and lay the shards out over ``mesh`` (1-D ``expert`` axis, see
    ``launch.mesh.make_expert_mesh``).

    Mutates ``registry`` in place: banked entries' backends become
    ``BankMember`` handles (the per-expert engines they replace are
    dropped, their params moving into the stacked bank). Groups smaller
    than ``min_bank`` and non-``ExpertEngine`` backends keep singleton
    shards. Returns the ``PlacementPlan`` the scheduler/router consume.
    """
    by_sig: Dict[ExpertSpec, List[int]] = {}
    for e in range(len(registry)):
        backend = registry[e].backend
        if isinstance(backend, BankMember):
            raise ValueError(
                f"expert {registry[e].name!r} is already bank-placed; "
                "plan_placement rebinds backends in place and cannot "
                "re-plan a planned registry — rebuild it from engines")
        if isinstance(backend, ExpertEngine):
            # derive from the live engine (authoritative) and publish on
            # the entry, so hub/router consumers read the same spec the
            # plan grouped by
            spec = backend.spec
            registry[e].spec = spec
            if spec.bankable:
                by_sig.setdefault(spec, []).append(e)

    shards: List[Shard] = []
    shard_of: Dict[int, int] = {}
    cursor = 0                      # rotates banks onto disjoint devices
    for experts in by_sig.values():
        if len(experts) < min_bank:
            continue
        engines = [registry[e].backend for e in experts]
        submesh, devices = _bank_submesh(len(experts), mesh, cursor)
        cursor += len(devices)
        bank = BankedEngine(
            engines[0].model, [eng.params for eng in engines],
            max_len=engines[0].max_len,
            len_buckets=engines[0].len_buckets,
            batch_buckets=engines[0].batch_buckets, mesh=submesh,
            kv_layout=engines[0].kv_layout,
            page_size=(engines[0].core.page
                       if engines[0].kv_layout == "paged" else 8),
            pool_pages=(engines[0].core.pool.n_pages
                        if engines[0].kv_layout == "paged" else None),
            chunk_len=(engines[0].core.chunk_len
                       if engines[0].kv_layout == "paged" else None),
            speculate_k=engines[0].core.speculate_k,
            draft=engines[0].core.draft_name)
        sid = len(shards)
        shards.append(Shard(sid=sid, experts=tuple(experts), bank=bank,
                            devices=devices))
        for local, e in enumerate(experts):
            registry[e].backend = BankMember(bank, local)
            shard_of[e] = sid
    if shards:
        # each replaced engine's core sits in reference cycles (its jit
        # closures and stats point back at it), so dropping the last
        # reference frees nothing: collect now, or their params and KV
        # pools stay on the device beside the bank's stacked copy
        del backend, engines
        gc.collect()
    for e in range(len(registry)):
        if e in shard_of:
            continue
        sid = len(shards)
        shards.append(Shard(sid=sid, experts=(e,)))
        shard_of[e] = sid
    return PlacementPlan(shards=shards, shard_of=shard_of, mesh=mesh)
