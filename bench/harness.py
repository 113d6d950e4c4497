"""One run of one cell: set-up, warm-up, the measured window, the check.

Set-up builds the system under test the way a deployment would: the
benchmark makes the client datasets, trains the matcher's autoencoders
and makes every expert's weights from the seed (through the cell's model
family, ``bench/families/``), then hands them to the program's own
entry points (``build_matcher``, ``ExpertEngine``, ``plan_placement``,
``RoutedServer``). Warm-up drives every shape the cell's traffic can
reach through the same server. The window is an open loop over
``RoutedServer.submit`` / ``RoutedServer.step`` on the wall clock. What
the window produced is kept for the check, which runs once the server
is gone.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import os
import sys
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from . import fingerprints, matcher_ref, traffic, weights
from .spec import ROOT, Cell

sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core import ExpertRegistry, MatcherConfig, build_matcher  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_expert_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.obs.trace import Tracer  # noqa: E402
from repro.serve import (ExpertEngine, Request, RoutedServer,  # noqa: E402
                         plan_placement)

PAGE = 8
N_PER_DATASET = 600      # fingerprints per dataset; half train the AEs
AE_EPOCHS = 40
DRAIN_LIMIT_S = 60.0     # how long past the close a request may finish
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def start() -> "CompileLog":
    """Per-process set-up shared by every entry point: JAX's persistent
    compile cache at the fixed ``<checkout>/.jax_cache``, handed to the
    program through ``$JAX_COMPILATION_CACHE_DIR``, with every program
    cached however quick its compile, so a second run of a cell
    compiles nothing; and the compile log."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return CompileLog()


class CompileLog:
    """Counts programs JAX lowers (a compile or a load from the
    persistent cache) and their compile seconds, from JAX's own
    monitoring events. Listeners cannot be removed, so one per
    process."""

    def __init__(self):
        self.lowered = 0
        self.compile_s = 0.0
        self.cache = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_kw):
        if event.startswith("/jax/compilation_cache/"):
            self.cache[event.rsplit("/", 1)[-1]] += 1


def _round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


@dataclasses.dataclass
class Geometry:
    """Engine shapes for one cell: from the mix's lengths and the
    server block of its file."""
    min_len_bucket: int
    max_len: int
    batch_buckets: tuple
    max_batch: int
    pool_pages: int
    submit_rows: int

    @classmethod
    def of(cls, mix: Dict[str, Any]) -> "Geometry":
        s = mix["server"]
        lo = _round_up(mix["prompt_len"]["min"], PAGE)
        top = lo
        while top < mix["prompt_len"]["max"]:
            top *= 2
        return cls(min_len_bucket=lo,
                   max_len=_round_up(top + mix["max_new"]["max"], PAGE),
                   batch_buckets=tuple(s["batch_buckets"]),
                   max_batch=int(s["max_batch"]),
                   pool_pages=_round_up(s["pool_tokens_per_expert"],
                                        PAGE) // PAGE,
                   submit_rows=int(s["submit_rows"]))


@dataclasses.dataclass
class System:
    """What set-up built, and what the check needs of it."""
    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    seed: int
    family: Any                       # the config's family module
    arch: Any                         # family.Arch of the config
    geometry: Geometry
    names: List[str]
    clients: List[np.ndarray]         # per dataset: client A rows
    spare: List[np.ndarray]           # per dataset: client B rows
    ae_params: Any
    ae_state: Any
    server: Optional[RoutedServer]
    setup_parts: Dict[str, float]


def expert_names(cfg: Dict[str, Any]) -> List[str]:
    return list(cfg["deployment"]["datasets"])


def build(cell: Cell, seed: int, t_start: float) -> System:
    """Set-up, one part per line of the log."""
    cfg, mix, fam = cell.config, cell.traffic, cell.family
    parts: Dict[str, float] = {}
    names = expert_names(cfg)
    arch = fam.Arch.from_config(cfg)
    geo = Geometry.of(mix)

    t = time.perf_counter()
    data = fingerprints.load(names, N_PER_DATASET, seed)
    parts["datasets"] = time.perf_counter() - t
    log(f"setup: datasets {len(names)} x {N_PER_DATASET} fingerprints "
        f"({parts['datasets']:.3f} s)")

    t = time.perf_counter()
    ae_params, ae_state = matcher_ref.train_bank(
        [data[n]["server"][0] for n in names], seed, epochs=AE_EPOCHS)
    jax.block_until_ready(ae_params)
    matcher = build_matcher(matcher_ref.unstack(ae_params, ae_state), names,
                            [data[n]["server"] for n in names],
                            MatcherConfig(use_kernel=True))
    parts["ae_training"] = time.perf_counter() - t
    log(f"setup: AE bank trained, {len(names)} autoencoders x "
        f"{AE_EPOCHS} epochs ({parts['ae_training']:.3f} s)")

    t = time.perf_counter()
    model = build_model(fam.program_arch(cfg))
    registry = ExpertRegistry()
    for e, name in enumerate(names):
        dev = fam.make_expert(weights.expert_key(seed, e), arch)
        # the engine keeps the caller's unstacked tree beside its own
        # stacked copy: hand it a host copy, so the chip holds one
        host = jax.device_get(dev)
        del dev
        registry.add(name, ExpertEngine(
            model, host, max_len=geo.max_len,
            min_len_bucket=geo.min_len_bucket,
            batch_buckets=geo.batch_buckets, kv_layout="paged",
            page_size=PAGE, pool_pages=geo.pool_pages),
            arch=cfg["name"])
        del host
    parts["init"] = time.perf_counter() - t
    log(f"setup: init {len(names)} x {cfg['name']} experts, "
        f"{arch.layers} layers, d{arch.hidden}, {cfg['torch_dtype']}, "
        f"paged KV max_len {geo.max_len}, {geo.pool_pages} pages/expert "
        f"({parts['init']:.3f} s)")

    t = time.perf_counter()
    placement = None
    if cfg["deployment"]["placement"] == "bank":
        placement = plan_placement(registry, mesh=make_expert_mesh())
    server = RoutedServer(matcher, registry, max_batch=geo.max_batch,
                          placement=placement)
    gc.collect()
    parts["placement"] = time.perf_counter() - t
    log(f"setup: placement {cfg['deployment']['placement']}, "
        f"{len(server.scheduler.shards)} shard(s) "
        f"({parts['placement']:.3f} s); bytes in use "
        f"{_mem('bytes_in_use')}, peak so far {_mem('peak_bytes_in_use')}")

    return System(cfg=cfg, mix=mix, seed=seed, family=fam, arch=arch,
                  geometry=geo, names=names,
                  clients=[data[n]["client_a"][0] for n in names],
                  spare=[data[n]["client_b"][0] for n in names],
                  ae_params=ae_params, ae_state=ae_state, server=server,
                  setup_parts=parts)


def _mem(key: str):
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get(key)


def warm(sys_: System) -> None:
    """Run every shape the window can reach once: the router's row
    buckets up to the submit size, and for each engine every (batch
    bucket, length bucket) pair the mix's lengths map to, prefill and
    a decode step."""
    t = time.perf_counter()
    server, geo, mix = sys_.server, sys_.geometry, sys_.mix
    rng = np.random.default_rng([sys_.seed, 5])
    rows = 1
    while rows <= geo.submit_rows:
        for spare in sys_.spare:
            # one dataset per call: the fine-score group is then the
            # whole call, so every group bucket is met too
            x = spare[rng.integers(len(spare), size=rows)]
            x = x + rng.normal(0, 1e-3, size=x.shape).astype(np.float32)
            server.router.route(x.astype(np.float32))
        rows *= 2
    pool = traffic.pool_fingerprints(mix, sys_.seed, sys_.clients)
    if len(pool):
        for lo in range(0, len(pool), geo.submit_rows):
            server.router.route(pool[lo:lo + geo.submit_rows])

    lo, hi = int(mix["prompt_len"]["min"]), int(mix["prompt_len"]["max"])
    first = server.registry[0].backend
    sbs = sorted({first.pad_shape(1, n)[1] for n in range(lo, hi + 1)})
    shards = server.scheduler.shards
    uid = -1
    for shard in shards:
        e = shard.experts[0]
        for bb in geo.batch_buckets:
            for sb in sbs:
                reqs = []
                for _ in range(bb):
                    reqs.append(Request(
                        uid=uid, features=sys_.spare[e][0],
                        prompt=rng.integers(0, sys_.arch.vocab, size=sb,
                                            dtype=np.int32),
                        max_new_tokens=2, expert=e))
                    uid -= 1
                server.submit(reqs)
                while server.scheduler.has_work:
                    server.step()
    sys_.setup_parts["warmup"] = time.perf_counter() - t
    log(f"setup: warm-up {len(shards)} shard(s) x batch buckets "
        f"{list(geo.batch_buckets)} x length buckets {sbs}, router rows "
        f"1..{geo.submit_rows} ({sys_.setup_parts['warmup']:.3f} s)")


def snapshot(server: RoutedServer) -> Dict[str, Any]:
    """The counters the per-layer metrics read, summed over engines."""
    snap = server.snapshot()
    eng = collections.Counter()
    for v in snap.get("engines", {}).values():
        for k, x in v.items():
            if isinstance(x, (int, float)) and not isinstance(x, bool):
                eng[k] += x
    q = snap["scheduler/latency/queue_ms"] if \
        "scheduler/latency/queue_ms" in snap else \
        snap["scheduler"]["latency"]["queue_ms"]
    return {"engine": dict(eng), "router": dict(server.router.stats),
            "queue_ms_sum": q["sum"], "queue_ms_count": q["count"],
            "scheduler": dict(server.scheduler.stats.as_dict())}


def diff(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    """b - a, recursively over numbers."""
    out = {}
    for k, v in b.items():
        if isinstance(v, dict):
            out[k] = diff(a.get(k, {}), v)
        elif isinstance(v, (int, float)):
            out[k] = v - a.get(k, 0)
    return out


@dataclasses.dataclass
class Window:
    """What one window produced."""
    seconds: float
    offers: List[traffic.Offer]
    done: List[Optional[float]]             # answer time, from the open
    responses: Dict[int, Any]               # uid -> Response
    rejected: int
    lateness: List[float]                   # submit time minus due
    lowered_in_window: int
    gave_up: float                          # loop end, from the open
    counters: Dict[str, Any]                # over the whole window
    trace_counters: Optional[Dict[str, Any]] = None
    spans: Optional[List[Dict[str, Any]]] = None


def run_window(sys_: System, offers: List[traffic.Offer], seconds: float,
               compiles: CompileLog, *, trace_dir: Optional[str] = None,
               trace_s: float = 0.0) -> Window:
    """The open loop. Requests are submitted once due (in chunks of the
    submit size), the server steps while it has work, and the loop
    sleeps only when it has none. Offering ends with the last request
    due before the close; in-flight requests then drain, for at most
    ``DRAIN_LIMIT_S``. With ``trace_dir`` the last ``trace_s`` seconds
    before the close are profiled and the program's tracer records
    spans over the whole window."""
    server, geo = sys_.server, sys_.geometry
    tracer = None
    if trace_dir:
        tracer = Tracer(enabled=True)
        server.bind_tracer(tracer)
    n = len(offers)
    done: List[Optional[float]] = [None] * n
    responses: Dict[int, Any] = {}
    lateness: List[float] = []
    rejected = 0
    lowered0 = compiles.lowered
    c0 = snapshot(server)
    tc0 = tc1 = None
    # the profile covers [seconds - trace_s, seconds): "pending" until
    # it starts, "on" while it runs, "done" after
    trace = "pending" if trace_dir else "done"
    i = 0
    # what set-up left is never garbage the window has to scan
    gc.collect()
    gc.freeze()
    t_open = time.perf_counter()
    while True:
        now = time.perf_counter() - t_open
        if trace == "pending" and now >= seconds - trace_s:
            tc0 = snapshot(server)
            jax.profiler.start_trace(trace_dir)
            trace = "on"
        if trace == "on" and now >= seconds:
            jax.profiler.stop_trace()
            tc1 = snapshot(server)
            trace = "done"
        j = i
        while j < n and offers[j].due <= now:
            j += 1
        if j > i:
            with jax.profiler.TraceAnnotation("bench.submit"):
                for lo in range(i, j, geo.submit_rows):
                    batch = [Request(uid=o.uid, features=o.features,
                                     prompt=o.prompt,
                                     max_new_tokens=o.max_new)
                             for o in offers[lo:min(j, lo + geo.submit_rows)]]
                    took = server.submit(batch)
                    rejected += len(batch) - took
            lateness.extend(now - o.due for o in offers[i:j])
            i = j
        if server.scheduler.has_work:
            with jax.profiler.TraceAnnotation("bench.step"):
                out = server.step()
            t = time.perf_counter() - t_open
            for r in out:
                done[r.uid] = t
                responses[r.uid] = r
        elif i < n or trace != "done":
            nxt = offers[i].due if i < n else seconds
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt - now, 0.05)))
        else:
            break
        if now > seconds + DRAIN_LIMIT_S:
            break
    gave_up = time.perf_counter() - t_open
    gc.unfreeze()
    c1 = snapshot(server)
    if tracer is not None:
        server.bind_tracer(None)
    if trace == "on":
        jax.profiler.stop_trace()
    return Window(seconds=seconds, offers=offers, done=done,
                  responses=responses, rejected=rejected,
                  lateness=lateness,
                  lowered_in_window=compiles.lowered - lowered0,
                  gave_up=gave_up,
                  counters=diff(c0, c1),
                  trace_counters=diff(tc0, tc1) if tc1 else None,
                  spans=tracer.records() if tracer else None)
