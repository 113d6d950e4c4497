"""repro.obs — zero-dependency tracing + metrics for the serving mesh.

Two halves, both pure stdlib (no numpy, no jax — importable from any
layer without dragging a backend in):

``trace``
    Request-lifecycle spans. A :class:`Tracer` mints per-request trace
    ids at ``Scheduler.submit`` and carries them through routing, hub
    admission (park → stage → commit), chunked prefill, speculative
    verify/fallback and harvest. Host work uses ``span(...)`` contexts;
    device work uses ``begin_device``/``end_device`` pairs that close
    only at the engine's existing harvest sync points, so tracing adds
    **zero** new host blocks by construction (``EngineStats.host_blocks``
    is asserted identical with tracing on and off). Each record carries
    an ``id`` and the ``parent`` span open on its thread, and an enabled
    tracer enters a ``jax.profiler.TraceAnnotation`` per span (JAX is
    imported on the first span, never at import), so program spans share
    the profiler's clock with the device. Export is Chrome
    ``trace_event`` JSON (load in ``chrome://tracing`` / Perfetto) or a
    greppable JSONL stream.

``metrics``
    ``Counter`` / ``Gauge`` / ``Histogram`` (fixed log buckets, pure
    Python in the hot path) plus a :class:`MetricsRegistry` that folds
    ``EngineStats``, ``HubStats``, scheduler counters and
    ``PagePool.telemetry()`` into one ``snapshot()`` tree — the single
    source of truth ``serving_bench`` and the placement rebalancer read.

The static side of the contract lives in ``repro.analysis.obs_lint``
(rules O001–O003): no tracing call inside jit-traced code, device-
dispatch spans must end at a blessed sync site, histogram buckets
declared as literals.
"""
from .metrics import (DEFAULT_MS_BUCKETS, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .trace import NULL_TRACER, Tracer

__all__ = [
    "Counter",
    "DEFAULT_MS_BUCKETS",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "Tracer",
]
