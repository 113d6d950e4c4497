"""Trace reduction: interval arithmetic; a short profile recorded here
with ``jax.profiler`` (on the CPU backend in a test run), whose
operation events and window share one clock; and a profile recorded on
one TPU v5e, kept in ``bench/testdata``."""
import os

import jax
import jax.numpy as jnp
import pytest

from bench import spec, xtrace


def test_union_and_gaps():
    busy = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert xtrace.union_seconds(busy) == pytest.approx(3.0)
    gaps = xtrace.idle_gaps(busy, -1.0, 5.0)
    assert gaps == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    host = [(1.9, 2.8, "bench.step"), (2.8, 3.0, "bench.wait")]
    assert xtrace.label_gaps(gaps, host)[1] == ("bench.step", 1.0)
    assert xtrace.label_gaps([(9.0, 9.5)], host) == [("host:other", 0.5)]


def test_reduce_recorded_profile(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(5):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = xtrace.reduce(str(tmp_path))
    assert 0.0 < red.busy_s <= red.window_s
    assert red.seconds and sum(red.seconds.values()) > 0.0
    assert red.top_ops and red.gaps
    assert sum(s for _, s in red.gaps) <= red.window_s - red.busy_s + 1e-9


def test_reduce_chip_trace():
    """A profile recorded on one TPU v5e around a two-layer paged engine
    (prefill and decode) and one call of each routing kernel, twice."""
    red = xtrace.reduce(os.path.join(spec.BENCH_DIR, "testdata"))
    assert 0.0 < red.busy_s <= red.window_s < 1.0
    assert red.seconds["step"] > red.seconds["other"] > 0.0
    assert [len(red.kernel_calls[k]) for k in ("expert_score",
                                               "cosine_scores")] == [2, 2]
    assert all(0.0 < d < 1e-4 for calls in red.kernel_calls.values()
               for d in calls)
    names = [n for n, _ in red.top_ops]
    assert "step:copy bf16[1,49152,576]" in names
    assert red.breakdown()["idle_gaps"][0][0] == "bench.gen"
