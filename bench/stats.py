"""End-to-end arithmetic over one window's request records.

A request is offered at its due time and answered when the ``step``
that returns its response ends. Latency is answer minus due, so a stall
of the server or of the load generator lengthens every request that was
due during it. A request that failed (refused by the queue cap, or not
answered by the drain limit) has no answer: it counts as answered when
the run gave up on it, the least it was late by, which keeps every
percentile finite and puts failures at the slow end. Throughput counts
the tokens of responses answered inside the window over the whole
window, idle time included.
"""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100), linear between order statistics
    (numpy's default)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def latencies(due: Sequence[float], done: Sequence[Optional[float]],
              gave_up: float) -> list:
    """Seconds from due to answer; where there was no answer, to
    ``gave_up``, the time the run stopped waiting."""
    return [(gave_up if d is None else d) - t for t, d in zip(due, done)]


def tokens_per_s(tokens: Sequence[int], done: Sequence[Optional[float]],
                 seconds: float) -> float:
    """Tokens of responses answered by the close of a ``seconds`` window,
    per second of the window."""
    got = sum(n for n, d in zip(tokens, done)
              if d is not None and d <= seconds)
    return got / seconds


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median (``statistics.quantiles``
    quartiles)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
