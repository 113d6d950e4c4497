"""One generator for every traffic mix.

A mix is a data file under ``bench/traffic/`` (JSON): arrivals, prompt
and output lengths, how requests spread over the datasets, whether
clients send fresh or returning fingerprints, and the server geometry
the cell runs with. Nothing here knows a mix by name.

The work of a run is fixed by the mix alone: the count of requests,
their arrival times, prompt and output lengths and datasets, in order,
are drawn from the mix's own ``work_seed``. ``--seed`` draws what the
requests hold (token ids, which client sample each fingerprint comes
from, its noise), so two seeds offer the same work on the same
schedule and their runs differ by the system, not by the load. (A
window near the knee holds a few tens of requests; reordering them per
seed moved the latency tails by a quarter between seeds.)
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Mapping, Sequence

import numpy as np


@dataclasses.dataclass
class Offer:
    """One request the load generator offers: due ``due`` seconds after
    the window opens."""
    uid: int
    due: float
    dataset: int
    features: np.ndarray
    prompt: np.ndarray
    max_new: int


def _lengths(spec: Mapping, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["kind"] == "uniform":
        v = rng.integers(lo, hi + 1, size=n)
    elif spec["kind"] == "lognormal":
        v = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"],
                              size=n))
        v = np.clip(np.round(v), lo, hi)
    else:
        raise ValueError(f"unknown length kind {spec['kind']!r}")
    return v.astype(np.int64)


def _gaps(spec: Mapping, n: int, seconds: float,
          rng: np.random.Generator) -> np.ndarray:
    """``n`` gaps whose sum puts the last arrival inside the window."""
    if spec["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    g = rng.exponential(1.0, size=n)
    return g * (seconds * n / (n + 1)) / g.sum()


def _datasets(spec: Mapping, n: int, k: int,
              rng: np.random.Generator) -> np.ndarray:
    w = np.asarray(spec.get("weights", [1.0] * k), np.float64)
    if len(w) != k:
        raise ValueError(f"{len(w)} dataset weights for {k} datasets")
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[np.argsort(-(w / w.sum() * n - counts))[:n - counts.sum()]] += 1
    return rng.permutation(np.repeat(np.arange(k), counts))


def offered(mix: Mapping, seed: int, seconds: float, vocab: int,
            clients: Sequence[np.ndarray]) -> List[Offer]:
    """The requests of one run. ``clients[d]`` holds dataset d's client
    fingerprints (rows of 784 features)."""
    k = len(clients)
    n = max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))
    work = np.random.default_rng(int(mix["work_seed"]))
    gaps = _gaps(mix["arrivals"], n, seconds, work)
    plen = _lengths(mix["prompt_len"], n, work)
    mnew = _lengths(mix["max_new"], n, work)
    dsets = _datasets(mix.get("datasets", {}), n, k, work)

    due = np.cumsum(gaps)
    rng = np.random.default_rng([int(seed), 11])
    fp = mix["fingerprints"]
    pool = _pool(mix, seed, clients)
    out = []
    for i in range(n):
        d = int(dsets[i])
        if fp["kind"] == "pool":
            x = pool[d][int(rng.integers(len(pool[d])))]
        else:
            # a client sample and a little noise of its own: no earlier
            # request had these bytes, so the route cache misses
            x = clients[d][int(rng.integers(len(clients[d])))]
            x = (x + rng.normal(0, fp["noise"], size=x.shape)
                 ).astype(np.float32)
        out.append(Offer(
            uid=i, due=float(due[i]), dataset=d, features=x,
            prompt=rng.integers(0, vocab, size=int(plen[i]),
                                dtype=np.int32),
            max_new=int(mnew[i])))
    return out


def _pool(mix: Mapping, seed: int, clients: Sequence[np.ndarray]):
    """Per dataset, the fingerprints of its returning clients (None for
    a fresh mix)."""
    fp = mix["fingerprints"]
    if fp["kind"] == "fresh":
        return None
    if fp["kind"] != "pool":
        raise ValueError(f"unknown fingerprint kind {fp['kind']!r}")
    rng = np.random.default_rng([int(seed), 13])
    per = int(fp["per_dataset"])
    return [c[rng.choice(len(c), size=per, replace=False)]
            for c in clients]


def pool_fingerprints(mix: Mapping, seed: int,
                      clients: Sequence[np.ndarray]) -> np.ndarray:
    """The returning clients' fingerprints (none for a fresh mix): what
    they sent before this run, so that routing them is a cache hit."""
    pool = _pool(mix, seed, clients)
    if pool is None:
        return np.zeros((0, clients[0].shape[1]), np.float32)
    return np.concatenate(pool)

