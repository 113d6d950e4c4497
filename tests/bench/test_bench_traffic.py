"""Seeded traffic: the same seed gives the same requests; another seed
gives the same work in another order; every size stays in its range."""
import json
import os

import numpy as np
import pytest

from bench import spec, traffic

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(spec.BENCH_DIR,
                                                        "traffic")))
SECONDS = 30.0


def _mix(name):
    with open(spec.traffic_path(name)) as f:
        return json.load(f)


def _clients(mix):
    rng = np.random.default_rng(0)
    k = len(mix["datasets"]["weights"])
    return [rng.random((150, 784)).astype(np.float32) for _ in range(k)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = traffic.offered(mix, 2**33 + 5, SECONDS, 1000, _clients(mix))
    b = traffic.offered(mix, 2**33 + 5, SECONDS, 1000, _clients(mix))
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)
        assert np.array_equal(x.features, y.features)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_work(name):
    mix = _mix(name)
    a = traffic.offered(mix, 1, SECONDS, 1000, _clients(mix))
    b = traffic.offered(mix, 2, SECONDS, 1000, _clients(mix))
    assert len(a) == len(b) == round(mix["arrivals"]["rate_per_s"] * SECONDS)
    for key in (lambda o: len(o.prompt), lambda o: o.max_new,
                lambda o: o.dataset, lambda o: o.due):
        assert list(map(key, a)) == list(map(key, b))
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not any(np.array_equal(x.features, y.features)
                   for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_ranges(name):
    mix = _mix(name)
    reqs = traffic.offered(mix, 9, SECONDS, 1000, _clients(mix))
    p, m = mix["prompt_len"], mix["max_new"]
    assert all(p["min"] <= len(o.prompt) <= p["max"] for o in reqs)
    assert all(m["min"] <= o.max_new <= m["max"] for o in reqs)
    dues = [o.due for o in reqs]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < SECONDS
    assert all(0 <= o.prompt.min() and o.prompt.max() < 1000 for o in reqs)


def test_fresh_fingerprints_are_all_new():
    mix = _mix("smollm6_fresh")
    reqs = traffic.offered(mix, 3, SECONDS, 1000, _clients(mix))
    keys = {o.features.tobytes() for o in reqs}
    assert len(keys) == len(reqs)


def test_returning_clients_come_from_their_pool():
    mix = _mix("smollm6_long_repeat")
    clients = _clients(mix)
    pool = traffic.pool_fingerprints(mix, 3, clients)
    assert len(pool) == len(clients) * mix["fingerprints"]["per_dataset"]
    known = {x.tobytes() for x in pool}
    reqs = traffic.offered(mix, 3, SECONDS, 1000, clients)
    assert all(o.features.tobytes() in known for o in reqs)
