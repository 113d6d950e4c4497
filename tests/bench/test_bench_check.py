"""The check that decides ``correct``, driven through a whole run at a
test size on the CPU (the harness's look for a chip is skipped by
calling the driver directly).

- A sound run is correct.
- The control, the reference in the step below the stated precision
  (fp8 matmuls for the bf16 model, three-pass bf16 for the f32 matcher)
  put in the program's place, is not.
- Each fault a serving cell can have, planted in the timed path,
  makes ``correct`` false: a served token altered where the engine
  produces it; a decode step that returns its KV state unchanged; a
  routing answer altered where the router produces it.
"""
import json
import os
import time

import jax.numpy as jnp
import numpy as np

from bench import correct, driver, harness, spec, traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"peak_flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
DENSE = spec.family("dense")


def _cell():
    with open(os.path.join(DATA, "tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, "tiny_mix.json")) as f:
        mix = json.load(f)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    return spec.Cell(name="smollm6.fresh", chips=1, config=cfg,
                     family=DENSE, traffic=mix, end_to_end=doc["end_to_end"],
                     per_layer=[])


def _run(seed=3):
    return driver.run(_cell(), seed, 2.0, None, PEAKS, time.perf_counter())


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {"tok_s", "latency_p50_s",
                                 "latency_p95_s", "setup_s"}


def test_control_is_not_correct():
    cell = _cell()
    compiles = harness.CompileLog()
    sys_ = harness.build(cell, 4, time.perf_counter())
    harness.warm(sys_)
    offers = traffic.offered(cell.traffic, 4, 2.0, sys_.arch.vocab,
                             sys_.clients)
    win = harness.run_window(sys_, offers, 2.0, compiles)
    got = correct.collect(sys_, win)
    correct.free(sys_)
    ok, _ = correct.verdict(cell.config, correct.readings(sys_, got))
    assert ok
    ok, table = correct.verdict(
        cell.config, correct.readings(sys_, got, control=True))
    assert not ok, table


def test_altered_token_is_caught(monkeypatch):
    from repro.serve import core
    poll = core.EngineCore.poll

    def altered(self):
        out = poll(self)
        return [(local, uid, np.concatenate(
            [toks[:-1], (toks[-1:] + 1) % 512]).astype(np.int32))
            for local, uid, toks in out]

    monkeypatch.setattr(core.EngineCore, "poll", altered)
    r = _run()
    assert not r["correct"]
    assert r["compared"]["logit_gap"]["value"] > \
        r["compared"]["logit_gap"]["limit"]


def test_decode_state_left_unchanged_is_caught(monkeypatch):
    from repro.models import dense
    decode = dense.DecoderLM.paged_decode

    def stale(self, params, pool, *args, **kw):
        logits, _, pos, t = decode(self, params, pool, *args, **kw)
        return logits, pool, pos, t

    monkeypatch.setattr(dense.DecoderLM, "paged_decode", stale)
    r = _run()
    assert not r["correct"]
    assert r["compared"]["logit_gap"]["value"] > \
        r["compared"]["logit_gap"]["limit"]


def test_altered_route_is_caught(monkeypatch):
    from repro.serve import router
    route = router.Router.route

    def altered(self, feats):
        res = route(self, feats)
        res.coarse = (res.coarse + 1) % self.matcher.n_experts
        return res

    monkeypatch.setattr(router.Router, "route", altered)
    r = _run()
    assert not r["correct"]
    assert r["compared"]["route_gap"]["value"] > \
        r["compared"]["route_gap"]["limit"]


def test_half_the_batch_left_out_is_caught(monkeypatch):
    from repro.models import dense
    decode = dense.DecoderLM.paged_decode

    def half(self, params, pool, table, pos, t, batch, **kw):
        logits, pool, pos, t = decode(self, params, pool, table, pos, t,
                                      batch, **kw)
        # the first half of the rows (where a wave puts its requests)
        # is not computed: its logits are 0
        b = batch["token"].shape[0]
        keep = (jnp.arange(b) >= -(-b // 2)).reshape(
            (b,) + (1,) * (logits.ndim - 1))
        return jnp.where(keep, logits, 0), pool, pos, t

    monkeypatch.setattr(dense.DecoderLM, "paged_decode", half)
    r = _run()
    assert not r["correct"]
    assert r["compared"]["logit_gap"]["value"] > \
        r["compared"]["logit_gap"]["limit"]
