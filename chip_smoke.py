"""Routed serving on one TPU chip at published widths, checked end to end.

Drives the system's main path through the entry points a user calls:

  1. generate the six synthetic datasets from a seed and train the AE
     bank (``train_bank``); build the matcher with
     ``MatcherConfig(use_kernel=True)``, so coarse routing runs the
     fused expert-score kernel and fine routing the cosine kernel;
  2. register one ``smollm-135m`` expert per dataset at the published
     config (30 layers, d576, 9H/3KV, vocab 49152, bf16; random weights
     from the seed) with the paged KV cache, bank them with
     ``plan_placement(mesh=make_expert_mesh())``, and serve a few dozen
     requests (32-128 prompt tokens, 16 new tokens) through
     ``RoutedServer.serve``, in two waves: the first pays the compiles,
     the second is served warm;
  3. check, failing on any miss:
     (a) routing accuracy against each request's true dataset;
     (b) the compiled kernel's coarse scores against ``ae.bank_scores``
         at f32 ``highest`` matmul precision, within ``SCORE_RTOL``;
     (c) every served token of a few responses is an argmax of a
         float32 teacher-forced pass over prompt plus served tokens
         (params cast to f32, ``highest`` precision), up to
         ``LOGIT_MARGIN``. Logits are compared, not token ids, because
         bf16 rounding flips near-ties. The engine serves a prompt
         zero-padded to its length bucket (``EngineCore.admit_wave``),
         so the reference pass reads the same padded prompt.

``--chips 4`` runs only the sharded path and what it is compared with:
a bank of four experts (the matcher over four of the datasets) sharded
over the four chips' ``expert`` mesh, against an unsharded copy of the
same bank on chip 0, both serving the same requests.

The script fails, and prints no result, unless JAX's first device is a
TPU. Everything runs in this one process. The last line of standard
output is the JSON result. JAX's persistent compilation cache is on
(``repro.launch.compile_cache``), so a second run skips most compiles.

  python chip_smoke.py
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (ExpertRegistry, MatcherConfig,  # noqa: E402
                        bank_scores, build_matcher, train_bank)
from repro.data import SPECS, load_benchmark  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_expert_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.common import ArchConfig  # noqa: E402
from repro.serve import (ExpertEngine, Request, RoutedServer,  # noqa: E402
                         plan_placement)

PAGE = 8
SEED = 0
N_PER_DATASET = 600             # samples per dataset; half train the AEs
EPOCHS = 40
MIN_ACCURACY = 0.9
# Largest relative gap between kernel and reference coarse scores. Both
# sides are f32 at HIGHEST precision, so only summation order separates
# them; Mosaic's default (bf16) matmul passes moved the scores by 1.6%
# on a v5e, and a kernel reading the wrong expert's weights moves them
# by far more.
SCORE_RTOL = 1e-3
# Largest f32 logit shortfall of a served token below the f32 argmax.
# At smollm-135m's width the bf16 pass's logits sit at most 0.05 from
# the f32 pass's (measured on 3 seeds x 288 positions; logit std 0.48),
# so a bf16 argmax can trail the f32 argmax by at most 2 x 0.05. A token
# from the wrong expert or a broken cache trails by about the logit
# range (~2.5).
LOGIT_MARGIN = 0.1


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    model: ArchConfig = get_config("smollm-135m")  # published widths
    datasets: Optional[Tuple[str, ...]] = None     # None: all six
    requests: int = 36          # per wave
    prompt_len: Tuple[int, int] = (32, 128)
    max_new: int = 16
    max_batch: int = 8          # rows per expert per wave, one bucket
    n_check: int = 4            # responses checked against f32 logits

    @property
    def min_len_bucket(self) -> int:
        return -(-self.prompt_len[0] // PAGE) * PAGE

    @property
    def top_bucket(self) -> int:
        """Length bucket of the longest prompt: the engine's ladder
        doubles from ``min_len_bucket``."""
        b = self.min_len_bucket
        while b < self.prompt_len[1]:
            b *= 2
        return b

    @property
    def max_len(self) -> int:
        """KV capacity: a padded prompt plus its new tokens never wrap."""
        return -(-(self.top_bucket + self.max_new) // PAGE) * PAGE


# -- phases -----------------------------------------------------------------


def build_matcher_phase(cfg: SmokeConfig):
    """Datasets from the seed, the trained AE bank, the kernel matcher.
    Returns (bench, names, matcher)."""
    names = list(cfg.datasets or SPECS)
    bench = load_benchmark(names=names, n_per_dataset=N_PER_DATASET,
                           seed=SEED)
    aes, _ = train_bank([(n, bench[n]["server"][0]) for n in names],
                        epochs=EPOCHS, batch_size=64)
    matcher = build_matcher(aes, names, [bench[n]["server"] for n in names],
                            MatcherConfig(use_kernel=True))
    return bench, names, matcher


def make_requests(cfg: SmokeConfig, bench, names: Sequence[str], *,
                  wave: int) -> Tuple[List[Request], List[str]]:
    """One wave of requests from the client split; returns (requests,
    true dataset per request)."""
    rng = np.random.default_rng([SEED, wave])
    lo, hi = cfg.prompt_len
    reqs, truth = [], []
    for i in range(cfg.requests):
        name = names[int(rng.integers(len(names)))]
        x, _ = bench[name]["client_a"]
        n = int(rng.integers(lo, hi + 1))
        reqs.append(Request(
            uid=wave * cfg.requests + i,
            features=x[int(rng.integers(len(x)))],
            prompt=rng.integers(0, cfg.model.vocab_size, size=n,
                                dtype=np.int32),
            max_new_tokens=cfg.max_new))
        truth.append(name)
    return reqs, truth


def build_server(cfg: SmokeConfig, matcher, names: Sequence[str], mesh):
    """One expert per dataset, banked over ``mesh`` (None: one device).
    Returns (server, plan)."""
    model = build_model(cfg.model)
    init = jax.jit(model.init)
    registry = ExpertRegistry()
    for i, name in enumerate(names):
        # no local keeps an engine: plan_placement frees the ones the
        # bank replaces only if nothing else holds them
        registry.add(name, ExpertEngine(
            model, init(jax.random.PRNGKey(SEED + i)),
            max_len=cfg.max_len, min_len_bucket=cfg.min_len_bucket,
            batch_buckets=(cfg.max_batch,), kv_layout="paged"),
            arch=cfg.model.name)
    plan = plan_placement(registry, mesh=mesh)
    server = RoutedServer(matcher, registry, max_batch=cfg.max_batch,
                          placement=plan)
    return server, plan


def serve_phase(server: RoutedServer, reqs: Sequence[Request]):
    """Returns (responses, wall seconds). Responses carry host tokens,
    so the clock stops after the device has finished."""
    t0 = time.perf_counter()
    resps = server.serve(list(reqs))
    return resps, time.perf_counter() - t0


def check_routing(resps, truth) -> float:
    acc = float(np.mean([r.expert == t for r, t in zip(resps, truth)]))
    if acc < MIN_ACCURACY:
        raise SmokeFailure(f"routing accuracy {acc:.3f} < {MIN_ACCURACY}")
    return acc


def check_coarse_kernel(matcher, x) -> float:
    """Kernel coarse scores vs the f32 reference; returns the largest
    relative gap."""
    got = np.asarray(kops.expert_score(matcher.bank_params, jnp.asarray(x),
                                       matcher.bank_states))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(bank_scores)(
            matcher.bank_params, matcher.bank_states, jnp.asarray(x)))
    rel = float(np.max(np.abs(got - want) / np.abs(want)))
    if not rel <= SCORE_RTOL:
        raise SmokeFailure(f"kernel coarse scores differ from the "
                           f"reference by {rel:.3g} > {SCORE_RTOL}")
    return rel


def kernel_custom_calls(matcher, x) -> Dict[str, bool]:
    """Whether each routing kernel, compiled as routing calls it for
    the default device, is a Mosaic ``tpu_custom_call`` in the HLO."""
    x = jnp.asarray(x)
    folded = kops.fold_bank(matcher.bank_params, matcher.bank_states)
    coarse = kops.expert_score_folded.lower(folded, x).compile()
    z = jnp.zeros((x.shape[0], matcher.centroids.shape[-1]), jnp.float32)
    fine = kops.cosine_scores.lower(z, matcher.centroids[0],
                                    matcher.centroid_mask[0]).compile()
    return {name: "tpu_custom_call" in c.as_text()
            for name, c in (("expert_score", coarse),
                            ("cosine_scores", fine))}


def logit_gaps(cfg: SmokeConfig, server: RoutedServer,
               reqs: Sequence[Request], resps) -> Tuple[np.ndarray, int]:
    """f32 teacher-forced check of up to ``n_check`` responses, one per
    expert. Each response's params are read back from the bank slice
    that served it. Returns (per-token gap of the served token below
    the f32 argmax, tokens that are the exact f32 argmax)."""
    ref_model = build_model(cfg.model.replace(param_dtype="float32",
                                              compute_dtype="float32"))
    width = cfg.top_bucket + cfg.max_new

    @jax.jit
    def ref_logits(params, tokens):
        p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                     params)
        return ref_model.logits(p32, {"tokens": tokens})[0]

    by_uid = {q.uid: q for q in reqs}
    names = server.registry.names
    seen, gaps, exact = set(), [], 0
    for r in resps:
        if r.expert in seen or len(seen) >= cfg.n_check:
            continue
        seen.add(r.expert)
        member = server.registry[names.index(r.expert)].backend
        params = jax.tree_util.tree_map(lambda a: a[member.local],
                                        member.bank.params)
        prompt, served = by_uid[r.uid].prompt, np.asarray(r.tokens)
        sb = member.pad_shape(1, len(prompt))[1]
        seq = np.zeros((1, width), np.int32)   # causal: the tail is inert
        seq[0, :len(prompt)] = prompt
        seq[0, sb:sb + len(served) - 1] = served[:-1]
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(ref_logits(params, jnp.asarray(seq)))
        rows = lg[sb - 1:sb - 1 + len(served)]
        picked = rows[np.arange(len(served)), served]
        gaps.append(rows.max(-1) - picked)
        exact += int(np.sum(rows.argmax(-1) == served))
    gaps = np.concatenate(gaps)
    return gaps, exact


def check_logits(gaps: np.ndarray) -> float:
    worst = float(gaps.max())
    if not worst <= LOGIT_MARGIN:
        raise SmokeFailure(f"a served token trails the f32 argmax by "
                           f"{worst:.4f} > margin {LOGIT_MARGIN}")
    return worst


# -- drivers ----------------------------------------------------------------


class _CompileLog:
    """Compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.events = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_kw):
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[-1]] += 1


def _device_bytes(key: str) -> str:
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append(f"{d.id}:{stats.get(key, 'not reported')}")
    return " ".join(out)


def run_one_chip(cfg: SmokeConfig, log: _CompileLog) -> None:
    t0 = time.perf_counter()
    bench, names, matcher = build_matcher_phase(cfg)
    print(f"matcher: {len(names)} AEs trained "
          f"({time.perf_counter() - t0:.1f} s)", flush=True)
    server, plan = build_server(cfg, matcher, names, make_expert_mesh())
    for line in plan.describe(names).splitlines():
        print(f"placement: {line}", flush=True)
    print(f"experts: {cfg.model.name} x{len(names)}, "
          f"{cfg.model.n_layers} layers, d{cfg.model.d_model}, "
          f"{cfg.model.param_dtype}, paged KV, max_len {cfg.max_len}",
          flush=True)
    print(f"bytes in use after placement: {_device_bytes('bytes_in_use')}"
          f", peak so far {_device_bytes('peak_bytes_in_use')}", flush=True)
    compile_before = log.seconds
    waves = []
    for wave in range(2):
        reqs, truth = make_requests(cfg, bench, names, wave=wave)
        resps, secs = serve_phase(server, reqs)
        waves.append((reqs, truth, resps, secs))
        toks = sum(len(r.tokens) for r in resps)
        print(f"wave {wave}: {len(resps)} requests, {toks} tokens served "
              f"in {secs:.3f} s", flush=True)
    print(f"compile: {log.seconds:.1f} s in all, "
          f"{log.seconds - compile_before:.1f} s of it while serving; "
          f"persistent cache {dict(log.events)}", flush=True)

    reqs = [q for w in waves for q in w[0]]
    truth = [t for w in waves for t in w[1]]
    resps = [r for w in waves for r in w[2]]
    acc = check_routing(resps, truth)
    print(f"routing accuracy: {acc:.4f} over {len(resps)} requests "
          f"(min {MIN_ACCURACY}); router {server.router.stats}",
          flush=True)
    x = np.stack([q.features for q in reqs])
    rel = check_coarse_kernel(matcher, x)
    print(f"coarse kernel vs jnp bank_scores (f32 highest): max rel "
          f"gap {rel:.3g} (limit {SCORE_RTOL})", flush=True)
    hlo = kernel_custom_calls(matcher, x)
    print(f"tpu_custom_call in HLO: {hlo}", flush=True)
    if not all(hlo.values()):
        raise SmokeFailure(f"a routing kernel is not a Mosaic call: {hlo}")
    gaps, exact = logit_gaps(cfg, server, reqs, waves[0][2])
    worst = check_logits(gaps)
    print(f"f32 teacher-forced check: {len(gaps)} served tokens, "
          f"{exact} exact argmax, largest logit gap {worst:.5f} "
          f"(margin {LOGIT_MARGIN})", flush=True)
    print(f"peak bytes in use: {_device_bytes('peak_bytes_in_use')}",
          flush=True)


def run_four_chips(cfg: SmokeConfig, log: _CompileLog) -> None:
    cfg = dataclasses.replace(cfg, datasets=tuple(list(SPECS)[:4]))
    bench, names, matcher = build_matcher_phase(cfg)
    sharded, plan = build_server(cfg, matcher, names, make_expert_mesh())
    single, single_plan = build_server(cfg, matcher, names, None)
    for line in plan.describe(names).splitlines():
        print(f"placement: {line}", flush=True)
    copy = single_plan.shards[0].bank.params["embed"].sharding.device_set
    print(f"unsharded copy on: {sorted(str(d) for d in copy)}", flush=True)
    embed = plan.shards[0].bank.params["embed"]
    for shard in sorted(embed.addressable_shards,
                        key=lambda s: s.index[0].start or 0):
        print(f"expert slice {shard.index[0].start}:{shard.index[0].stop}"
              f" -> {shard.device}", flush=True)
    if len(embed.sharding.device_set) != 4:
        raise SmokeFailure(f"bank spans {embed.sharding.device_set}, "
                           "not four chips")
    print(f"bytes in use per chip: {_device_bytes('bytes_in_use')}",
          flush=True)
    reqs, truth = make_requests(cfg, bench, names, wave=0)
    got = {}
    for label, server in (("sharded", sharded), ("chip 0", single)):
        resps, secs = serve_phase(server, reqs)
        acc = check_routing(resps, truth)
        gaps, exact = logit_gaps(cfg, server, reqs, resps)
        worst = check_logits(gaps)
        got[label] = resps
        print(f"{label}: {len(resps)} requests in {secs:.3f} s, routing "
              f"accuracy {acc:.4f}, {len(gaps)} checked tokens, {exact} "
              f"exact f32 argmax, largest logit gap {worst:.5f} "
              f"(margin {LOGIT_MARGIN})", flush=True)
    same = sum(np.array_equal(a.tokens, b.tokens)
               for a, b in zip(got["sharded"], got["chip 0"]))
    print(f"sharded vs chip 0: {same}/{len(reqs)} responses token-"
          f"identical; compile {log.seconds:.1f} s", flush=True)
    print(f"peak bytes in use per chip: "
          f"{_device_bytes('peak_bytes_in_use')}", flush=True)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the expert bank sharded over four "
                         "chips against an unsharded copy on chip 0")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    print(f"device_kind: {devices[0].device_kind} x{len(devices)}",
          flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    log = _CompileLog()
    try:
        if args.chips == 4:
            run_four_chips(SmokeConfig(), log)
        else:
            run_one_chip(SmokeConfig(), log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
