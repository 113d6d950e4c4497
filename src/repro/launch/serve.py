"""Serving launcher: ExpertMatcher-routed fleet (Fig. 2 of the paper).

Trains the AE bank on the 6 synthetic benchmark datasets, registers one
expert engine per dataset (reduced zoo architectures on CPU), and serves
batches of mixed-modality requests.

With ``--hub-slots K`` (K > 0) the experts are served through an
``ExpertHub`` holding only K device slots: each expert is checkpointed
to ``--store`` (or a temp dir), staged on demand and evicted by
popularity-weighted LRU — the launcher prints the hub's lifecycle
ledger after serving.

  PYTHONPATH=src python -m repro.launch.serve --requests 32
  PYTHONPATH=src python -m repro.launch.serve --hub-slots 2
"""
from __future__ import annotations

import argparse
import tempfile
import time

import jax
import numpy as np

from ..configs import ALL_ARCHS, get_config
from ..core import ExpertRegistry, build_matcher, train_bank
from ..data import load_benchmark
from ..models import build_model
from ..obs import Tracer
from ..serve import ExpertEngine, ExpertHub, Request, RoutedServer
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--n-per-dataset", type=int, default=2000)
    ap.add_argument("--epochs", type=int, default=40)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--executor", choices=("serial", "overlapped"),
                    default="overlapped",
                    help="dispatch executor: 'overlapped' enqueues every "
                         "shard's prefill/decode before blocking (async "
                         "dispatch); 'serial' is the blocking reference")
    ap.add_argument("--kv", choices=("ring", "paged"), default="ring",
                    help="KV cache layout: 'paged' pools fixed-size "
                         "pages per shard and shares prompt-prefix "
                         "pages between requests (dense-family experts "
                         "only; others keep the ring layout)")
    ap.add_argument("--hub-slots", type=int, default=0,
                    help="serve through an ExpertHub with this many "
                         "device slots (0 = every expert resident, the "
                         "per-engine path); experts are checkpointed "
                         "cold and staged on demand")
    ap.add_argument("--store", default=None,
                    help="expert checkpoint store dir for --hub-slots "
                         "(default: a temp dir)")
    ap.add_argument("--trace", metavar="OUT", default=None,
                    help="record request-lifecycle spans while serving "
                         "and write a Chrome trace_event JSON to OUT "
                         "(open in chrome://tracing or Perfetto), plus "
                         "a greppable JSONL sibling at OUT + 'l'")
    args = ap.parse_args()
    print(f"compile cache: {enable_compile_cache()}")

    t0 = time.time()
    bench = load_benchmark(n_per_dataset=args.n_per_dataset)
    names = list(bench)
    aes, _ = train_bank([(n, bench[n]["server"][0]) for n in names],
                        epochs=args.epochs, batch_size=64)
    cents = [(bench[n]["server"][0], bench[n]["server"][1]) for n in names]
    matcher = build_matcher(aes, names, cents)
    print(f"[{time.time()-t0:.1f}s] matcher ready ({len(names)} experts)")

    hub = None
    if args.hub_slots > 0:
        # the hub slot bank requires one homogeneous architecture
        # (equal ExpertSpec = slot compatibility); checkpoint each
        # expert cold so staging exercises the full lifecycle
        cfg = get_config("llama3_2_1b").reduced(name="llama-hub")
        model = build_model(cfg)
        kv = args.kv if model.supports_paged_kv else "ring"
        store = args.store or tempfile.mkdtemp(prefix="expert-store-")
        hub = ExpertHub(model, n_slots=args.hub_slots, max_len=64,
                        kv_layout=kv, store=store)
        for i, n in enumerate(names):
            hub.add_expert(n, model.init(jax.random.PRNGKey(i)),
                           cold=True)
        registry = hub.build_registry()
        print(f"[{time.time()-t0:.1f}s] hub: {len(registry)} experts "
              f"checkpointed to {store}, {args.hub_slots} device slots")
    else:
        registry = ExpertRegistry()
        for i, n in enumerate(names):
            arch = ALL_ARCHS[i % len(ALL_ARCHS)]
            cfg = get_config(arch).reduced(name=f"{arch}@{n}")
            if cfg.family in ("encdec", "vlm"):  # token-only demo
                cfg = get_config("llama3_2_1b").reduced(name=f"llama@{n}")
            model = build_model(cfg)
            kv = args.kv if model.supports_paged_kv else "ring"
            registry.add(n, ExpertEngine(model, model.init(
                jax.random.PRNGKey(i)), max_len=64, kv_layout=kv),
                arch=cfg.name)
    tracer = Tracer() if args.trace else None
    server = RoutedServer(matcher, registry, executor=args.executor,
                          hub=hub, tracer=tracer)

    rng = np.random.default_rng(0)
    reqs, truth = [], []
    for uid in range(args.requests):
        n = names[rng.integers(len(names))]
        x, _ = bench[n]["client_a"]
        reqs.append(Request(uid=uid, features=x[rng.integers(len(x))],
                            prompt=rng.integers(0, 100, size=8),
                            max_new_tokens=args.max_new))
        truth.append(n)
    t1 = time.time()
    resps = server.serve(reqs)
    dt = time.time() - t1
    acc = np.mean([r.expert == t for r, t in zip(resps, truth)])
    print(f"served {len(resps)} reqs in {dt:.2f}s "
          f"({len(resps)/dt:.1f} req/s); routing accuracy {acc:.1%}")
    st = server.stats
    blocks = sum(es.host_blocks
                 for es in {**st["engines"], **st["banks"]}.values())
    print(f"executor={args.executor}: {blocks} host-blocking syncs "
          f"across all engines")
    if hub is not None:
        print(f"hub: {hub.stats!r}")
        print(f"resident now: "
              f"{[hub.catalog[e].name for e in hub.resident_experts]} "
              f"({server.scheduler.stats.resident_stalls} "
              "resident-miss stalls)")
    if tracer is not None:
        n_events = tracer.export_chrome(args.trace)
        tracer.export_jsonl(args.trace + "l")
        print(f"trace: {n_events} events -> {args.trace} "
              f"(+ {args.trace}l)")


if __name__ == "__main__":
    main()
