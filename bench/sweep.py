"""Finds a cell's knee: the highest offered rate the system sustains.

    python3 bench/sweep.py --workload smollm6.fresh --rates 4,8,12,16 \\
        --seconds 20 --seed 5

Sets the cell up once, then offers its traffic at each rate in turn
(the mix's rate replaced, everything else as in its file) through the
same server, each window draining before the next. Prints one JSON
line per rate: tokens/s answered inside the window, latency median and
95th percentile, and the requests still unanswered at the close (a
backlog that grows with the window is a rate above the knee). Run it
when a cell is defined or the system moves the knee; the traffic file
then carries the chosen fixed rate.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from bench import harness, spec, stats, traffic
    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    compiles = harness.start()
    sys_ = harness.build(cell, args.seed, T_START)
    harness.warm(sys_)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_per_s"] = rate
        offers = traffic.offered(mix, args.seed, args.seconds,
                                 sys_.arch.vocab, sys_.clients)
        win = harness.run_window(sys_, offers, args.seconds, compiles)
        # what the drain limit left runs out before the next rate, whose
        # requests reuse the same ids
        while sys_.server.scheduler.has_work:
            sys_.server.step()
        lat = stats.latencies([o.due for o in offers], win.done,
                              win.gave_up)
        toks = [len(win.responses[o.uid].tokens)
                if o.uid in win.responses else 0 for o in offers]
        print(json.dumps({
            "rate": rate, "offered": len(offers),
            "tok_s": stats.tokens_per_s(toks, win.done, args.seconds),
            "offered_tok_s": sum(o.max_new for o in offers) / args.seconds,
            "latency_p50_s": stats.percentile(lat, 50),
            "latency_p95_s": stats.percentile(lat, 95),
            "open_at_close": sum(d is None or d > args.seconds
                                 for d in win.done),
            "failed": sum(d is None for d in win.done),
            "lowered_in_window": win.lowered_in_window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
