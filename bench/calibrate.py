"""Readings that the check's limits are set from, for one cell.

    python3 bench/calibrate.py --workload smollm6.fresh \\
        --seeds 11,12,13 --seconds 10

For each seed, in one process (the compile cache stays warm between
them): set up the cell, run a window at the cell's own load, and read
every compared number twice: once for what the program served (the
lower readings, from sound runs) and once for the control, the step
below the stated precision put in the program's place (the upper
readings). Prints one JSON line per seed. The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def readings_for_seed(cell, seed: int, seconds: float, compiles,
                      t_start: float):
    from bench import correct, harness, traffic
    sys_ = harness.build(cell, seed, t_start)
    harness.warm(sys_)
    offers = traffic.offered(cell.traffic, seed, seconds, sys_.arch.vocab,
                             sys_.clients)
    win = harness.run_window(sys_, offers, seconds, compiles)
    got = correct.collect(sys_, win)
    correct.free(sys_)
    program = correct.readings(sys_, got)
    control = correct.readings(sys_, got, control=True)
    return {"seed": seed, "answered": len(got["features"]),
            "offered": len(offers), "program": program,
            "control": control,
            "verdict_program": correct.verdict(cell.config, program)[0],
            "verdict_control": correct.verdict(cell.config, control)[0]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    from bench import harness, spec
    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    compiles = harness.start()
    for s in args.seeds.split(","):
        t = time.perf_counter()
        out = readings_for_seed(cell, int(s), args.seconds, compiles, t)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
