"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload smollm6.fresh --seed 7 --seconds 10 \\
        --trace 0

Runs in one process on the chip JAX finds, and exits non-zero with no
result unless that is a TPU with as many chips as the cell asks for.
Earlier lines (standard error) give each part of set-up, how late the
load generator ran, the programs compiled inside the window (there
should be none), and last the numbers the check compared with their
limits. The last line of standard output is the result: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profile of the last seconds of the
window and from the program's counters and spans.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _args(argv)
    import jax
    from bench import spec
    from bench.peaks import UnknownDevice, peaks_for

    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    try:
        peaks = peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from bench import driver
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") \
        if args.trace else None
    try:
        result = driver.run(cell, args.seed, args.seconds, trace_dir,
                            peaks, T_START)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
