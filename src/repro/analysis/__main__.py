"""CLI for the contract checkers.

    python -m repro.analysis --all --fail-on-violation
    python -m repro.analysis lint pallas races
    python -m repro.analysis sanitizer
    python -m repro.analysis --emit-baseline races

The ``hlo`` pass needs >= 8 devices, which on a CPU-only runner means
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set *before*
jax initialises. The CLI handles that itself: the parent process runs
``lint``/``pallas``/``races`` in-process (they need no device mesh),
runs the ``sanitizer`` schedule fuzzer in-process too (its stub-model
hubs are CPU-friendly), and re-execs ``hlo`` as a child with the
forced-device environment, collecting the child's findings over a
JSON pipe. The ``obs`` pass (rules O001–O003, the tracing/metrics
contract) is pure AST like ``lint`` and runs in-process. Exit status with ``--fail-on-violation``: 0 when every
error-severity finding is covered by ``baseline.toml``, 1 otherwise
(the report prints a ready to paste baseline stanza per unbaselined
error; ``--emit-baseline`` prints *only* those stanzas, for piping
straight into the file).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
from typing import List

from . import (Violation, apply_baseline, format_report, load_baseline,
               REPO_ROOT)

_PASSES = ("lint", "obs", "hlo", "pallas", "races", "sanitizer")
_CHILD_FLAG = "--emit-json"


def _run_lint() -> List[Violation]:
    from . import lint
    return lint.run()


def _run_obs() -> List[Violation]:
    from . import obs_lint
    return obs_lint.run()


def _run_pallas() -> List[Violation]:
    from . import pallas_check
    return pallas_check.run()


def _run_races() -> List[Violation]:
    from . import races
    return races.run()


def _run_sanitizer() -> List[Violation]:
    from . import sanitizer
    return sanitizer.run()


def _run_hlo_inprocess() -> List[Violation]:
    from . import hlo_contracts
    return hlo_contracts.run()


def _run_hlo_subprocess() -> List[Violation]:
    """Re-exec the hlo pass with the 8-device CPU environment forced
    before jax can initialise in the child. The child never takes the
    TPU: a chip belongs to one process, and the parent may hold it."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(REPO_ROOT, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "hlo", _CHILD_FLAG],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"hlo contract child failed (exit {proc.returncode}):\n"
            f"{proc.stdout}\n{proc.stderr}")
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith("JSON:"):
            payload = line[len("JSON:"):]
    if payload is None:
        raise RuntimeError(
            f"hlo contract child produced no JSON line:\n{proc.stdout}")
    return [Violation(**d) for d in json.loads(payload)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static contract checkers for the serving stack")
    ap.add_argument("passes", nargs="*", choices=(*_PASSES, []),
                    help=f"passes to run (default: all of {_PASSES})")
    ap.add_argument("--all", action="store_true",
                    help="run every pass (same as naming none)")
    ap.add_argument("--fail-on-violation", action="store_true",
                    help="exit 1 if any unbaselined error remains")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore baseline.toml (show every finding)")
    ap.add_argument("--emit-baseline", action="store_true",
                    help="print only ready-to-paste baseline stanzas "
                         "for the unbaselined errors, nothing else")
    ap.add_argument(_CHILD_FLAG, dest="emit_json", action="store_true",
                    help=argparse.SUPPRESS)   # internal child protocol
    args = ap.parse_args(argv)

    passes = list(args.passes) or list(_PASSES)
    if args.all:
        passes = list(_PASSES)

    violations: List[Violation] = []
    for p in passes:
        if p == "lint":
            violations += _run_lint()
        elif p == "obs":
            violations += _run_obs()
        elif p == "pallas":
            violations += _run_pallas()
        elif p == "races":
            violations += _run_races()
        elif p == "sanitizer":
            violations += _run_sanitizer()
        elif p == "hlo":
            if args.emit_json:
                violations += _run_hlo_inprocess()
            else:
                violations += _run_hlo_subprocess()

    if args.emit_json:
        print("JSON:" + json.dumps(
            [dataclasses.asdict(v) for v in violations]))
        return 0

    entries = [] if args.no_baseline else load_baseline()
    active, suppressed = apply_baseline(violations, entries)
    if args.emit_baseline:
        for v in active:
            if v.severity == "error":
                print(v.stanza())
                print()
        return 0
    print(f"repro.analysis: {' '.join(passes)} — "
          f"{len(active)} active finding(s), "
          f"{len(suppressed)} baselined")
    print(format_report(active, suppressed))
    errors = [v for v in active if v.severity == "error"]
    if args.fail_on_violation and errors:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
