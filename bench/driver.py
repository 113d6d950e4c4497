"""The run itself, after ``run.py`` has found the chip: set-up, window,
metrics, check. Returns the result line as a dict."""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from . import (correct, fingerprints, harness, spec, stats, traffic,
               xtrace)
from .harness import log

TRACE_MAX_S = 4.0


@dataclasses.dataclass
class Served:
    """One answered request, as the metric readers see it."""
    prompt_len: int
    padded_len: int
    tokens: int


@dataclasses.dataclass
class View:
    """What a per-layer metric reader may read. ``counters`` are over
    the whole window, ``trace_counters`` over the profiled seconds.
    ``family`` is the cell's family module (its work counts), ``arch``
    its sizes."""
    family: Any
    arch: Any
    experts: int                  # experts the matcher scores
    centroids: int                # class centroids per expert
    experts_per_dispatch: int
    peaks: Dict[str, float]
    counters: Dict[str, Any]
    trace_counters: Optional[Dict[str, Any]]
    spans: List[Dict[str, Any]]
    trace: Optional[xtrace.Reduced]
    served: List[Served]

    def decoded_in_trace(self) -> float:
        """Tokens decoded in the profiled seconds: the decode dispatches
        made there, times the tokens a dispatch decodes on average over
        the whole window. Dispatches are counted as they are made;
        tokens only when a row finishes, which may lie far from the
        profiled seconds, so only the whole window's ratio is used. The
        window drains, so its tokens and dispatches belong together."""
        e, t = self.counters["engine"], self.trace_counters["engine"]
        steps = e.get("decode_steps", 0)
        if not steps:
            return 0.0
        decoded = max(e.get("tokens_generated", 0)
                      - e.get("rows_served", 0), 0)
        return t.get("decode_steps", 0) * decoded / steps


def _e2e(cell, win, setup_s: float) -> Dict[str, Dict[str, Any]]:
    offers = win.offers
    lat = stats.latencies([o.due for o in offers], win.done, win.gave_up)
    toks = [len(win.responses[o.uid].tokens) if o.uid in win.responses
            else 0 for o in offers]
    values = {
        "tok_s": stats.tokens_per_s(toks, win.done, win.seconds),
        "latency_p50_s": stats.percentile(lat, 50),
        "latency_p95_s": stats.percentile(lat, 95),
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def _per_layer(cell, view: View) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(view)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(cell: spec.Cell, seed: int, seconds: float,
        trace_dir: Optional[str], peaks: Dict[str, float],
        t_start: float) -> Dict[str, Any]:
    compiles = harness.start()
    device = jax.devices()[0]
    log(f"device: {device.device_kind} x{len(jax.devices())}; cell "
        f"{cell.name}: {cell.config['name']} under "
        f"{ {k: v for k, v in cell.traffic.items() if k != 'server'} }")

    sys_ = harness.build(cell, seed, t_start)
    harness.warm(sys_)
    offers = traffic.offered(cell.traffic, seed, seconds,
                             sys_.arch.vocab, sys_.clients)
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f} s in all; {compiles.lowered} programs "
        f"lowered, {compiles.compile_s:.1f} s compiling, persistent "
        f"cache {dict(compiles.cache)}")

    trace_s = min(TRACE_MAX_S, seconds / 3) if trace_dir else 0.0
    win = harness.run_window(sys_, offers, seconds, compiles,
                             trace_dir=trace_dir, trace_s=trace_s)
    stats_ = device.memory_stats() or {}
    peak = int(stats_.get("peak_bytes_in_use", 0))
    late = sorted(win.lateness) or [0.0]
    answered = sum(d is not None for d in win.done)
    failed = len(offers) - answered
    log(f"window: {len(offers)} offered in {seconds:g} s, {answered} "
        f"answered, {win.rejected} refused by the queue, {failed} failed; "
        f"generator late by mean {np.mean(late) * 1e3:.3f} ms, p95 "
        f"{stats.percentile(late, 95) * 1e3:.3f} ms, max "
        f"{late[-1] * 1e3:.3f} ms")
    log(f"window: {win.lowered_in_window} programs compiled or loaded "
        f"inside the window (want 0)")

    result: Dict[str, Any] = {"correct": False, "attempted": len(offers),
                              "failed": failed}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace_dir:
        red = xtrace.reduce(trace_dir)
        backend = sys_.server.registry[0].backend
        served = [Served(len(o.prompt),
                         int(backend.pad_shape(1, len(o.prompt))[1]),
                         len(win.responses[o.uid].tokens))
                  for o in offers if o.uid in win.responses]
        view = View(family=sys_.family, arch=sys_.arch,
                    experts=len(sys_.names),
                    centroids=max(fingerprints.SPECS[n][0]
                                  for n in sys_.names),
                    experts_per_dispatch=(
                        len(sys_.names) if cell.config["deployment"]
                        ["placement"] == "bank" else 1),
                    peaks=peaks, counters=win.counters,
                    trace_counters=win.trace_counters,
                    spans=win.spans or [], trace=red, served=served)
        result["metrics"] = _per_layer(cell, view)
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    else:
        result["metrics"] = _e2e(cell, win, setup_s)
    result["device"] = dev
    log(f"metrics: {result['metrics']}")
    log(f"memory: peak {peak} bytes")

    t = time.perf_counter()
    got = correct.collect(sys_, win)
    correct.free(sys_)
    log(f"check: server freed, {harness._mem('bytes_in_use')} bytes in "
        f"use")
    readings = correct.readings(sys_, got)
    ok, table = correct.verdict(cell.config, readings)
    log(f"check: {readings['checked_tokens']} served tokens from "
        f"{len(got['samples'])} responses, {readings['exact_argmax']} the "
        f"exact f32 argmax; {len(got['features'])} routes "
        f"({time.perf_counter() - t:.3f} s)")
    for k, v in table.items():
        log(f"compared: {k} {v['value']:.6g} limit {v['limit']:g}")
    result["correct"] = bool(ok)
    result["compared"] = table
    return result
