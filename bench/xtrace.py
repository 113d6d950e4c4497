"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Reads the file with ``jax.profiler.ProfileData`` only. The device plane
gives operation events on the chip; the host plane gives the harness's
own ``jax.profiler.TraceAnnotation`` spans (``bench.submit``,
``bench.step``, ``bench.wait``), which say what the host was doing in
each gap. Both are on the profiler's one clock.

Each device operation is put in a class: ``step`` for the operations
of the engine's step programs (paged prefill and decode), found by the
name of the program that ran them (``STEP_MODULES``); ``expert_score``
and ``cosine_scores`` for the routing kernels' own calls, found by the
kernel's name on its custom call; ``other`` for the rest (routing
around the kernels, sampling, copies between programs). On a TPU the
program of an operation is the ``XLA Modules`` event that contains it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

HOST_PREFIX = "bench."


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    seconds: Dict[str, float]          # device seconds per class
    kernel_calls: Dict[str, List[float]]   # each call's device seconds
    top_ops: List[Tuple[str, float]]
    gaps: List[Tuple[str, float]]

    def breakdown(self) -> Dict[str, List]:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(busy: Sequence[Tuple[float, float]], t0: float, t1: float
              ) -> List[Tuple[float, float]]:
    """The complement of the busy intervals inside [t0, t1)."""
    out, cur = [], t0
    for s, e in sorted(busy):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host: Sequence[Tuple[float, float, str]]
               ) -> List[Tuple[str, float]]:
    """Each gap, longest first, named by the host span that covers most
    of it (``host:other`` where none does)."""
    out = []
    for s, e in gaps:
        best, name = 0.0, "host:other"
        for hs, he, hn in host:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, hn
        out.append((name, e - s))
    out.sort(key=lambda x: -x[1])
    return out


# the engine jits its paged prefill and decode as unnamed lambdas, so
# their programs are ``jit__lambda``; no other program of the serving
# path is a lambda
STEP_MODULES = ("jit__lambda",)
KERNELS = {"cosine_scores": "%cosine_scores", "expert_score": "%expert_score"}
KERNEL_MARK = "custom-call"


@dataclasses.dataclass
class _Op:
    name: str
    start: float          # seconds, on the profile's clock
    dur: float
    module: str           # the program that ran it, without its id


def _stats(ev) -> Dict[str, object]:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def _event(ev, module: str) -> _Op:
    return _Op(name=ev.name, start=ev.start_ns * 1e-9,
               dur=ev.duration_ns * 1e-9, module=module)


def _module_name(name: str) -> str:
    """``jit_f(1234)`` -> ``jit_f``."""
    return name.split("(", 1)[0]


def _device_ops(pd) -> Dict[str, List[_Op]]:
    """Operation events per device plane. On a TPU these are the
    ``XLA Ops`` of the ``/device:TPU:n`` planes, each given the program
    of the ``XLA Modules`` event it lies in; on the CPU backend (tests)
    the host plane's events that name their ``hlo_module`` stand in for
    one device."""
    out: Dict[str, List[_Op]] = {}
    for p in pd.planes:
        if not p.name.startswith("/device:"):
            continue
        lines = {l.name: list(l.events) for l in p.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       _module_name(e.name))
                      for e in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        ops = []
        for ev in lines.get("XLA Ops", []):
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            inside = i >= 0 and ev.start_ns <= mods[i][1]
            ops.append(_event(ev, mods[i][2] if inside else ""))
        if ops:
            out[p.name] = ops
    if out:
        return out
    for p in pd.planes:
        if p.name != "/host:CPU":
            continue
        ops = []
        for line in p.lines:
            for ev in line.events:
                st = _stats(ev)
                if "hlo_module" in st and not ev.name.startswith("end: "):
                    ops.append(_event(ev, str(st["hlo_module"])))
        out[p.name] = ops
    return out


def _host_spans(pd) -> List[Tuple[float, float, str]]:
    spans = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    s = ev.start_ns * 1e-9
                    spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
    return spans


def _profile_window(pd, ops: Sequence[_Op]) -> Tuple[float, float]:
    """The traced window on the events' clock: ``ProfileData`` gives
    event times from the profile's start (on the CPU and on a v5e), and
    the Task Environment plane the start and stop on the wall clock."""
    for p in pd.planes:
        if p.name == "Task Environment":
            st = _stats(p)
            if "profile_start_time" in st and "profile_stop_time" in st:
                return 0.0, (st["profile_stop_time"]
                             - st["profile_start_time"]) * 1e-9
    if not ops:
        return 0.0, 0.0
    return (min(o.start for o in ops),
            max(o.start + o.dur for o in ops))


def classify(ops: Sequence[_Op]) -> List[str]:
    """A class per operation: a routing kernel's own call is named
    after the kernel; every other operation of an engine step program
    is ``step``; the rest is ``other``."""
    out = []
    for o in ops:
        cls = "other"
        if KERNEL_MARK in o.name:
            for kernel, head in KERNELS.items():
                if o.name.startswith(head):
                    cls = kernel
        if cls == "other" and o.module in STEP_MODULES:
            cls = "step"
        out.append(cls)
    return out


def _short(name: str) -> str:
    """An operation's name for the breakdown: the HLO instruction's name
    without its number, and its result type where that is one array
    (``%copy.101 = bf16[6,2049]{...} copy(...)`` -> ``copy
    bf16[6,2049]``)."""
    head, eq, rest = name.partition(" = ")
    head = head.lstrip("%")
    base, _, tail = head.rpartition(".")
    if base and tail.isdigit():
        head = base
    if eq and rest[:1] != "(":
        shape = rest.split("{", 1)[0].split(" ", 1)[0]
        return f"{head} {shape}"
    return head


def reduce(trace_dir: str) -> Reduced:
    """Device metrics of the profile under ``trace_dir``. Busy time is
    the union of operation intervals inside the window, averaged over
    the chips; so is each class's time, since an operation such as a
    loop contains the operations of its body."""
    import jax
    pd = jax.profiler.ProfileData.from_file(find_xplane(trace_dir))
    per_dev = _device_ops(pd)
    all_ops = [o for ops in per_dev.values() for o in ops]
    t0, t1 = _profile_window(pd, all_ops)
    window = max(t1 - t0, 0.0)
    n_dev = max(len(per_dev), 1)
    busy, seconds = 0.0, {}
    kernel_calls: Dict[str, List] = {k: [] for k in KERNELS}
    per_name: Dict[str, float] = {}
    first_busy = None
    for ops in per_dev.values():
        by_cls: Dict[str, List[Tuple[float, float]]] = {}
        iv = []
        for o, cls in zip(ops, classify(ops)):
            if o.start + o.dur <= t0 or o.start >= t1:
                continue
            span = (max(o.start, t0), min(o.start + o.dur, t1))
            iv.append(span)
            by_cls.setdefault(cls, []).append(span)
            if cls in kernel_calls:
                kernel_calls[cls].append(o.dur)
            key = f"{cls}:{_short(o.name)}"
            per_name[key] = per_name.get(key, 0.0) + o.dur
        busy += union_seconds(iv) / n_dev
        for cls, spans in by_cls.items():
            seconds[cls] = seconds.get(cls, 0.0) + union_seconds(spans) / n_dev
        if first_busy is None:
            first_busy = iv
    gaps = label_gaps(idle_gaps(first_busy or [], t0, t1), _host_spans(pd))
    top = sorted(per_name.items(), key=lambda kv: -kv[1])
    return Reduced(window_s=window, busy_s=busy, seconds=seconds,
                   kernel_calls=kernel_calls, top_ops=top, gaps=gaps)
