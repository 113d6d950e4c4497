"""Pallas kernel validator (rules P001-P004): BlockSpec geometry
checked on a CPU-only runner.

On the CPU every serving kernel runs in the Pallas interpreter, so CI
can execute it without a TPU — but interpret mode checks *none* of the
Mosaic lowering constraints, so a BlockSpec whose index map walks off
the operand, a block that doesn't divide its array, or a scratch buffer
in an illegal memory space all pass CI green and explode on the first
real-TPU run. This pass closes the CPU-checkable half of that gap
statically (``tests/test_tpu_compile.py`` closes the rest by compiling
the routing kernels for a described v5e chip):

  P001  block-shape divisibility: every BlockSpec dim must divide its
        operand dim (the repo's kernels are written no-padding; a
        non-dividing block silently reads garbage lanes in the last
        block).
  P002  index-map bounds: the index map, evaluated over the full grid
        (or its corners when the grid is large) with the call's real
        scalar-prefetch operands, must return one block index per
        operand dim with ``idx*block + block <= dim``.
  P003  memory-space / VMEM-budget legality: scratch buffers must live
        in an addressable TPU space (VMEM/SMEM/semaphore), and the
        per-grid-step working set (all in/out blocks + scratch) must
        fit the ~16 MiB per-core VMEM the guide documents.
  P004  (warning) tile alignment: a block's last dim should be a
        multiple of the 128-lane VREG width — or span the whole
        operand axis, which Mosaic pads internally.

Capture, not execution: ``pl.pallas_call`` is monkeypatched with a
recorder that notes the grid/spec geometry and the concrete call
shapes, then returns zero outputs — so each kernel's own Python
wrapper (reshapes, moveaxis, block-size snapping) runs for real and
the checked specs are exactly what a TPU lowering would see.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import os
from typing import Any, Callable, Dict, Iterator, List, Optional, \
    Sequence, Tuple

from . import REPO_ROOT, Violation

LANE = 128
VMEM_BYTES = 16 * 1024 * 1024          # per-core, from the TPU guide
_GRID_ENUM_CAP = 4096                  # full enumeration bound

_LEGAL_SCRATCH_SPACES = {"vmem", "smem", "semaphore"}


@dataclasses.dataclass
class PallasCallRecord:
    """One captured ``pl.pallas_call`` invocation."""
    kernel_name: str
    path: str                          # repo-relative file of the kernel
    line: int
    grid: Tuple[int, ...]
    in_specs: Sequence[Any]
    out_specs: Sequence[Any]
    scratch_shapes: Sequence[Any]
    num_scalar_prefetch: int
    in_shapes: Sequence[Tuple[Tuple[int, ...], Any]]   # (shape, dtype)
    out_shapes: Sequence[Tuple[Tuple[int, ...], Any]]
    scalar_args: Sequence[Any]         # host copies of prefetch operands


def _kernel_origin(kernel: Callable) -> Tuple[str, str, int]:
    fn = kernel
    while hasattr(fn, "func"):         # unwrap functools.partial
        fn = fn.func
    name = getattr(fn, "__name__", str(fn))
    code = getattr(fn, "__code__", None)
    if code is None:
        return name, "<unknown>", 0
    path = os.path.relpath(code.co_filename, REPO_ROOT)
    return name, path.replace(os.sep, "/"), code.co_firstlineno


def _flat(specs: Any) -> List[Any]:
    if specs is None:
        return []
    if isinstance(specs, (list, tuple)):
        return list(specs)
    return [specs]


@contextlib.contextmanager
def capture_pallas_calls() -> Iterator[List[PallasCallRecord]]:
    """Swap ``pl.pallas_call`` for a recorder returning zero outputs.

    The wrapper under test runs eagerly; every pallas_call it makes is
    appended to the yielded list instead of executing."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    records: List[PallasCallRecord] = []
    orig = pl.pallas_call

    def recorder(kernel, out_shape=None, *, grid_spec=None, grid=(),
                 in_specs=None, out_specs=None, scratch_shapes=(),
                 interpret=False, **_kw):
        name, path, line = _kernel_origin(kernel)
        if grid_spec is not None:
            g = tuple(grid_spec.grid)
            ins = _flat(grid_spec.in_specs)
            outs = _flat(grid_spec.out_specs)
            scratch = _flat(getattr(grid_spec, "scratch_shapes", ()))
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0))
        else:
            g = tuple(grid) if isinstance(grid, (tuple, list)) else \
                (grid,)
            ins, outs = _flat(in_specs), _flat(out_specs)
            scratch, nsp = _flat(scratch_shapes), 0

        def runner(*args):
            shapes = [(tuple(a.shape), a.dtype) for a in args]
            out_leaves = jax.tree_util.tree_leaves(
                out_shape, is_leaf=lambda x: hasattr(x, "shape"))
            records.append(PallasCallRecord(
                kernel_name=name, path=path, line=line, grid=g,
                in_specs=ins, out_specs=outs, scratch_shapes=scratch,
                num_scalar_prefetch=nsp,
                in_shapes=shapes[nsp:],
                out_shapes=[(tuple(o.shape), o.dtype)
                            for o in out_leaves],
                scalar_args=[np.asarray(a) for a in args[:nsp]]))
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), out_shape,
                is_leaf=lambda x: hasattr(x, "shape"))

        return runner

    pl.pallas_call = recorder
    try:
        yield records
    finally:
        pl.pallas_call = orig


# ---------------------------------------------------------------------------
# geometry checks over one record
# ---------------------------------------------------------------------------


def _grid_points(grid: Tuple[int, ...]) -> List[Tuple[int, ...]]:
    total = 1
    for g in grid:
        total *= max(int(g), 1)
    if total <= _GRID_ENUM_CAP:
        return list(itertools.product(*(range(int(g)) for g in grid)))
    corners = itertools.product(*(sorted({0, int(g) - 1})
                                  for g in grid))
    return list(corners)


def _dtype_bytes(dtype: Any) -> int:
    import numpy as np
    return int(np.dtype(dtype).itemsize)


def check_record(rec: PallasCallRecord, case: str) -> List[Violation]:
    out: List[Violation] = []

    def v(rule: str, msg: str, severity: str = "error") -> None:
        out.append(Violation(rule, rec.path, rec.line,
                             f"{rec.kernel_name}[{case}]", msg,
                             severity=severity))

    roles = ([("in", i, s, sh) for i, (s, sh) in
              enumerate(zip(rec.in_specs, rec.in_shapes))]
             + [("out", i, s, sh) for i, (s, sh) in
                enumerate(zip(rec.out_specs, rec.out_shapes))])
    if len(rec.in_specs) != len(rec.in_shapes):
        v("P001", f"{len(rec.in_specs)} in_specs for "
          f"{len(rec.in_shapes)} non-prefetch operands")
    if len(rec.out_specs) != len(rec.out_shapes):
        v("P001", f"{len(rec.out_specs)} out_specs for "
          f"{len(rec.out_shapes)} outputs")

    vmem = 0
    for role, i, spec, (shape, dtype) in roles:
        block = tuple(spec.block_shape)
        where = f"{role}_specs[{i}] (operand {shape})"
        if len(block) != len(shape):
            v("P001", f"{where}: block rank {len(block)} != operand "
              f"rank {len(shape)}")
            continue
        nb = 1
        for d, (b, s) in enumerate(zip(block, shape)):
            if b is None:
                b = s
            if b <= 0 or s % b:
                v("P001", f"{where}: block dim {d} = {b} does not "
                  f"divide operand dim {s} (last block would read "
                  "out of bounds)")
            nb *= max(int(b), 1)
        vmem += nb * _dtype_bytes(dtype)
        # P004 — lane alignment (warning): last block dim must be a
        # multiple of the 128-lane VREG or take the whole axis
        if block and block[-1] is not None and shape:
            last = int(block[-1])
            if last % LANE and last != shape[-1]:
                v("P004", f"{where}: last block dim {last} is neither "
                  f"a multiple of {LANE} lanes nor the full axis "
                  f"({shape[-1]}) — Mosaic will pad or reject",
                  severity="warning")

    # P002 — index-map bounds over the grid
    points = _grid_points(rec.grid)
    for role, i, spec, (shape, dtype) in roles:
        imap = getattr(spec, "index_map", None)
        block = tuple(spec.block_shape)
        if imap is None or len(block) != len(shape):
            continue
        where = f"{role}_specs[{i}]"
        for pt in points:
            try:
                idx = imap(*pt, *rec.scalar_args)
            except Exception as e:   # noqa: BLE001 — report as finding
                v("P002", f"{where}: index map raised {e!r} at grid "
                  f"point {pt}")
                break
            idx = tuple(idx) if isinstance(idx, (tuple, list)) else \
                (idx,)
            if len(idx) != len(shape):
                v("P002", f"{where}: index map returned {len(idx)} "
                  f"indices for rank-{len(shape)} operand at {pt}")
                break
            bad = False
            for d, (j, b, s) in enumerate(zip(idx, block, shape)):
                b = s if b is None else b
                j = int(j)
                if j < 0 or (j + 1) * int(b) > s:
                    v("P002", f"{where}: grid point {pt} maps dim {d} "
                      f"to block {j} (elements {j * int(b)}.."
                      f"{(j + 1) * int(b)}) outside operand dim {s}")
                    bad = True
                    break
            if bad:
                break

    # P003 — scratch memory space + VMEM budget
    for i, sc in enumerate(rec.scratch_shapes):
        space = str(getattr(sc, "memory_space", "vmem") or "vmem")
        space = space.split(".")[-1].lower()
        if space not in _LEGAL_SCRATCH_SPACES:
            v("P003", f"scratch_shapes[{i}]: memory space {space!r} is "
              "not addressable from a TPU kernel (use VMEM/SMEM/"
              "semaphore)")
        shape = tuple(getattr(sc, "shape", ()))
        n = 1
        for s in shape:
            n *= int(s)
        if space == "vmem":
            vmem += n * _dtype_bytes(getattr(sc, "dtype", "float32"))
    if vmem > VMEM_BYTES:
        v("P003", f"per-grid-step working set {vmem / 2**20:.1f} MiB "
          f"exceeds the ~{VMEM_BYTES // 2**20} MiB per-core VMEM "
          "(shrink the block sizes)")
    return out


# ---------------------------------------------------------------------------
# kernel registry — representative serving shapes per kernel
# ---------------------------------------------------------------------------


def _cases() -> List[Tuple[str, Callable[[], Any]]]:
    import jax.numpy as jnp
    import numpy as np

    def ring(B, H, KV, dh, S, bs, dtype=jnp.float32):
        def build():
            from repro.kernels.decode_attention import \
                decode_attention_pallas
            z = lambda *s: jnp.zeros(s, dtype)          # noqa: E731
            decode_attention_pallas(
                z(B, H, dh), z(B, S, KV, dh), z(B, S, KV, dh),
                jnp.zeros((), jnp.int32),
                jnp.zeros((S,), jnp.int32), block_s=bs)
        return build

    def paged(B, H, KV, dh, page, nlp, dtype=jnp.float32):
        def build():
            from repro.kernels.decode_attention import \
                paged_decode_attention_pallas
            P1 = B * nlp + 1
            z = lambda *s: jnp.zeros(s, dtype)          # noqa: E731
            tbl = np.arange(B * nlp, dtype=np.int32).reshape(B, nlp)
            paged_decode_attention_pallas(
                z(B, H, dh), z(P1, page, KV, dh), z(P1, page, KV, dh),
                jnp.asarray(tbl), jnp.zeros((), jnp.int32),
                jnp.zeros((nlp * page,), jnp.int32))
        return build

    def cosine(B, M, h):
        def build():
            from repro.kernels.cosine_topk import cosine_scores_pallas
            cosine_scores_pallas(jnp.zeros((B, h)), jnp.zeros((M, h)),
                                 jnp.zeros((M,)))
        return build

    def escore(B, D, H, K):
        def build():
            from repro.kernels.expert_score import expert_score_pallas, \
                pad_to_lane
            Dp = pad_to_lane(D)
            expert_score_pallas(
                jnp.zeros((B, Dp)), jnp.zeros((K, Dp, H)),
                jnp.zeros((K, H)), jnp.zeros((K, H, Dp)),
                jnp.zeros((K, Dp)), d_real=D)
        return build

    def wkv(B, H, P):
        def build():
            from repro.kernels.wkv_step import wkv_step_pallas
            z = lambda *s: jnp.zeros(s)                 # noqa: E731
            wkv_step_pallas(z(B, H, P), z(B, H, P), z(B, H, P),
                            z(B, H, P), z(H, P), z(B, H, P, P))
        return build

    return [
        ("ring_B2_H8_KV2_dh128_S1024", ring(2, 8, 2, 128, 1024, 256)),
        ("ring_B4_H8_KV2_dh64_S512_bf16",
         ring(4, 8, 2, 64, 512, 128, jnp.bfloat16)),
        ("paged_B3_H8_KV2_dh64_p8", paged(3, 8, 2, 64, 8, 8)),
        ("paged_B2_H16_KV2_dh128_p16", paged(2, 16, 2, 128, 16, 4)),
        ("cosine_B256_M10_h128", cosine(256, 10, 128)),
        ("expert_score_B128_D784_H128_K6", escore(128, 784, 128, 6)),
        ("wkv_B2_H4_P64", wkv(2, 4, 64)),
        ("wkv_B1_H8_P128", wkv(1, 8, 128)),
    ]


def run() -> List[Violation]:
    out: List[Violation] = []
    for case, build in _cases():
        with capture_pallas_calls() as records:
            build()
        if not records:
            out.append(Violation(
                "P001", "src/repro/kernels", 0, case,
                "kernel wrapper made no pallas_call (capture broken?)"))
        for rec in records:
            out.extend(check_record(rec, case))
    return out
