"""Pallas TPU kernels: GQA flash-decode (single query token vs. KV cache),
dense-ring and paged variants.

``decode_attention_pallas`` — grid (B, KV_heads, S_blocks); for each
(batch row, kv head) the G = H/KV query heads attend to one KV-cache
block per grid step with an online-softmax carried in VMEM scratch
(m, l, acc). Position ids (-1 = empty ring slot) provide the mask, so
full and sliding-window ring caches use the same kernel. Block size is
the VMEM tiling knob: (block_s, dh) K/V tiles.

``paged_decode_attention_pallas`` — the paged-KV variant: K/V live in a
pool of fixed-size pages ``(P + 1, page, KV, dh)`` (last page is the
write-discard "trash" page) and each row carries a page table mapping
its logical cache pages to physical pool pages, so prefix-sharing rows
point at the *same* physical pages with zero copying. The table rides
in as a scalar-prefetch argument (``pltpu.PrefetchScalarGridSpec``):
the BlockSpec index maps read ``table[b, s]`` to DMA exactly the pages
a row owns — the kernel never materialises a dense per-row KV view.
The online-softmax body is shared with the ring kernel; position ids
are logical-slot-indexed and mask trash-backed (never-written) pages.

The pure-jnp oracle for both is ``repro.models.attention.attention``
(chunk=0), composed with a page-table gather for the paged variant
(``repro.kernels.ref.paged_decode_attention_ref``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import resolve_interpret

NEG_INF = -1e30


def _kernel(qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, window: int, n_blocks: int):
    blk = pl.program_id(2)

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]          # (G, dh)
    k = k_ref[0]             # (bs, dh)
    v = v_ref[0]             # (bs, dh)
    kv_pos = pos_ref[0]      # (bs,)
    q_pos = qpos_ref[0]      # scalar int32
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    s = jnp.dot(q.astype(jnp.float32), k.T.astype(jnp.float32)) * scale
    ok = (kv_pos >= 0) & (kv_pos <= q_pos)
    if window:
        ok &= kv_pos > q_pos - window
    s = jnp.where(ok[None, :], s, NEG_INF)

    m_prev = m_ref[...]      # (G, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v.astype(jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(blk == n_blocks - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, q_pos, kv_pos, *, window: int = 0,
                            block_s: int = 512,
                            interpret: Optional[bool] = None):
    """q: (B, H, dh); k, v: (B, S, KV, dh); q_pos: () int32;
    kv_pos: (S,) int32 (-1 = empty). Returns (B, H, dh)."""
    B, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    bs = min(block_s, S)
    assert S % bs == 0, (S, bs)
    n_blocks = S // bs
    qg = q.reshape(B, KV, G, dh)
    kt = jnp.moveaxis(k, 2, 1)  # (B, KV, S, dh)
    vt = jnp.moveaxis(v, 2, 1)
    out = pl.pallas_call(
        functools.partial(_kernel, window=window, n_blocks=n_blocks),
        grid=(B, KV, n_blocks),
        in_specs=[
            pl.BlockSpec((1,), lambda b, j, s: (0,)),
            pl.BlockSpec((1, 1, G, dh), lambda b, j, s: (b, j, 0, 0)),
            pl.BlockSpec((1, bs, dh), lambda b, j, s: (b * KV + j, s, 0)),
            pl.BlockSpec((1, bs, dh), lambda b, j, s: (b * KV + j, s, 0)),
            pl.BlockSpec((1, bs), lambda b, j, s: (0, s)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), lambda b, j, s: (b, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q_pos.reshape(1).astype(jnp.int32),
      qg, kt.reshape(B * KV, S, dh), vt.reshape(B * KV, S, dh),
      kv_pos[None, :].astype(jnp.int32))
    return out.reshape(B, H, dh)


def _paged_kernel(tbl_ref, qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, window: int, n_blocks: int):
    # the page-table ref is consumed by the BlockSpec index maps (it
    # decides WHICH page was DMA'd here); the softmax body is the ring
    # kernel's, operating on whatever page landed in VMEM
    del tbl_ref
    _kernel(qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, window=window, n_blocks=n_blocks)


def paged_decode_attention_pallas(q, k_pages, v_pages, table, q_pos,
                                  kv_pos, *, window: int = 0,
                                  interpret: Optional[bool] = None):
    """Flash-decode through a per-row page table.

    q: (B, H, dh); k_pages, v_pages: (P1, page, KV, dh) physical pool
    (``P1 - 1`` is the trash page — writable garbage, always masked);
    table: (B, n_pages) int32 physical page per logical page; q_pos: ()
    int32; kv_pos: (C,) int32 logical-slot positions (-1 = empty),
    C = n_pages * page. Returns (B, H, dh).

    One grid step DMAs exactly one physical page per (row, kv head):
    the scalar-prefetched table feeds the K/V BlockSpec index maps, so
    prefix-sharing rows re-read the same pool pages and no dense
    per-row KV copy ever exists.
    """
    B, H, dh = q.shape
    P1, page, KV = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    nlp = table.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, dh)
    kp = jnp.moveaxis(k_pages, 2, 1).reshape(P1 * KV, page, dh)
    vp = jnp.moveaxis(v_pages, 2, 1).reshape(P1 * KV, page, dh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, nlp),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh), lambda b, j, s, tbl, qp:
                         (b, j, 0, 0)),
            pl.BlockSpec((1, page, dh), lambda b, j, s, tbl, qp:
                         (tbl[b * nlp + s] * KV + j, 0, 0)),
            pl.BlockSpec((1, page, dh), lambda b, j, s, tbl, qp:
                         (tbl[b * nlp + s] * KV + j, 0, 0)),
            pl.BlockSpec((1, page), lambda b, j, s, tbl, qp: (0, s)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), lambda b, j, s, tbl, qp:
                               (b, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, window=window, n_blocks=nlp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(table.reshape(-1).astype(jnp.int32),
      q_pos.reshape(1).astype(jnp.int32),
      qg, kp, vp, kv_pos[None, :].astype(jnp.int32))
    return out.reshape(B, H, dh)
