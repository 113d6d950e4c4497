"""GQA attention: blockwise online-softmax (flash) for train/prefill,
plain masked attention for single-token decode, sliding-window support,
and KV-cache plumbing.

TPU adaptation note: instead of porting a CUDA flash-attention kernel we use
a `jax.lax.scan` over KV chunks with an online-softmax carry — XLA:TPU keeps
the (Sq x chunk) score tile in VMEM and never materializes the full S x S
matrix. The chunk size (`cfg.attn_chunk`) is a roofline tuning knob.
A Pallas flash-decode kernel (repro/kernels/decode_attention.py) covers the
decode hot path on real TPUs; the code here is also its oracle.

Masking is position-id based throughout: every key slot carries an absolute
position (-1 = empty), which makes full caches and sliding-window ring
caches look identical to the attention math.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_scores(q, k):
    """q: (B, Sq, H, dh), k: (B, Sk, KV, dh) -> (B, Sq, H, Sk) in f32."""
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, dh)
    s = jnp.einsum("bqkgd,bskd->bqkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32))
    return s.reshape(B, Sq, H, Sk)


def _gqa_av(p, v):
    """p: (B, Sq, H, Sk) f32, v: (B, Sk, KV, dh) -> (B, Sq, H, dh) f32."""
    B, Sq, H, Sk = p.shape
    KV, dh = v.shape[2], v.shape[3]
    G = H // KV
    pg = p.reshape(B, Sq, KV, G, Sk)
    o = jnp.einsum("bqkgs,bskd->bqkgd", pg, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, dh)


def _edge_mask(q_pos, kv_pos, window: int, causal: bool = True):
    """Allowed-edge mask. Shared positions — q_pos (Sq,), kv_pos (Sk,)
    — give an (Sq, Sk) mask; per-row positions — q_pos (B, Sq), kv_pos
    (B, Sk), the speculative-verify path where rows advance by
    different accepted-prefix lengths — give (B, Sq, Sk). kv_pos == -1
    marks an empty cache slot (always masked); the comparisons are
    elementwise either way, so the two ranks agree wherever a per-row
    mask carries the same positions in every row."""
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    m = kp >= 0
    if causal:
        m &= kp <= qp
    if window:
        m &= kp > qp - window
    return m


def attention(q, k, v, *, q_pos, kv_pos, window: int = 0, chunk: int = 0,
              causal: bool = True):
    """Unified GQA attention.

    q: (B, Sq, H, dh); k, v: (B, Sk, KV, dh); q_pos: (Sq,) int32 absolute
    query positions; kv_pos: (Sk,) int32 absolute key positions (-1 empty).
    Per-row positions — q_pos (B, Sq) / kv_pos (B, Sk) — are accepted on
    the plain path only (speculative verify is single-token decode, which
    never takes the flash branch). Returns (B, Sq, H, dh) in q.dtype.
    ``chunk`` selects the blockwise online-softmax path when it tiles Sk.
    """
    Sq, Sk = q.shape[1], k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    if chunk and Sq > 1 and Sk > chunk and Sk % chunk == 0:
        if q_pos.ndim != 1 or kv_pos.ndim != 1:
            raise ValueError("flash path requires shared (1-D) positions")
        return _flash(q, k, v, q_pos=q_pos, kv_pos=kv_pos, window=window,
                      chunk=chunk, scale=scale, causal=causal)
    m = _edge_mask(q_pos, kv_pos, window, causal)  # (Sq, Sk) | (B, Sq, Sk)
    m = m[None, :, None, :] if m.ndim == 2 else m[:, :, None, :]
    s = _gqa_scores(q, k) * scale  # (B, Sq, H, Sk)
    s = jnp.where(m, s, NEG_INF)
    # guard fully-masked rows (empty cache) against NaN
    p = jax.nn.softmax(s, axis=-1)
    o = _gqa_av(p, v)
    return o.astype(q.dtype)


def _flash(q, k, v, *, q_pos, kv_pos, window, chunk, scale, causal=True):
    """Online-softmax scan over KV chunks; never materializes (Sq, Sk)."""
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    n_chunks = Sk // chunk
    kc = jnp.moveaxis(k.reshape(B, n_chunks, chunk, KV, dh), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n_chunks, chunk, KV, dh), 1, 0)
    pc = kv_pos.reshape(n_chunks, chunk)

    def body(carry, inp):
        m_run, l_run, acc = carry
        kb, vb, pos_b = inp
        s = _gqa_scores(q, kb) * scale  # (B, Sq, H, chunk) f32
        msk = _edge_mask(q_pos, pos_b, window, causal)  # (Sq, chunk)
        s = jnp.where(msk[None, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + _gqa_av(p, vb)
        return (m_new, l_new, acc), None

    init = (
        jnp.full((B, Sq, H), NEG_INF, jnp.float32),
        jnp.zeros((B, Sq, H), jnp.float32),
        jnp.zeros((B, Sq, H, dh), jnp.float32),
    )
    (m_run, l_run, acc), _ = jax.lax.scan(body, init, (kc, vc, pc))
    out = acc / jnp.maximum(l_run, 1e-30)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache: dict {k, v, pos, t}
#   k, v: (B, C, KV, dh) where C = max_len (full) or window (ring)
#   pos:  (C,) absolute position held in each slot, -1 if empty
#   t:    () next absolute position to write
# ---------------------------------------------------------------------------


def init_kv_cache(batch, capacity, n_kv, dh, dtype):
    return {
        "k": jnp.zeros((batch, capacity, n_kv, dh), dtype),
        "v": jnp.zeros((batch, capacity, n_kv, dh), dtype),
        "pos": jnp.full((capacity,), -1, jnp.int32),
        "t": jnp.zeros((), jnp.int32),
    }


def kv_cache_shapes(batch, capacity, n_kv, dh, dtype):
    """ShapeDtypeStruct pytree mirroring init_kv_cache (for dry-run)."""
    f = jax.ShapeDtypeStruct
    return {
        "k": f((batch, capacity, n_kv, dh), dtype),
        "v": f((batch, capacity, n_kv, dh), dtype),
        "pos": f((capacity,), jnp.int32),
        "t": f((), jnp.int32),
    }


def cache_prefill(cache, k, v):
    """Write a full prefill of S tokens (positions 0..S-1) into the cache.
    If the cache is a ring (capacity < S), keep the last `capacity` tokens."""
    S = k.shape[1]
    C = cache["k"].shape[1]
    if S <= C:
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, 0, 0, 0))
        pos = jnp.where(jnp.arange(C) < S, jnp.arange(C), -1).astype(jnp.int32)
    else:
        # ring: keep last C tokens; slot = absolute_pos % C
        last_k = k[:, S - C:, :, :]
        last_v = v[:, S - C:, :, :]
        abs_pos = jnp.arange(S - C, S)
        slots = abs_pos % C
        ck = cache["k"].at[:, slots].set(last_k.astype(cache["k"].dtype))
        cv = cache["v"].at[:, slots].set(last_v.astype(cache["v"].dtype))
        pos = jnp.zeros((C,), jnp.int32).at[slots].set(abs_pos)
    return {"k": ck, "v": cv, "pos": pos, "t": jnp.asarray(S, jnp.int32)}


# ---------------------------------------------------------------------------
# Paged KV cache protocol (device primitives)
#
# Instead of one dense (B, C, KV, dh) buffer per micro-batch, K/V live
# in a shared pool of fixed-size pages. A model stores each layer's
# pages as *page rows*: (P1, page * KV * dh), one physical page per row,
# where physical page n_pages is the *trash page*, a write-discard
# target for rows whose computed KV is deliberately dropped (batch
# padding, rows deduplicated against a shared prefix). Each row carries
# a page table (B, C // page) of physical page ids; prefix-sharing rows
# simply map leading logical pages to the same physical pages.
# `pos`/`t` tracking is unchanged from the ring cache: positions are
# logical-slot-indexed and rows advance in lockstep, so the attention
# masking math cannot tell the layouts apart. Allocation/refcounting is
# host-side (`repro.serve.kvcache.PagePool`); these helpers are the
# device half.
# ---------------------------------------------------------------------------


def paged_gather(k_pages, v_pages, table, heads=None, layer=None):
    """Materialise each row's logical KV view through its page table.

    k_pages, v_pages: (P1, page, KV, dh) pages, or (P1, page * KV * dh)
    page rows with ``heads`` = (KV, dh); with ``layer`` given, a layer
    stack (L, P1, page * KV * dh) read at ``layer`` (a scalar, or an
    index array broadcast against ``table``) in the same gather — a
    plane sliced out first, or a layer axis batched over, would copy or
    relay out the pool. table: (B, n) int32 physical page per logical
    page. Returns dense (..., B, n * page, KV, dh) views whose values
    equal the ring cache's for every written slot (unwritten slots
    carry pool garbage — always masked via pos == -1).
    """
    at, lead = table, table.shape
    if layer is not None:
        at, lead = (layer, table), jnp.broadcast_shapes(jnp.shape(layer),
                                                        lead)
    shape = lead[:-1] + (-1,) + tuple(heads or k_pages.shape[-2:])
    return k_pages[at].reshape(shape), v_pages[at].reshape(shape)


def paged_scatter_pages(k_pages, v_pages, scatter_tbl, k, v):
    """Write whole prefill pages into a layer stack of page rows
    (L, P1, page * KV * dh), in place: k, v (L, B, S, KV, dh) with S a
    multiple of the page size; scatter_tbl (B, S // page) physical
    destinations. Rows whose compute is discarded point every entry at
    the trash page (duplicate trash indices are fine — the page is
    never read). One scatter per layer, in a loop: a single scatter of
    every layer's rows compiles several times slower on the TPU."""
    shape = scatter_tbl.shape + k_pages.shape[2:]

    def put(l, pages, x):
        return pages.at[l, scatter_tbl].set(
            x[l].reshape(shape).astype(pages.dtype))

    return jax.lax.fori_loop(
        0, k_pages.shape[0],
        lambda l, kv: (put(l, kv[0], k), put(l, kv[1], v)),
        (k_pages, v_pages))


def paged_write_slots(k_pages, v_pages, idx, k, v):
    """Write single tokens' KV into page rows, in place.

    k_pages, v_pages: (..., P1, page * KV * dh) page rows, optionally
    under leading axes (a layer stack); idx: (N, k_pages.ndim) int32 per
    written token: its leading indices, physical page and slot within
    the page; k, v: (N, KV, dh). Each token is one (KV * dh)-wide window
    of its page's row. Rows may only collide on the trash page, where
    the winning write is irrelevant — the page is never read unmasked."""
    N = idx.shape[0]
    W = k.shape[-2] * k.shape[-1]
    idx = idx.at[:, -1].multiply(W)
    nd = k_pages.ndim
    dn = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=tuple(range(nd - 1)),
        scatter_dims_to_operand_dims=tuple(range(nd)))

    def put(pages, x):
        return jax.lax.scatter(pages, idx,
                               x.reshape(N, W).astype(pages.dtype), dn)

    return put(k_pages, k), put(v_pages, v)


def suffix_attend(q, k_suf, v_suf, pk, pv, *, offset, window=0, chunk=0):
    """Suffix-prefill attention: queries at absolute positions
    ``offset .. offset + Ssuf - 1`` attend over the cached prefix KV
    (absolute positions ``0 .. offset - 1``, typically gathered through a
    page table with :func:`paged_gather`) concatenated with the suffix's
    own freshly-computed KV.

    q, k_suf, v_suf: (B, Ssuf, ·, dh); pk, pv: (B, offset, KV, dh).
    ``offset`` must be a static int (it shapes the position vectors).

    Exactness: causal masking means prefix positions never attend to the
    suffix, so the prefix KV read from the pool is the same tensor a
    monolithic prefill would have computed in place — a greedy decode
    seeded from suffix logits is token-identical to the monolithic path.
    Rows whose prefix table points at the trash page read finite garbage;
    their outputs must be discarded by the caller (batch padding).
    """
    Ssuf = q.shape[1]
    positions = jnp.arange(offset, offset + Ssuf)
    fk = jnp.concatenate([pk.astype(k_suf.dtype), k_suf], axis=1)
    fv = jnp.concatenate([pv.astype(v_suf.dtype), v_suf], axis=1)
    kv_pos = jnp.concatenate([jnp.arange(offset), positions])
    return attention(q, fk, fv, q_pos=positions, kv_pos=kv_pos,
                     window=window, chunk=chunk)
