"""Pallas TPU kernel: fine-grained assignment scores (paper's FA metric).

cos(z_i, mu_m) for every sample bottleneck z against every class centroid,
fused normalize + matmul in VMEM; invalid (padded) centroids masked to -inf
so downstream argmax is safe. Grid over sample tiles; the centroid matrix
(M x hid, few KB) is broadcast to every grid cell.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mode import resolve_interpret


def _kernel(z_ref, c_ref, mask_ref, out_ref, *, eps: float):
    z = z_ref[...]                      # (bm, h)
    c = c_ref[...]                      # (M, h)
    mask = mask_ref[...]                # (1, M)
    zn = z * jax.lax.rsqrt(jnp.sum(z * z, -1, keepdims=True) + eps)
    cn = c * jax.lax.rsqrt(jnp.sum(c * c, -1, keepdims=True) + eps)
    sim = jnp.dot(zn, cn.T,             # (bm, M); f32, as expert_score
                  precision=jax.lax.Precision.HIGHEST)
    out_ref[...] = jnp.where(mask > 0, sim, -jnp.inf)


def cosine_scores_pallas(z, centroids, mask, *, block_m: int = 128,
                         eps: float = 1e-12,
                         interpret: Optional[bool] = None):
    """z: (B, h); centroids: (M, h); mask: (M,). Returns (B, M) cosine
    similarity with masked classes = -inf."""
    B, h = z.shape
    M = centroids.shape[0]
    bm = min(block_m, B)
    assert B % bm == 0, (B, bm)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(B // bm,),
        in_specs=[
            pl.BlockSpec((bm, h), lambda i: (i, 0)),
            pl.BlockSpec((M, h), lambda i: (0, 0)),
            pl.BlockSpec((1, M), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, M), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, M), z.dtype),
        interpret=resolve_interpret(interpret),
    )(z, centroids, mask[None, :].astype(z.dtype))
