"""Pallas TPU kernel: fused AE-bank routing score (the paper's hot path).

For every (sample tile, expert k) grid cell, computes the full
encode -> ReLU -> decode -> per-sample MSE chain in VMEM:

    h    = relu(x @ W1_k + b1_k)         (BN folded into W1/b1 by ops.py)
    xhat = h @ W2_k + b2_k
    out[i, k] = mean((xhat - x)^2)

TPU adaptation (vs. launching K tiny GPU kernels): one pallas_call, grid
(B/bm, K); the 784-dim feature axis is zero-padded to 896 = 7*128 for VREG
lane alignment (zero padding is exact for MSE — pad reconstructs pad), and
the per-expert weights (896x128 + 128x896 ~ 900 KB f32) stay resident in
VMEM for the whole sample tile, so reconstructions never touch HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mode import resolve_interpret

LANE = 128


def pad_to_lane(d: int) -> int:
    return ((d + LANE - 1) // LANE) * LANE


def _kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, out_ref, *, d_real: int):
    # f32 matmuls at HIGHEST: Mosaic's default runs them as bf16 passes,
    # which moved scores by 1.6% on a v5e — enough to flip close routes
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    x = x_ref[...]  # (bm, Dp)
    h = jnp.maximum(dot(x, w1_ref[0]) + b1_ref[0], 0.0)  # (bm, H)
    xhat = dot(h, w2_ref[0]) + b2_ref[0]  # (bm, Dp)
    d = xhat - x
    out_ref[0] = jnp.sum(d * d, axis=-1, keepdims=True) / d_real  # (bm, 1)


def expert_score_pallas(x, w1, b1, w2, b2, *, d_real: int, block_m: int = 128,
                        interpret: Optional[bool] = None):
    """x: (B, Dp) f32; w1: (K, Dp, H); b1: (K, H); w2: (K, H, Dp);
    b2: (K, Dp). Returns (B, K) per-sample MSE. Dp must be lane-padded.

    Mosaic tiles the last two dims of every block by (8, 128) unless a
    block spans them whole. So the biases ride as (K, 1, n) and the
    scores come out expert-major as (K, B, 1): each block's trailing
    dims are then either whole or the (bm, Dp)/(bm, H) tiles, and bm
    is a multiple of 8 or all of B."""
    B, Dp = x.shape
    K, _, H = w1.shape
    bm = min(block_m, B)
    assert B % bm == 0, (B, bm)
    grid = (B // bm, K)
    out = pl.pallas_call(
        functools.partial(_kernel, d_real=d_real),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, Dp), lambda i, k: (i, 0)),
            pl.BlockSpec((1, Dp, H), lambda i, k: (k, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda i, k: (k, 0, 0)),
            pl.BlockSpec((1, H, Dp), lambda i, k: (k, 0, 0)),
            pl.BlockSpec((1, 1, Dp), lambda i, k: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, 1), lambda i, k: (k, i, 0)),
        out_shape=jax.ShapeDtypeStruct((K, B, 1), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, w1, b1[:, None, :], w2, b2[:, None, :])
    return out[:, :, 0].T
