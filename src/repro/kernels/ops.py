"""Jitted public wrappers around the Pallas kernels.

``expert_score(bank_params, x)`` is a drop-in for
``repro.core.autoencoder.bank_scores``: it folds each AE's eval-mode
BatchNorm into the encoder weights, lane-pads 784 -> 896, and calls the
fused kernel. Every wrapper's ``interpret=None`` leaves the mode to the
backend (``repro.kernels.mode``): interpreted on the CPU, Mosaic on a
TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .cosine_topk import cosine_scores_pallas
from .decode_attention import (decode_attention_pallas,
                               paged_decode_attention_pallas)
from .expert_score import expert_score_pallas, pad_to_lane
from .wkv_step import wkv_step_pallas

IN_DIM = 784


def fold_bank(bank_params, bank_states, eps: float = 1e-5):
    """Fold eval-mode BN into (W1, b1); lane-pad the feature dim.

    Returns dict(w1 (K, Dp, H), b1 (K, H), w2 (K, H, Dp), b2 (K, Dp)).
    """
    scale = bank_params["bn_scale"] * jax.lax.rsqrt(
        bank_states["var"] + eps)  # (K, H)
    w1 = bank_params["w_enc"] * scale[:, None, :]
    b1 = (bank_params["b_enc"] - bank_states["mean"]) * scale \
        + bank_params["bn_bias"]
    w2, b2 = bank_params["w_dec"], bank_params["b_dec"]
    K, D, H = w1.shape
    Dp = pad_to_lane(D)
    w1 = jnp.pad(w1, ((0, 0), (0, Dp - D), (0, 0)))
    w2 = jnp.pad(w2, ((0, 0), (0, 0), (0, Dp - D)))
    b2 = jnp.pad(b2, ((0, 0), (0, Dp - D)))
    return {"w1": w1, "b1": b1, "w2": w2, "b2": b2, "d_real": D}


@functools.partial(jax.jit, static_argnames=("interpret", "block_m"))
def expert_score_folded(folded, x, *, interpret: Optional[bool] = None,
                        block_m: int = 128):
    """x: (B, 784) -> (B, K) reconstruction MSE via the fused kernel."""
    B, D = x.shape  # D = real (unpadded) feature dim — static at trace time
    Dp = folded["w1"].shape[1]
    xpad = jnp.pad(x, ((0, 0), (0, Dp - D)))
    bm = min(block_m, B)
    while B % bm:
        bm //= 2
    return expert_score_pallas(xpad, folded["w1"], folded["b1"],
                               folded["w2"], folded["b2"],
                               d_real=D, block_m=max(bm, 1),
                               interpret=interpret)


def expert_score(bank_params, x, bank_states=None, *,
                 interpret: Optional[bool] = None):
    """Convenience entry used by MatcherConfig(use_kernel=True)."""
    if bank_states is None:  # identity BN stats
        K, _, H = bank_params["w_enc"].shape
        bank_states = {"mean": jnp.zeros((K, H)), "var": jnp.ones((K, H)),
                       "count": jnp.zeros((K,))}
    folded = fold_bank(bank_params, bank_states)
    return expert_score_folded(folded, x, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cosine_scores(z, centroids, mask, *, interpret: Optional[bool] = None):
    B = z.shape[0]
    bm = 128
    while B % bm:
        bm //= 2
    return cosine_scores_pallas(z, centroids, mask, block_m=max(bm, 1),
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret",
                                             "block_s"))
def decode_attention(q, k, v, q_pos, kv_pos, *, window: int = 0,
                     block_s: int = 512,
                     interpret: Optional[bool] = None):
    S = k.shape[1]
    bs = min(block_s, S)
    while S % bs:
        bs //= 2
    return decode_attention_pallas(q, k, v, q_pos, kv_pos, window=window,
                                   block_s=max(bs, 1), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, table, q_pos, kv_pos, *,
                           window: int = 0,
                           interpret: Optional[bool] = None):
    """Flash-decode gathering K/V through a per-row page table (the
    paged-KV serving layout). Block size is the page size — the pool's
    physical granularity IS the kernel's VMEM tile."""
    return paged_decode_attention_pallas(q, k_pages, v_pages, table,
                                         q_pos, kv_pos, window=window,
                                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv_decode_step(r, k, v, logw, u, state, *,
                    interpret: Optional[bool] = None):
    """Fused RWKV6 decode step (output + state update in one VMEM pass)."""
    return wkv_step_pallas(r, k, v, logw, u, state, interpret=interpret)
