"""Pallas TPU kernel: fused WKV6 decode step (RWKV serving hot spot).

One grid cell per (batch row, head): reads the (P x P) wkv state tile,
produces the output token projection and the decayed state update in a
single VMEM pass —

    o[j]   = sum_i r[i] * (S[i,j] + u[i] k[i] v[j])
    S'[i,j] = exp(logw[i]) * S[i,j] + k[i] v[j]

The state (B, H, P, P) is the decode working set (it IS the "KV cache" of
an attention-free model); fusing output + update halves its HBM traffic
per token vs the two-pass jnp formulation. Oracle: repro.models.rwkv6.wkv_step.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mode import resolve_interpret


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s_ref, o_ref, s_out_ref):
    r = r_ref[0, 0].astype(jnp.float32)      # (1, P)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)         # (1, P)
    S = s_ref[0].astype(jnp.float32)         # (P, P)
    kv = k.T @ v                              # (P, P) outer product
    # o[j] = sum_i r[i] * (S[i,j] + u[i]*k[i]*v[j])  ==  r @ S + (r·(u*k)) v
    o_state = r @ S                           # (1, P)
    o_bonus = jnp.sum(r * u * k) * v          # (1, P)
    o_ref[0, 0] = (o_state + o_bonus).astype(o_ref.dtype)
    s_out_ref[0] = (jnp.exp(w).T * S + kv).astype(s_out_ref.dtype)


def wkv_step_pallas(r, k, v, logw, u, state, *,
                    interpret: Optional[bool] = None):
    """r/k/v/logw: (B, H, P); u: (H, P); state: (B, H, P, P) f32.
    Returns (o (B, H, P) f32, new_state (B, H, P, P) f32)."""
    B, H, P = r.shape
    rs = r.reshape(B, H, 1, P)
    ks = k.reshape(B, H, 1, P)
    vs = v.reshape(B, H, 1, P)
    ws = logw.reshape(B, H, 1, P)
    o, s_new = pl.pallas_call(
        _kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, 1, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, P), lambda b, h: (h, 0, 0)),
            pl.BlockSpec((1, P, P), lambda b, h: (b * H + h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, P, P), lambda b, h: (b * H + h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, 1, P), jnp.float32),
            jax.ShapeDtypeStruct((B * H, P, P), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(rs, ks, vs, ws, u.reshape(H, 1, P),
      state.reshape(B * H, P, P).astype(jnp.float32))
    return o.reshape(B, H, P), s_new.reshape(B, H, P, P)
