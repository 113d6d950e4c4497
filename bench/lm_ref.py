"""Parts of the plain references that every decoder family shares.

A family module (``bench/families/``) writes its own layers from the
published description and builds them from these: float32 matmuls at
``highest`` precision, with both operands rounded to float8 e4m3 (a
per-tensor scale, accumulation in float32) for the control, the step
below the bf16 the configurations state; RMSNorm; rotary embedding on
the two halves of each head (the Hugging Face ``rotate_half`` form); the
vocabulary head over blocks of the vocabulary, so a wide head fits
beside its own bf16 copy; and the teacher-forced check of served tokens
(``served_gaps``), given the family's final-normed hidden states.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = q8(x), q8(w)
    return jnp.matmul(x, w, precision=HI)


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """x: (n, S, heads, dh); rotate the halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("tied", "blocks", "fp8"))
def head_stats(params, h, picks, *, tied: bool, blocks: int, fp8: bool):
    """Over the vocabulary in ``blocks`` blocks of the head (the
    embedding's transpose where ``tied``, else ``params["unembed"]``):
    the largest logit of each row, its index, and the logits at
    ``picks`` (T, k)."""
    w = params["embed"].T if tied else params["unembed"]
    T, V = h.shape[0], w.shape[1]
    bw = V // blocks
    hq = q8(h) if fp8 else h

    def body(carry, b):
        best, arg, got = carry
        wb = jax.lax.dynamic_slice_in_dim(w, b * bw, bw, axis=1)
        wb = wb.astype(jnp.float32)
        lg = jnp.matmul(hq, q8(wb) if fp8 else wb, precision=HI)
        bmax, barg = jnp.max(lg, -1), jnp.argmax(lg, -1) + b * bw
        arg = jnp.where(bmax > best, barg, arg)
        best = jnp.maximum(best, bmax)
        local = picks - b * bw
        inb = (local >= 0) & (local < bw)
        val = jnp.take_along_axis(lg, jnp.clip(local, 0, bw - 1), axis=1)
        got = jnp.where(inb, val, got)
        return (best, arg, got), None

    init = (jnp.full((T,), -jnp.inf), jnp.zeros((T,), jnp.int32),
            jnp.zeros(picks.shape, jnp.float32))
    (best, arg, got), _ = jax.lax.scan(body, init, jnp.arange(blocks))
    return best, arg, got


def vocab_blocks(vocab: int) -> int:
    for b in (16, 8, 4, 2):
        if vocab % b == 0:
            return b
    return 1


def served_gaps(hidden: Callable, params, seqs: np.ndarray, spans, *,
                tied: bool, vocab: int, pad_to: int,
                control: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Teacher-forced check of served tokens.

    ``hidden(tokens, rows, cols, fp8)`` is the family's reference: the
    final-normed hidden states at (rows[i], cols[i]) of a causal pass
    over ``tokens`` (n, S). ``seqs`` (n, S) holds each padded prompt
    followed by its served tokens; ``spans`` lists (row, first position,
    served tokens) with the first served token predicted at that
    position. Returns, per served token, the f32 reference's gap between
    its best logit and the served token's logit, and 1 where the served
    token is the f32 argmax. With ``control`` the gap is that of the
    token the fp8 pass puts first, in place of the served one. The token
    list is padded to ``pad_to`` entries, so one cell always compiles
    the same shapes; only the first entries, one per served token, are
    returned."""
    rows, cols, toks = [], [], []
    for r, p0, served in spans:
        for j, t in enumerate(served):
            rows.append(r)
            cols.append(p0 + j)
            toks.append(int(t))
    n = len(rows)
    if n > pad_to:
        raise ValueError(f"{n} served tokens > pad_to {pad_to}")
    pad = [0] * (pad_to - n)
    rows = np.asarray(rows + pad, np.int32)
    cols = np.asarray(cols + pad, np.int32)
    toks = np.asarray(toks + pad, np.int32)
    nb = vocab_blocks(vocab)
    tok_dev = jnp.asarray(seqs, jnp.int32)
    h = hidden(tok_dev, rows, cols, False)
    if control:
        hq = hidden(tok_dev, rows, cols, True)
        _, toks_q, _ = head_stats(params, hq, jnp.zeros((len(rows), 1),
                                                        jnp.int32),
                                  tied=tied, blocks=nb, fp8=True)
        toks = np.asarray(toks_q, np.int32)
    best, arg, got = head_stats(params, h, jnp.asarray(toks)[:, None],
                                tied=tied, blocks=nb, fp8=False)
    gap = np.asarray(best) - np.asarray(got)[:, 0]
    return gap[:n], (np.asarray(arg) == toks)[:n].astype(np.int32)
