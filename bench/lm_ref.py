"""Plain reference of a dense GQA decoder (Llama / Qwen2 layout).

Written from the published description, in float32 at ``highest``
matmul precision, with no cache, batching trick or kernel: token
embedding; per layer RMSNorm, q/k/v projections (with bias where the
configuration has it), rotary embedding on the two halves of each head
(the Hugging Face ``rotate_half`` form), causal grouped-query attention,
output projection and residual, RMSNorm, SwiGLU feed-forward and
residual; final RMSNorm and the vocabulary head (the embedding itself
where tied). The layers run one at a time in a scan, each upcast from
the served bf16 weights only while it runs, and the head runs over
vocabulary blocks, so a 14B-wide expert fits beside its own bf16 copy.

``fp8=True`` is the control: the same pass with both operands of every
projection, feed-forward and head matmul rounded to float8 e4m3 with a
per-tensor scale, accumulation in float32. It is the step below the
bf16 the configurations state.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .flops import Arch

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x), _q8(w)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (n, S, heads, dh); rotate the halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, a: Arch, eps: float, theta: float, fp8: bool):
    n, S, _ = x.shape
    H, KV, dh = a.heads, a.kv_heads, a.head_dim
    pos = jnp.arange(S)
    h = _rms(x, lp["ln1"], eps)
    q, k, v = (_mm(h, lp[w], fp8) for w in ("wq", "wk", "wv"))
    if a.qkv_bias:
        q = q + lp["bq"].astype(jnp.float32)
        k = k + lp["bk"].astype(jnp.float32)
        v = v + lp["bv"].astype(jnp.float32)
    q = _rope(q.reshape(n, S, H, dh), pos, theta)
    k = _rope(k.reshape(n, S, KV, dh), pos, theta)
    v = v.reshape(n, S, KV, dh)
    g = H // KV
    q = q.reshape(n, S, KV, g, dh)
    s = jnp.einsum("nqkgd,nskd->nkgqs", q, k, precision=HI) / np.sqrt(dh)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nkgqs,nskd->nqkgd", p, v, precision=HI)
    x = x + _mm(o.reshape(n, S, H * dh), lp["wo"], fp8)
    h = _rms(x, lp["ln2"], eps)
    m = lp["mlp"]
    y = jax.nn.silu(_mm(h, m["w_gate"], fp8)) * _mm(h, m["w_up"], fp8)
    return x + _mm(y, m["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("a", "eps", "theta", "fp8"))
def hidden_at(params, tokens, rows, cols, *, a: Arch, eps: float,
              theta: float, fp8: bool):
    """Final-normed hidden states at (rows[i], cols[i]) of a causal pass
    over ``tokens`` (n, S): (T, hidden) float32."""
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, lp):
        return _layer(x, lp, a, eps, theta, fp8), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _rms(x[rows, cols], params["ln_f"], eps)


@functools.partial(jax.jit, static_argnames=("a", "blocks", "fp8"))
def head_stats(params, h, picks, *, a: Arch, blocks: int, fp8: bool):
    """Over the vocabulary in ``blocks`` blocks: the largest logit of
    each row, its index, and the logits at ``picks`` (T, k)."""
    w = params["embed"].T if a.tied else params["unembed"]
    T, V = h.shape[0], w.shape[1]
    bw = V // blocks
    hq = _q8(h) if fp8 else h

    def body(carry, b):
        best, arg, got = carry
        wb = jax.lax.dynamic_slice_in_dim(w, b * bw, bw, axis=1)
        wb = wb.astype(jnp.float32)
        lg = jnp.matmul(hq, _q8(wb) if fp8 else wb, precision=HI)
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


def served_gaps(params, a: Arch, eps: float, theta: float,
                seqs: np.ndarray, spans, *, pad_to: int,
                control: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Teacher-forced check of served tokens.

    ``seqs`` (n, S) holds each padded prompt followed by its served
    tokens; ``spans`` lists (row, first position, served tokens) with
    the first served token predicted at that position. Returns, per
    served token, the f32 reference's gap between its best logit and
    the served token's logit, and 1 where the served token is the f32
    argmax. With ``control`` the gap is that of the token the fp8 pass
    puts first, in place of the served one. The token list is padded to
    ``pad_to`` entries, so one cell always compiles the same shapes;
    only the first entries, one per served token, are returned."""
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
    nb = vocab_blocks(a.vocab)
    tok_dev = jnp.asarray(seqs, jnp.int32)
    h = hidden_at(params, tok_dev, rows, cols, a=a, eps=eps, theta=theta,
                  fp8=False)
    if control:
        hq = hidden_at(params, tok_dev, rows, cols, a=a, eps=eps,
                       theta=theta, fp8=True)
        _, toks_q, _ = head_stats(params, hq, jnp.zeros((len(rows), 1),
                                                        jnp.int32),
                                  a=a, blocks=nb, fp8=True)
        toks = np.asarray(toks_q, np.int32)
    best, arg, got = head_stats(params, h, jnp.asarray(toks)[:, None],
                                a=a, blocks=nb, fp8=False)
    gap = np.asarray(best) - np.asarray(got)[:, 0]
    return gap[:n], (np.asarray(arg) == toks)[:n].astype(np.int32)
