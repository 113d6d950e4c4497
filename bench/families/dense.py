"""The dense GQA decoder family (Llama / Qwen2 layout).

Everything the benchmark knows of a dense decoder is here, behind the
interface every family module gives (``bench/spec.py`` finds it by the
configuration's ``"family"``):

- ``Arch``: the sizes, from a configuration file (``Arch.from_config``);
  shared code reads only ``layers``, ``hidden`` and ``vocab``;
- ``program_arch(cfg)``: the program's ``ArchConfig`` for those sizes;
- ``make_expert(key, a)``: one expert's random weights, made on the
  device in one jitted call, in the program's layout and served dtypes;
- ``served_gaps(params, a, cfg, seqs, spans, *, pad_to, control)``: the
  plain f32 reference's check of served tokens, and the fp8 control;
- work counts from shapes: ``expert_param_bytes``, ``weight_bytes_read``,
  ``kv_bytes_per_token``, ``prefill_flops``, ``decode_flops``.

Weights. Random: speed and agreement with the reference do not depend on
training. They follow the program's parameter layout (layers stacked on
a leading axis) and the dtypes it serves (bf16 matrices and biases, f32
norm scales). Every part of the layer is given values that matter: norm
scales spread around 1, and q/k/v biases that are not zero where the
configuration has them, so a path that dropped one would show in the
logits. The reference calls ``make_expert`` with the same key, so it
sees the same bits without taking anything from the program.

Reference. Written from the published description, in float32 at
``highest`` matmul precision, with no cache, batching trick or kernel:
token embedding; per layer RMSNorm, q/k/v projections (with bias where
the configuration has it), rotary embedding on the two halves of each
head (the Hugging Face ``rotate_half`` form), causal grouped-query
attention, output projection and residual, RMSNorm, SwiGLU feed-forward
and residual; final RMSNorm and the vocabulary head (the embedding
itself where tied). The layers run one at a time in a scan, each upcast
from the served bf16 weights only while it runs, and the head runs over
vocabulary blocks (``bench.lm_ref``), so a 14B-wide expert fits beside
its own bf16 copy. ``fp8=True`` is the control: the same pass with both
operands of every projection, feed-forward and head matmul rounded to
float8 e4m3 with a per-tensor scale, accumulation in float32; the step
below the bf16 the configurations state.

Counts are written from the same published equations, not read from the
program: q, k, v, o projections, SwiGLU feed-forward, the tied or untied
head. They count the work a request needs (causal attention counts only
the key positions a query may see), so a share of a peak built from
them is a share of useful work.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from bench import lm_ref, weights


@dataclasses.dataclass(frozen=True)
class Arch:
    """Sizes of a dense decoder, named as in a Hugging Face config."""
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    tied: bool
    qkv_bias: bool
    param_bytes: int = 2        # bf16 weights
    kv_bytes: int = 2           # bf16 cache

    @classmethod
    def from_config(cls, c: Mapping) -> "Arch":
        return cls(layers=int(c["num_hidden_layers"]),
                   hidden=int(c["hidden_size"]),
                   heads=int(c["num_attention_heads"]),
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c["head_dim"]),
                   ffn=int(c["intermediate_size"]),
                   vocab=int(c["vocab_size"]),
                   tied=bool(c["tie_word_embeddings"]),
                   qkv_bias=bool(c["attention_bias"]))


def program_arch(cfg: Dict[str, Any]):
    """The program's config for a configuration file's sizes."""
    from repro.models.common import ArchConfig
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]),
        qkv_bias=bool(cfg["attention_bias"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


# --- weights -----------------------------------------------------------

def _layer_weights(key, a: Arch) -> Dict:
    d, q, kv, f = a.hidden, a.heads * a.head_dim, \
        a.kv_heads * a.head_dim, a.ffn
    ks = jax.random.split(key, 12)
    p = {
        "ln1": weights.scale(ks[0], (d,)),
        "ln2": weights.scale(ks[1], (d,)),
        "wq": weights.normal(ks[2], (d, q), d ** -0.5),
        "wk": weights.normal(ks[3], (d, kv), d ** -0.5),
        "wv": weights.normal(ks[4], (d, kv), d ** -0.5),
        "wo": weights.normal(ks[5], (q, d), q ** -0.5),
        "mlp": {
            "w_gate": weights.normal(ks[6], (d, f), d ** -0.5),
            "w_up": weights.normal(ks[7], (d, f), d ** -0.5),
            "w_down": weights.normal(ks[8], (f, d), f ** -0.5),
        },
    }
    if a.qkv_bias:
        p["bq"] = weights.normal(ks[9], (q,), 0.2)
        p["bk"] = weights.normal(ks[10], (kv,), 0.2)
        p["bv"] = weights.normal(ks[11], (kv,), 0.2)
    return p


@functools.partial(jax.jit, static_argnames=("a",))
def make_expert(key, a: Arch) -> Dict:
    """One expert's full parameter tree, on the default device."""
    k_emb, k_layers, k_norm, k_head = jax.random.split(key, 4)
    layers = jax.vmap(lambda k: _layer_weights(k, a))(
        jax.random.split(k_layers, a.layers))
    params = {
        "embed": weights.normal(k_emb, (a.vocab, a.hidden), 0.02),
        "layers": layers,
        "ln_f": weights.scale(k_norm, (a.hidden,)),
    }
    if not a.tied:
        params["unembed"] = weights.normal(k_head, (a.hidden, a.vocab),
                                           a.hidden ** -0.5)
    return params


# --- reference -----------------------------------------------------------

def _layer(x, lp, a: Arch, eps: float, theta: float, fp8: bool):
    n, S, _ = x.shape
    H, KV, dh = a.heads, a.kv_heads, a.head_dim
    pos = jnp.arange(S)
    h = lm_ref.rms(x, lp["ln1"], eps)
    q, k, v = (lm_ref.mm(h, lp[w], fp8) for w in ("wq", "wk", "wv"))
    if a.qkv_bias:
        q = q + lp["bq"].astype(jnp.float32)
        k = k + lp["bk"].astype(jnp.float32)
        v = v + lp["bv"].astype(jnp.float32)
    q = lm_ref.rope(q.reshape(n, S, H, dh), pos, theta)
    k = lm_ref.rope(k.reshape(n, S, KV, dh), pos, theta)
    v = v.reshape(n, S, KV, dh)
    g = H // KV
    q = q.reshape(n, S, KV, g, dh)
    s = jnp.einsum("nqkgd,nskd->nkgqs", q, k,
                   precision=lm_ref.HI) / np.sqrt(dh)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nkgqs,nskd->nqkgd", p, v, precision=lm_ref.HI)
    x = x + lm_ref.mm(o.reshape(n, S, H * dh), lp["wo"], fp8)
    h = lm_ref.rms(x, lp["ln2"], eps)
    m = lp["mlp"]
    y = jax.nn.silu(lm_ref.mm(h, m["w_gate"], fp8)) * \
        lm_ref.mm(h, m["w_up"], fp8)
    return x + lm_ref.mm(y, m["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("a", "eps", "theta", "fp8"))
def hidden_at(params, tokens, rows, cols, *, a: Arch, eps: float,
              theta: float, fp8: bool):
    """Final-normed hidden states at (rows[i], cols[i]) of a causal pass
    over ``tokens`` (n, S): (T, hidden) float32."""
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, lp):
        return _layer(x, lp, a, eps, theta, fp8), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return lm_ref.rms(x[rows, cols], params["ln_f"], eps)


def served_gaps(params, a: Arch, cfg: Mapping, seqs: np.ndarray, spans,
                *, pad_to: int, control: bool = False):
    """``bench.lm_ref.served_gaps`` through this family's reference, with
    the norm epsilon and rotary base the configuration states."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])

    def hidden(tokens, rows, cols, fp8):
        return hidden_at(params, tokens, rows, cols, a=a, eps=eps,
                         theta=theta, fp8=fp8)

    return lm_ref.served_gaps(hidden, params, seqs, spans, tied=a.tied,
                              vocab=a.vocab, pad_to=pad_to, control=control)


# --- work counts ---------------------------------------------------------

def layer_matmul_params(a: Arch) -> int:
    """Weights one token multiplies through in one layer."""
    d, q, kv = a.hidden, a.heads * a.head_dim, a.kv_heads * a.head_dim
    return d * q + 2 * d * kv + q * d + 3 * d * a.ffn


def layer_param_bytes(a: Arch) -> int:
    """Bytes of one layer's weights: bf16 matrices and biases, the two
    RMSNorm scales in f32."""
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    bias = (q + 2 * kv) if a.qkv_bias else 0
    return (layer_matmul_params(a) + bias) * a.param_bytes + 2 * a.hidden * 4


def head_param_bytes(a: Arch) -> int:
    return a.hidden * a.vocab * a.param_bytes


def expert_param_bytes(a: Arch) -> int:
    """All of one expert's weights: layers, embedding, head, final norm."""
    tables = 1 if a.tied else 2
    return (a.layers * layer_param_bytes(a)
            + tables * a.hidden * a.vocab * a.param_bytes + a.hidden * 4)


def weight_bytes_read(a: Arch, tokens: float) -> int:
    """Weights one dispatch of one expert over ``tokens`` tokens must
    read: every layer and the head, whatever the tokens. The embedding
    is a gather of a few rows."""
    return a.layers * layer_param_bytes(a) + head_param_bytes(a)


def kv_bytes_per_token(a: Arch) -> int:
    return 2 * a.layers * a.kv_heads * a.head_dim * a.kv_bytes


def attention_flops(a: Arch, q_positions: int, keys_seen: float) -> float:
    """QK^T and AV over ``keys_seen`` keys for each query position, all
    layers."""
    return 4.0 * a.layers * a.heads * a.head_dim * q_positions * keys_seen


def prefill_flops(a: Arch, prompt: int) -> float:
    """One row's prefill of ``prompt`` tokens: every layer at every
    position (causal attention), and the head at the last position."""
    lin = 2.0 * a.layers * layer_matmul_params(a) * prompt
    attn = attention_flops(a, 1, 1) * prompt * (prompt + 1) / 2
    return lin + attn + 2.0 * a.hidden * a.vocab


def decode_flops(a: Arch, context: float) -> float:
    """One decoded token whose query sees ``context`` cached positions."""
    return (2.0 * a.layers * layer_matmul_params(a)
            + attention_flops(a, 1, context) + 2.0 * a.hidden * a.vocab)
