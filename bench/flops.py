"""Operations and bytes of the work the benchmark drives, from shapes.

Every count here is written from the published equations of the layer,
not read from the program: a dense GQA decoder layer (q, k, v, o
projections, SwiGLU feed-forward), the tied or untied vocabulary head,
the fused AE-bank routing score and the cosine fine-routing score.
Counts are of the work a request needs (causal attention counts only
the key positions a query may see, padding rows count nothing), so a
share of a peak built from them is a share of useful work.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

AE_IN = 784          # matcher fingerprint width
AE_IN_PADDED = 896   # 784 lane-padded to 7 x 128, as the kernel reads it
AE_HID = 128


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


def decode_streamed_bytes(a: Arch) -> int:
    """Weights one decode step of one expert must read: every layer and
    the head. The embedding is a gather of a few rows."""
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


def expert_score_cost(rows: int, experts: int, calls: int = 1) -> tuple:
    """(flops, bytes) of ``calls`` fused AE-bank score calls over
    ``rows`` fingerprints in all: encode and decode matmuls per (row,
    expert); each call reads every expert's folded f32 weights once,
    each row once, and writes one score per (row, expert)."""
    d, h = AE_IN_PADDED, AE_HID
    flops = 2.0 * rows * experts * d * h * 2
    nbytes = 4.0 * (rows * d + calls * experts * (2 * d * h + h + d)
                    + rows * experts)
    return flops, nbytes


def cosine_scores_cost(rows: int, centroids: int, calls: int = 1) -> tuple:
    """(flops, bytes) of ``calls`` cosine fine-score calls over ``rows``
    bottlenecks in all: dot products and both norms; each call reads its
    expert's centroids and mask once."""
    h = AE_HID
    flops = 2.0 * rows * centroids * h + 2.0 * (rows + calls * centroids) * h
    nbytes = 4.0 * (rows * h + calls * (centroids * h + centroids)
                    + rows * centroids)
    return flops, nbytes


def served_means(a: Arch, served) -> tuple:
    """(prefill flops per row, mean keys seen per decoded token) over
    answered requests, each with ``prompt_len``, ``padded_len`` (the
    length bucket the engine prefilled) and ``tokens``. The first token
    of a row comes from its prefill; decoded token j >= 1 attends over
    the padded prompt and the j tokens before it."""
    if not served:
        return 0.0, 0.0
    pre = sum(prefill_flops(a, s.prompt_len) for s in served) / len(served)
    keys = n = 0
    for s in served:
        m = s.tokens - 1
        keys += m * s.padded_len + m * (m + 1) / 2
        n += m
    return pre, (keys / n if n else 0.0)
