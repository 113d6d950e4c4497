"""Operations and bytes of the work the benchmark drives, from shapes,
where they hold for any model family.

Every count here is written from the published equations of the layer,
not read from the program: the fused AE-bank routing score and the
cosine fine-routing score. The model's own counts are its family's
(``bench/families/``): ``expert_param_bytes``, ``weight_bytes_read``,
``kv_bytes_per_token``, ``prefill_flops`` and ``decode_flops``. Counts
are of the work a request needs (causal attention counts only the key
positions a query may see, padding rows count nothing), so a share of a
peak built from them is a share of useful work.
"""
from __future__ import annotations

AE_IN = 784          # matcher fingerprint width
AE_IN_PADDED = 896   # 784 lane-padded to 7 x 128, as the kernel reads it
AE_HID = 128


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


def served_means(family, a, served) -> tuple:
    """(prefill flops per row, mean keys seen per decoded token) over
    answered requests, each with ``prompt_len``, ``padded_len`` (the
    length bucket the engine prefilled) and ``tokens``, for sizes ``a``
    of ``family``. The first token of a row comes from its prefill;
    decoded token j >= 1 attends over the padded prompt and the j tokens
    before it."""
    if not served:
        return 0.0, 0.0
    pre = sum(family.prefill_flops(a, s.prompt_len)
              for s in served) / len(served)
    keys = n = 0
    for s in served:
        m = s.tokens - 1
        keys += m * s.padded_len + m * (m + 1) / 2
        n += m
    return pre, (keys / n if n else 0.0)
