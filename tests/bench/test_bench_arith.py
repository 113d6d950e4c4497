"""End-to-end arithmetic, peaks and operation counts, against numbers
worked out by hand."""
import pytest

from bench import flops, peaks, stats
from bench.families import dense

SMOLLM = dense.Arch(layers=30, hidden=576, heads=9, kv_heads=3, head_dim=64,
                    ffn=1536, vocab=49152, tied=True, qkv_bias=False)
QWEN4 = dense.Arch(layers=4, hidden=5120, heads=40, kv_heads=8, head_dim=128,
                   ffn=13824, vocab=152064, tied=False, qkv_bias=True)


def test_window_with_a_stall():
    # ten requests due every 0.1 s; the server stalls from 0.3 s to
    # 1.3 s, so the four due inside the stall all answer at 1.3 s; the
    # last request is never answered
    due = [0.1 * i for i in range(10)]
    done = [d + 0.05 for d in due]
    for i in (3, 4, 5, 6):
        done[i] = 1.3
    done[9] = None
    lat = stats.latencies(due, done, gave_up=10.9)
    assert lat[3] == pytest.approx(1.0) and lat[6] == pytest.approx(0.7)
    assert lat[9] == pytest.approx(10.0)
    # sorted: 0.05 x5, 0.7, 0.8, 0.9, 1.0, 10.0
    assert stats.percentile(lat, 50) == pytest.approx(0.375)
    assert stats.percentile(lat, 95) == pytest.approx(1.0 + 0.55 * 9.0)
    assert stats.percentile(lat[:9], 95) == pytest.approx(0.96)
    toks = [10] * 10
    # a 1.0 s window: answered by the close are requests 0, 1, 2, 7, 8
    assert stats.tokens_per_s(toks, done, 1.0) == pytest.approx(50.0)
    assert stats.tokens_per_s(toks, done, 2.0) == pytest.approx(45.0)


def test_spread_uses_statistics_quartiles():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["peak_flops_bf16"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]


def test_smollm_bank_by_hand():
    # per layer: q 576x576, k and v 576x192, o 576x576, three 576x1536
    assert dense.layer_matmul_params(SMOLLM) == 3_538_944
    params = 30 * 3_538_944 + 49152 * 576          # 134,475,264
    assert dense.expert_param_bytes(SMOLLM) == \
        2 * params + 30 * 2 * 576 * 4 + 576 * 4
    assert 6 * dense.expert_param_bytes(SMOLLM) == pytest.approx(1.61e9,
                                                                 rel=0.01)
    assert dense.kv_bytes_per_token(SMOLLM) == 23_040


def test_qwen_stage_by_hand():
    assert dense.expert_param_bytes(QWEN4) == pytest.approx(5.32e9,
                                                            rel=0.01)
    streamed = dense.weight_bytes_read(QWEN4, 1)
    assert streamed == pytest.approx(3.76e9, rel=0.01)
    assert dense.head_param_bytes(QWEN4) / streamed == pytest.approx(
        0.41, abs=0.01)
    assert dense.kv_bytes_per_token(QWEN4) == 16_384
    # 4.6 ms per expert per decode step at 819 GB/s
    assert streamed / 819e9 == pytest.approx(4.6e-3, rel=0.01)


def test_step_flops_by_hand():
    lin = 2 * 30 * 3_538_944
    head = 2 * 576 * 49152
    attn1 = 4 * 30 * 9 * 64
    assert dense.decode_flops(SMOLLM, 100) == lin + attn1 * 100 + head
    assert dense.prefill_flops(SMOLLM, 4) == \
        4 * lin + attn1 * (4 * 5 / 2) + head


def test_kernel_costs_by_hand():
    f, b = flops.expert_score_cost(16, 6)
    assert f == 2 * 16 * 6 * 896 * 128 * 2
    assert b == 4 * (16 * 896 + 6 * (2 * 896 * 128 + 128 + 896) + 16 * 6)
    f, b = flops.cosine_scores_cost(16, 10)
    assert f == 2 * 16 * 10 * 128 + 2 * 26 * 128
    assert b == 4 * (16 * 128 + 10 * 128 + 10 + 160)
    f2, b2 = flops.expert_score_cost(32, 6, calls=2)
    assert f2 == 2 * f_expert(16) and b2 == 2 * b_expert(16)


def f_expert(rows):
    return flops.expert_score_cost(rows, 6)[0]


def b_expert(rows):
    return flops.expert_score_cost(rows, 6)[1]


def test_served_means():
    class S:
        def __init__(self, p, sb, n):
            self.prompt_len, self.padded_len, self.tokens = p, sb, n
    pre, keys = flops.served_means(dense, SMOLLM,
                                   [S(30, 32, 3), S(60, 64, 1)])
    assert pre == pytest.approx((dense.prefill_flops(SMOLLM, 30)
                                 + dense.prefill_flops(SMOLLM, 60)) / 2)
    # row 1 decodes 2 tokens seeing 33 and 34 keys; row 2 decodes none
    assert keys == pytest.approx(33.5)
