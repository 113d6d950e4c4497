"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(the CPU backend runs every kernel body in the Pallas interpreter)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.expert_score import pad_to_lane


def _bank(K, D, H, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    params = {
        "w_enc": jax.random.normal(ks[0], (K, D, H)) * 0.03,
        "b_enc": jax.random.normal(ks[1], (K, H)) * 0.01,
        "bn_scale": 1.0 + jax.random.normal(ks[2], (K, H)) * 0.1,
        "bn_bias": jax.random.normal(ks[3], (K, H)) * 0.05,
        "w_dec": jax.random.normal(ks[4], (K, H, D)) * 0.03,
        "b_dec": jax.random.normal(ks[5], (K, D)) * 0.01,
    }
    states = {"mean": jax.random.normal(ks[6], (K, H)) * 0.1,
              "var": 1.0 + jax.random.uniform(ks[7], (K, H)),
              "count": jnp.ones((K,))}
    return params, states


# interpret-mode sizes are capped for tier-1 runtime: the multi-tile
# grid case (B=256 > block_m) uses the small-D bank, not the 784-dim one
@pytest.mark.parametrize("B,D,H,K", [
    (32, 784, 128, 6), (128, 512, 64, 10),
    (16, 100, 32, 3), (256, 100, 32, 3),
])
def test_expert_score_shapes(B, D, H, K):
    params, states = _bank(K, D, H, seed=B + K)
    x = jax.random.uniform(jax.random.PRNGKey(B), (B, D))
    got = np.asarray(ops.expert_score(params, x, states))
    folded = ops.fold_bank(params, states)
    Dp = pad_to_lane(D)
    xp = jnp.pad(x, ((0, 0), (0, Dp - D)))
    want = np.asarray(ref.expert_score_ref(
        xp, folded["w1"], folded["b1"], folded["w2"], folded["b2"],
        d_real=D))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


def test_expert_score_matches_ae_bank_math():
    """Kernel == the actual matcher scoring path (BN folding is exact)."""
    from repro.core.autoencoder import bank_scores
    params, states = _bank(5, 784, 128)
    x = jax.random.uniform(jax.random.PRNGKey(7), (64, 784))
    got = np.asarray(ops.expert_score(params, x, states))
    want = np.asarray(bank_scores(params, states, x))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("B,M,h", [(32, 10, 128), (64, 3, 64), (16, 17, 32)])
def test_cosine_scores(B, M, h):
    z = jax.random.normal(jax.random.PRNGKey(B), (B, h))
    c = jax.random.normal(jax.random.PRNGKey(M), (M, h))
    mask = (jnp.arange(M) < max(M - 2, 1)).astype(jnp.float32)
    got = np.asarray(ops.cosine_scores(z, c, mask))
    want = np.asarray(ref.cosine_scores_ref(z, c, mask))
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-5, atol=1e-6)
    assert (np.isinf(got) == np.isinf(want)).all()


@pytest.mark.parametrize("B,H,KV,dh,S,win,dtype", [
    (4, 8, 2, 64, 1024, 0, jnp.float32),
    (2, 4, 4, 64, 512, 0, jnp.float32),
    (4, 8, 2, 64, 1024, 256, jnp.float32),
    (1, 16, 2, 128, 1024, 0, jnp.float32),
    (2, 8, 2, 64, 512, 0, jnp.bfloat16),
])
def test_decode_attention(B, H, KV, dh, S, win, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 3)
    q = jax.random.normal(ks[0], (B, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, dh), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, dh), dtype)
    t = S - S // 3
    q_pos = jnp.asarray(t, jnp.int32)
    kv_pos = jnp.where(jnp.arange(S) <= t, jnp.arange(S), -1).astype(jnp.int32)
    got = np.asarray(ops.decode_attention(q, k, v, q_pos, kv_pos,
                                          window=win, block_s=256),
                     np.float32)
    want = np.asarray(ref.decode_attention_ref(q, k, v, q_pos, kv_pos,
                                               window=win), np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_decode_attention_ring_cache_semantics():
    """Scrambled (ring) slot order must not change the result."""
    B, H, KV, dh, S = 2, 4, 2, 32, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, dh))
    k = jax.random.normal(ks[1], (B, S, KV, dh))
    v = jax.random.normal(ks[2], (B, S, KV, dh))
    q_pos = jnp.asarray(300, jnp.int32)
    kv_pos = jnp.arange(S) + 300 - S + 1  # ring holding last S positions
    perm = np.random.default_rng(0).permutation(S)
    got1 = np.asarray(ops.decode_attention(q, k, v, q_pos,
                                           kv_pos.astype(jnp.int32),
                                           window=128, block_s=64))
    got2 = np.asarray(ops.decode_attention(
        q, k[:, perm], v[:, perm], q_pos,
        kv_pos[perm].astype(jnp.int32), window=128, block_s=64))
    np.testing.assert_allclose(got1, got2, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("B,H,KV,dh,page,nlp,win,dtype", [
    (3, 8, 2, 64, 8, 8, 0, jnp.float32),
    (2, 4, 4, 64, 16, 4, 0, jnp.float32),
    (3, 8, 2, 64, 8, 8, 24, jnp.float32),    # sliding window
    (1, 16, 2, 128, 8, 4, 0, jnp.float32),
    (2, 8, 2, 64, 8, 8, 0, jnp.bfloat16),
])
def test_paged_decode_attention_parity(B, H, KV, dh, page, nlp, win,
                                       dtype):
    """Paged kernel == ring kernel on the gathered dense view == jnp
    reference, through a scrambled page table with shared pages between
    rows and trash-backed (never-written) logical tail pages — the
    kernel body the serving path would run, here in the interpreter."""
    from repro.kernels.decode_attention import paged_decode_attention_pallas
    from repro.models.attention import paged_gather
    C = nlp * page
    P1 = 3 * B * nlp + 1                       # pool + trash page
    ks = jax.random.split(jax.random.PRNGKey(C + H), 3)
    kp = jax.random.normal(ks[0], (P1, page, KV, dh), dtype)
    vp = jax.random.normal(ks[1], (P1, page, KV, dh), dtype)
    q = jax.random.normal(ks[2], (B, H, dh), dtype)
    t = C - C // 3                             # last pages unwritten
    n_valid = -(-t // page)
    rng = np.random.default_rng(0)
    perm = rng.permutation(P1 - 1)             # scrambled physical order
    tbl = np.full((B, nlp), P1 - 1, np.int32)  # tail -> trash
    for b in range(B):
        tbl[b, :n_valid] = perm[b * nlp:b * nlp + n_valid]
    tbl[1:, 0] = tbl[0, 0]                     # rows share a prefix page
    q_pos = jnp.asarray(t - 1, jnp.int32)
    kv_pos = jnp.where(jnp.arange(C) < t, jnp.arange(C), -1).astype(
        jnp.int32)
    tblj = jnp.asarray(tbl)
    got = np.asarray(paged_decode_attention_pallas(
        q, kp, vp, tblj, q_pos, kv_pos, window=win), np.float32)
    want = np.asarray(ref.paged_decode_attention_ref(
        q, kp, vp, tblj, q_pos, kv_pos, window=win), np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    # triangulate against the ring kernel on the gathered dense view
    kd, vd = paged_gather(kp, vp, tblj)
    ring = np.asarray(ops.decode_attention(q, kd, vd, q_pos, kv_pos,
                                           window=win, block_s=page),
                      np.float32)
    np.testing.assert_allclose(got, ring, rtol=tol, atol=tol)


def test_paged_decode_attention_page_table_remap_invariance():
    """Remapping rows to different physical pages with identical
    contents must not change the output (storage layout is invisible
    to the attention math)."""
    B, H, KV, dh, page, nlp = 2, 4, 2, 32, 8, 4
    C = nlp * page
    P1 = 2 * B * nlp + 1
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    kp = jax.random.normal(ks[0], (P1, page, KV, dh))
    vp = jax.random.normal(ks[1], (P1, page, KV, dh))
    q = jax.random.normal(ks[2], (B, H, dh))
    tbl1 = np.arange(B * nlp, dtype=np.int32).reshape(B, nlp)
    # duplicate contents into a disjoint region, remap row 1 there
    kp = kp.at[B * nlp:2 * B * nlp].set(kp[:B * nlp])
    vp = vp.at[B * nlp:2 * B * nlp].set(vp[:B * nlp])
    tbl2 = tbl1.copy()
    tbl2[1] += B * nlp
    q_pos = jnp.asarray(C - 1, jnp.int32)
    kv_pos = jnp.arange(C, dtype=jnp.int32)
    a = np.asarray(ops.paged_decode_attention(
        q, kp, vp, jnp.asarray(tbl1), q_pos, kv_pos))
    b = np.asarray(ops.paged_decode_attention(
        q, kp, vp, jnp.asarray(tbl2), q_pos, kv_pos))
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("B,H,P", [(2, 4, 32), (1, 8, 64), (4, 2, 16)])
def test_wkv_decode_step(B, H, P):
    from repro.kernels.wkv_step import wkv_step_pallas
    from repro.models.rwkv6 import wkv_step as wkv_oracle
    ks = jax.random.split(jax.random.PRNGKey(B * P), 6)
    r = jax.random.normal(ks[0], (B, H, P))
    k = jax.random.normal(ks[1], (B, H, P))
    v = jax.random.normal(ks[2], (B, H, P))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, P)) * 0.5)
    u = jax.random.normal(ks[4], (H, P)) * 0.2
    S = jax.random.normal(ks[5], (B, H, P, P))
    o_k, S_k = wkv_step_pallas(r, k, v, logw, u, S)
    S_ref, o_ref = wkv_oracle(S, r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(S_k), np.asarray(S_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("B,H", [(1, 2), (3, 4), (2, 8)])
def test_wkv_step_parity_grid(B, H, dtype):
    """Interpret-mode kernel vs oracle over the (batch, head, dtype)
    grid the serving path actually exercises: both sides upcast to f32
    in-kernel, so bf16 activations must agree to f32-rounding level,
    not just bf16 precision — a regression here means the kernel
    dropped its internal upcast."""
    from repro.kernels.wkv_step import wkv_step_pallas
    from repro.models.rwkv6 import wkv_step as wkv_oracle
    P = 32
    ks = jax.random.split(jax.random.PRNGKey(B * 100 + H), 6)
    r = jax.random.normal(ks[0], (B, H, P)).astype(dtype)
    k = jax.random.normal(ks[1], (B, H, P)).astype(dtype)
    v = jax.random.normal(ks[2], (B, H, P)).astype(dtype)
    logw = (-jnp.exp(jax.random.normal(ks[3], (B, H, P)) * 0.5)
            ).astype(dtype)
    u = (jax.random.normal(ks[4], (H, P)) * 0.2).astype(dtype)
    S = jax.random.normal(ks[5], (B, H, P, P))   # state stays f32
    o_k, S_k = wkv_step_pallas(r, k, v, logw, u, S)
    assert o_k.dtype == jnp.float32 and S_k.dtype == jnp.float32
    S_ref, o_ref = wkv_oracle(S, r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref),
                               rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(S_k), np.asarray(S_ref),
                               rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_wkv_step_chain_matches_scan(dtype):
    """T chained kernel decode steps reproduce wkv_scan's outputs and
    final state — the decode loop is the scan, one token at a time."""
    from repro.kernels.wkv_step import wkv_step_pallas
    from repro.models.rwkv6 import wkv_scan
    B, T, H, P = 2, 5, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    r = jax.random.normal(ks[0], (B, T, H, P)).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, H, P)).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, H, P)).astype(dtype)
    logw = (-jnp.exp(jax.random.normal(ks[3], (B, T, H, P)) * 0.5)
            ).astype(dtype)
    u = jnp.zeros((H, P), dtype) + 0.1
    o_scan, S_scan = wkv_scan(r, k, v, logw, u)
    S = jnp.zeros((B, H, P, P), jnp.float32)
    outs = []
    for t in range(T):
        o, S = wkv_step_pallas(r[:, t], k[:, t], v[:, t],
                               logw[:, t], u, S)
        outs.append(o)
    o_chain = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(o_chain), np.asarray(o_scan),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_scan),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend,want", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_kernel_mode_follows_the_backend(monkeypatch, backend, want):
    """One place picks a kernel's mode: the interpreter on the CPU,
    Mosaic on a TPU, an error anywhere else; an explicit mode wins."""
    from repro.kernels.mode import resolve_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="no Pallas kernel path"):
            resolve_interpret(None)
    else:
        assert resolve_interpret(None) is want
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
