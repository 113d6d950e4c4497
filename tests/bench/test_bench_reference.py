"""The plain reference against the program's own model, at a test size
in float32, for each variant of the dense layer the configurations use
(q/k/v bias or none, tied or untied head); and the weight maker's
determinism, which the reference relies on to see the served bits, and
its bits themselves."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.families import dense

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _cfg(**kw):
    with open(os.path.join(DATA, "tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(kw)
    return cfg


@pytest.mark.parametrize("bias,tied", [(True, True), (True, False),
                                       (False, True)])
def test_reference_matches_program_model(bias, tied):
    from repro.models import build_model
    cfg = _cfg(attention_bias=bias, tie_word_embeddings=tied,
               torch_dtype="float32")
    a = dense.Arch.from_config(cfg)
    params = dense.make_expert(weights.seed_key(5, 1), a)
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    model = build_model(dense.program_arch(cfg))
    toks = np.random.default_rng(0).integers(0, a.vocab, (2, 24),
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.logits(p32, {"tokens": jnp.asarray(toks)}))
    rows = np.repeat(np.arange(2), 24).astype(np.int32)
    cols = np.tile(np.arange(24), 2).astype(np.int32)
    h = dense.hidden_at(params, jnp.asarray(toks), rows, cols, a=a,
                        eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
                        fp8=False)
    head = params["embed"].T if tied else params["unembed"]
    got = np.asarray(h @ head.astype(jnp.float32)).reshape(want.shape)
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


def test_served_gaps_of_the_reference_argmax_are_zero():
    cfg = _cfg()
    a = dense.Arch.from_config(cfg)
    params = dense.make_expert(weights.seed_key(2, 0), a)
    seqs = np.random.default_rng(1).integers(0, a.vocab, (3, 16),
                                             dtype=np.int32)
    # serve the reference's own greedy choice at positions 3..6 of row 1
    rows = np.ones(4, np.int32)
    cols = np.arange(3, 7, dtype=np.int32)
    h = dense.hidden_at(params, jnp.asarray(seqs), rows, cols, a=a,
                        eps=1e-5, theta=1e4, fp8=False)
    best = np.asarray(jnp.argmax(h @ params["embed"].T.astype(jnp.float32),
                                 -1))
    seqs[1, 4:8] = best
    gap, hit = dense.served_gaps(params, a, cfg, seqs, [(1, 3, best)],
                                 pad_to=8)
    assert gap.shape == (4,) and np.all(hit[:1] == 1)
    assert gap[0] == pytest.approx(0.0, abs=1e-6)


def test_weights_are_a_function_of_the_key():
    a = dense.Arch.from_config(_cfg())
    one = dense.make_expert(weights.expert_key(2**40 + 3, 0), a)
    two = dense.make_expert(weights.expert_key(2**40 + 3, 0), a)
    other = dense.make_expert(weights.expert_key(2**40 + 3, 1), a)
    for x, y, z in zip(jax.tree_util.tree_leaves(one),
                       jax.tree_util.tree_leaves(two),
                       jax.tree_util.tree_leaves(other)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
        assert not np.array_equal(np.asarray(x), np.asarray(z))
    assert one["layers"]["bq"].dtype == jnp.bfloat16
    assert one["layers"]["ln1"].dtype == jnp.float32


def _tree_digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        x = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {x.dtype} {x.shape}".encode())
        h.update(x.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("kw,digest", [
    ({}, "4d18acd76853327db23dc8c883edb4e690d207d56e65ec90270e4c5e5d1c9872"),
    ({"tie_word_embeddings": False, "attention_bias": False},
     "b485c10f1b6fd4d5dd426662551b4f83ea945a8c0d51c3845f9435a0a92fd098"),
], ids=["tied_bias", "untied_no_bias"])
def test_weights_bits_are_pinned(kw, digest):
    """The digests were recorded on the CPU from ``weights.make_expert``
    as it stood before the dense family had a module of its own (every
    leaf's path, dtype, shape and bytes, in tree order), so the weights
    every cell serves are the same bits after the move."""
    a = dense.Arch.from_config(_cfg(**kw))
    assert _tree_digest(dense.make_expert(
        weights.expert_key(2**40 + 3, 0), a)) == digest
