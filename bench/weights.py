"""Expert weights, made on the device from the seed.

The weights are random: speed and agreement with the reference do not
depend on training. They follow the program's parameter layout for a
dense decoder (layers stacked on a leading axis) and the dtypes it
serves (bf16 matrices and biases, f32 norm scales). Every part of the
layer is given values that matter: norm scales spread around 1, and
q/k/v biases that are not zero where the configuration has them, so a
path that dropped one would show in the logits.

``make_expert`` is one jitted call per expert. The reference calls the
same function with the same key, so it sees the same bits without
taking anything from the program.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .flops import Arch


def seed_key(seed: int, *tags: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)
    and integer tags."""
    word = np.random.SeedSequence([int(seed), *map(int, tags)]
                                  ).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def _normal(key, shape, std, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _scale(key, shape):
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def _layer(key, a: Arch) -> Dict:
    d, q, kv, f = a.hidden, a.heads * a.head_dim, \
        a.kv_heads * a.head_dim, a.ffn
    ks = jax.random.split(key, 12)
    p = {
        "ln1": _scale(ks[0], (d,)),
        "ln2": _scale(ks[1], (d,)),
        "wq": _normal(ks[2], (d, q), d ** -0.5),
        "wk": _normal(ks[3], (d, kv), d ** -0.5),
        "wv": _normal(ks[4], (d, kv), d ** -0.5),
        "wo": _normal(ks[5], (q, d), q ** -0.5),
        "mlp": {
            "w_gate": _normal(ks[6], (d, f), d ** -0.5),
            "w_up": _normal(ks[7], (d, f), d ** -0.5),
            "w_down": _normal(ks[8], (f, d), f ** -0.5),
        },
    }
    if a.qkv_bias:
        p["bq"] = _normal(ks[9], (q,), 0.2)
        p["bk"] = _normal(ks[10], (kv,), 0.2)
        p["bv"] = _normal(ks[11], (kv,), 0.2)
    return p


@functools.partial(jax.jit, static_argnames=("a",))
def make_expert(key, a: Arch) -> Dict:
    """One expert's full parameter tree, on the default device."""
    k_emb, k_layers, k_norm, k_head = jax.random.split(key, 4)
    layers = jax.vmap(lambda k: _layer(k, a))(
        jax.random.split(k_layers, a.layers))
    params = {
        "embed": _normal(k_emb, (a.vocab, a.hidden), 0.02),
        "layers": layers,
        "ln_f": _scale(k_norm, (a.hidden,)),
    }
    if not a.tied:
        params["unembed"] = _normal(k_head, (a.hidden, a.vocab),
                                    a.hidden ** -0.5)
    return params


def expert_key(seed: int, expert: int) -> jax.Array:
    return seed_key(seed, 1, expert)
