"""The routing side's weights and its plain reference.

``train_bank`` makes the autoencoder bank the served matcher routes
with: one 784-128-784 autoencoder with batch norm per dataset, trained
on the server split with the paper's recipe (Adam, lr 1e-2 decayed
x0.1 every 15 epochs, MSE loss), all of them in one jitted scan. The
benchmark hands these weights to the program, so the reference below
reads nothing the program made.

``bank_scores`` is the reference: reconstruction MSE of every sample
under every autoencoder (eval-mode batch norm), in float32 at
``highest`` matmul precision. ``precision="high"`` gives the control,
the same arithmetic in three bf16 passes.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

IN, HID = 784, 128
BN_EPS = 1e-5
BN_MOMENTUM = 0.9


def _init(key):
    k1, k2 = jax.random.split(key)
    params = {
        "w_enc": jax.random.normal(k1, (IN, HID)) / np.sqrt(IN),
        "b_enc": jnp.zeros((HID,)),
        "bn_scale": jnp.ones((HID,)),
        "bn_bias": jnp.zeros((HID,)),
        "w_dec": jax.random.normal(k2, (HID, IN)) / np.sqrt(HID),
        "b_dec": jnp.zeros((IN,)),
    }
    state = {"mean": jnp.zeros((HID,)), "var": jnp.ones((HID,)),
             "count": jnp.zeros(())}
    return params, state


def _recon(params, x, mean, var, precision=None):
    h = jnp.matmul(x, params["w_enc"], precision=precision) \
        + params["b_enc"]
    h = (h - mean) * jax.lax.rsqrt(var + BN_EPS)
    z = jax.nn.relu(h * params["bn_scale"] + params["bn_bias"])
    return jnp.matmul(z, params["w_dec"], precision=precision) \
        + params["b_dec"]


def _loss(params, x):
    h = jnp.matmul(x, params["w_enc"], precision="highest") \
        + params["b_enc"]
    mu, var = jnp.mean(h, 0), jnp.var(h, 0)
    xhat = _recon(params, x, mu, var, "highest")
    return jnp.mean(jnp.square(xhat - x)), (mu, var)


@functools.partial(jax.jit, static_argnames=("steps_per_epoch",))
def _train(keys, data, order, *, steps_per_epoch: int):
    """keys (K,), data (K, N, 784), order (steps, K, batch) row indices.
    Returns stacked (params, state)."""
    lr0, decay_steps = 1e-2, 15 * steps_per_epoch
    b1, b2, eps = 0.9, 0.999, 1e-8

    def one(key, x, idx):
        params, state = _init(key)
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        v = jax.tree_util.tree_map(jnp.zeros_like, params)

        def step(carry, inp):
            params, state, m, v = carry
            t, rows = inp
            (_, (mu, var)), g = jax.value_and_grad(_loss, has_aux=True)(
                params, x[rows])
            lr = lr0 * 0.1 ** jnp.floor(t / decay_steps)
            m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b,
                                       m, g)
            v = jax.tree_util.tree_map(
                lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
            params = jax.tree_util.tree_map(
                lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
                params, m, v)
            state = {
                "mean": BN_MOMENTUM * state["mean"] + (1 - BN_MOMENTUM) * mu,
                "var": BN_MOMENTUM * state["var"] + (1 - BN_MOMENTUM) * var,
                "count": state["count"] + 1}
            return (params, state, m, v), None

        steps = jnp.arange(idx.shape[0], dtype=jnp.float32)
        (params, state, _, _), _ = jax.lax.scan(
            step, (params, state, m, v), (steps, idx))
        return params, state

    return jax.vmap(one, in_axes=(0, 0, 1))(keys, data, order)


def train_bank(xs: Sequence[np.ndarray], seed: int, *, epochs: int = 40,
               batch: int = 64) -> Tuple[Dict, Dict]:
    """One autoencoder per dataset in ``xs`` (equal row counts), trained
    in one call. Returns stacked (params, state) with a leading expert
    axis, as device float32 arrays."""
    n = min(len(x) for x in xs)
    data = np.stack([x[:n] for x in xs]).astype(np.float32)
    spe = n // batch
    rng = np.random.default_rng([seed, 7])
    order = np.stack([
        np.stack([rng.permutation(n)[:spe * batch].reshape(spe, batch)
                  for _ in xs], axis=1)
        for _ in range(epochs)]).reshape(epochs * spe, len(xs), batch)
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), len(xs))
    return _train(keys, jnp.asarray(data), jnp.asarray(order),
                  steps_per_epoch=spe)


def unstack(params, state) -> List[Tuple[Dict, Dict]]:
    """Stacked bank -> [(params, state)] per expert, the form the
    program's ``build_matcher`` takes."""
    k = params["w_enc"].shape[0]
    pick = lambda t, i: jax.tree_util.tree_map(lambda a: a[i], t)
    return [(pick(params, i), pick(state, i)) for i in range(k)]


@functools.partial(jax.jit, static_argnames=("precision",))
def _scores(params, state, x, precision):
    def one(p, s):
        xhat = _recon(p, x, s["mean"], s["var"], precision)
        return jnp.mean(jnp.square(xhat - x), axis=-1)
    return jax.vmap(one)(params, state).T


def bank_scores(params, state, x: np.ndarray,
                precision: str = "highest") -> np.ndarray:
    """(B, K) reconstruction MSE, lower is a better match."""
    return np.asarray(_scores(params, state, jnp.asarray(x, jnp.float32),
                              precision))
