"""Keys and draws for expert weights made on the device from the seed.

Each family module (``bench/families/``) makes its experts' parameter
trees in the program's layout from these draws, in one jitted call per
expert; the reference calls the same function with the same key, so it
sees the same bits without taking anything from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, *tags: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)
    and integer tags."""
    word = np.random.SeedSequence([int(seed), *map(int, tags)]
                                  ).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def normal(key, shape, std, dtype=jnp.bfloat16):
    """A matrix or bias: normal with ``std``, in the served dtype."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def scale(key, shape):
    """A norm scale in f32, spread around 1."""
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def expert_key(seed: int, expert: int) -> jax.Array:
    return seed_key(seed, 1, expert)
