"""Keys and generators from ``--seed``, which may exceed 32 bits."""
from __future__ import annotations

import jax
import numpy as np


def jax_key(seed: int, *tags: int) -> jax.Array:
    """A JAX key from a non-negative seed of any size, then each tag
    folded in. The same seed and tags give the same key."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(0)
    s = int(seed)
    while True:
        key = jax.random.fold_in(key, np.uint32(s & 0xFFFFFFFF))
        s >>= 32
        if not s:
            break
    for t in tags:
        key = jax.random.fold_in(key, np.uint32(t))
    return key


def np_rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *map(int, tags)])
