"""Seeds: a run may be given any whole number up to a little over 2**31
(more than 32 signed bits hold), so keys are built from both halves."""

from __future__ import annotations

import jax
import numpy as np


def jax_key(seed: int, stream: int = 0) -> jax.Array:
    s = int(seed)
    key = jax.random.PRNGKey(np.uint32(s & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((s >> 32) & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(stream))


def np_rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), int(stream)])
