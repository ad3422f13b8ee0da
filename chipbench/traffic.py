"""Seeded streams for a run's inputs (``chipbench/traffic/<name>.json``
names the mix a driver generates from them).

Every input a run makes comes from ``--seed`` through one independent
stream per purpose, so the same seed gives the same bytes.  Seeds of any
size are taken, as whole 32-bit words.
"""

from __future__ import annotations

from typing import List

import numpy as np

SEED_MASK = (1 << 32) - 1


def seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words (numpy's and JAX's keys)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seeds are non-negative")
    words = [seed & SEED_MASK]
    seed >>= 32
    while seed:
        words.append(seed & SEED_MASK)
        seed >>= 32
    return words


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per (seed, purpose)."""
    return np.random.default_rng([stream, *seed_words(seed)])


def jax_key(seed: int, stream: int):
    """An independent JAX PRNG key per (seed, purpose)."""
    import jax

    words = seed_words(seed)
    key = jax.random.PRNGKey(words[0])
    for w in [*words[1:], stream]:
        key = jax.random.fold_in(key, w)
    return key

