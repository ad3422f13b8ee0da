"""Seeded inputs: the same seed gives the same bytes, another seed others."""

import numpy as np
import pytest

from chipbench import traffic
from chipbench.reference import rbpf as ref_lib

SEEDS = (0, 7, 2**31 + 12345, 2**40 + 3)


@pytest.mark.parametrize("seed", SEEDS)
def test_observations_same_seed_same_bytes(seed):
    def ys(s):
        return np.asarray(ref_lib.simulate(traffic.jax_key(s, 4), 24)).tobytes()

    assert ys(seed) == ys(seed)
    assert ys(seed) != ys(seed + 1)


def test_streams_are_independent():
    a = traffic.rng(7, 1).integers(0, 1 << 30, size=8)
    b = traffic.rng(7, 2).integers(0, 1 << 30, size=8)
    assert not np.array_equal(a, b)
    assert np.array_equal(a, traffic.rng(7, 1).integers(0, 1 << 30, size=8))


def test_seed_words_cover_large_seeds():
    assert traffic.seed_words(5) == [5]
    assert traffic.seed_words(2**32 + 5) == [5, 1]
    with pytest.raises(ValueError):
        traffic.seed_words(-1)


def test_jax_key_takes_large_seeds():
    a = np.asarray(traffic.jax_key(2**31 + 5, 0))
    b = np.asarray(traffic.jax_key(2**31 + 6, 0))
    assert not np.array_equal(a, b)
    assert np.array_equal(a, np.asarray(traffic.jax_key(2**31 + 5, 0)))
