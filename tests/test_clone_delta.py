"""The jnp clone chain's refcount delta, from one scatter of the old tables.

``clone_chain_ref`` computes the clone bookkeeping without a histogram of
the gathered tables: each old entry is weighted by its row's offspring
count less one.  These tests hold it to the two-histogram oracle
``refcount_delta_ref(new, old)`` bit for bit on tables built to hit the
identity's corners, hold a whole filter's store to the composed
``clone(resample_systematic(...))`` path leaf for leaf, and guard the
structure: at the paper's RBPF size the delta is one scatter-add over the
N x mb table entries, not two.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import store as store_lib
from repro.core.config import CopyMode
from repro.kernels.clone_chain import clone_chain
from repro.kernels.clone_chain.ref import clone_chain_ref
from repro.kernels.refcount_update.ref import refcount_delta_ref
from repro.smc import resampling
from repro.smc.filters import FilterConfig, ParticleFilter
from repro.smc.programs import rbpf


def _random_tables(seed, n, mb, nb):
    key = jax.random.PRNGKey(seed)
    return jax.random.randint(key, (n, mb), -1, nb).astype(jnp.int32)


def _random_cdf(seed, n):
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(seed), (n,)))
    cum = jnp.cumsum(w)
    return cum / cum[-1]


def _uniform_cdf(n):
    # With u = 0.5 the comb lands mid-way in each particle's own slot.
    return (jnp.arange(n, dtype=jnp.float32) + 1) / n


def _case_random_nulls():
    n, mb, nb = 33, 5, 40
    return _random_cdf(1, n), jnp.float32(0.37), _random_tables(2, n, mb, nb), nb


def _case_all_null():
    n, mb, nb = 16, 6, 24
    tables = jnp.full((n, mb), -1, jnp.int32)
    return _random_cdf(3, n), jnp.float32(0.81), tables, nb


def _case_identity():
    n, mb, nb = 32, 4, 50
    return _uniform_cdf(n), jnp.float32(0.5), _random_tables(4, n, mb, nb), nb


def _case_one_takes_all():
    n, mb, nb = 24, 3, 30
    cum = jnp.where(jnp.arange(n) >= 5, 1.0, 0.0).astype(jnp.float32)
    return cum, jnp.float32(0.5), _random_tables(5, n, mb, nb), nb


def _case_top_id():
    n, mb, nb = 20, 4, 12
    tables = _random_tables(6, n, mb, nb)
    tables = tables.at[::3, 0].set(nb - 1).at[1::4, 2].set(nb - 1)
    return _random_cdf(7, n), jnp.float32(0.12), tables, nb


def _case_repeated_in_row():
    nb = 10
    tables = jnp.asarray(
        [[3, 3, 3, -1], [7, 3, 7, 7], [9, 9, -1, -1], [0, 1, 0, 1],
         [3, -1, -1, -1], [5, 5, 5, 5], [9, 0, 9, 0], [2, 2, 4, 4]],
        jnp.int32,
    )
    return _random_cdf(8, tables.shape[0]), jnp.float32(0.64), tables, nb


CASES = {
    "random_nulls": _case_random_nulls,
    "all_null": _case_all_null,
    "identity_ancestors": _case_identity,
    "one_ancestor_takes_all": _case_one_takes_all,
    "ids_at_top_block": _case_top_id,
    "repeated_id_in_row": _case_repeated_in_row,
}


def _assert_matches_oracle(tables, anc, new, delta, member, nb):
    np.testing.assert_array_equal(np.asarray(new), np.asarray(tables[anc]))
    d0, m0 = refcount_delta_ref(new.reshape(-1), tables.reshape(-1), nb)
    assert delta.dtype == d0.dtype and member.dtype == m0.dtype
    np.testing.assert_array_equal(np.asarray(delta), np.asarray(d0))
    np.testing.assert_array_equal(np.asarray(member), np.asarray(m0))


@pytest.mark.parametrize("case", sorted(CASES))
def test_delta_and_member_match_two_histograms(case):
    cum, u, tables, nb = CASES[case]()
    anc, new, delta, member = clone_chain_ref(cum, u, tables, nb)
    _assert_matches_oracle(tables, anc, new, delta, member, nb)
    anc = np.asarray(anc)
    if case == "identity_ancestors":
        np.testing.assert_array_equal(anc, np.arange(tables.shape[0]))
        assert not np.asarray(delta).any()
    if case == "one_ancestor_takes_all":
        assert (anc == 5).all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_systematic_comb_matches_two_histograms(seed):
    n, mb, nb = 64, 7, 90
    key = jax.random.PRNGKey(100 + seed)
    logw = 3.0 * jax.random.normal(jax.random.PRNGKey(200 + seed), (n,))
    tables = _random_tables(300 + seed, n, mb, nb)
    anc, new, delta, member = clone_chain(
        key, logw, tables, num_blocks=nb, use_kernel=False
    )
    np.testing.assert_array_equal(
        np.asarray(anc), np.asarray(resampling.resample_systematic(key, logw))
    )
    _assert_matches_oracle(tables, anc, new, delta, member, nb)


# -- a whole filter: fused jnp chain vs composed resample + clone ----------


def _composed_clone_chain(cfg, store, key, logw):
    ancestors = resampling.resample_systematic(key, logw)
    return store_lib.clone(cfg, store, ancestors), ancestors


@pytest.mark.parametrize("mode", [CopyMode.LAZY_SR, CopyMode.LAZY], ids=str)
def test_filter_store_matches_composed_clone(mode, monkeypatch):
    """An RBPF filter (N 64, T 24, systematic, resampling every
    generation) leaves the same store whether each generation's clone is
    the fused jnp chain or the composed ``clone``."""
    ssm, params = rbpf.build()
    fc = FilterConfig(n_particles=64, n_steps=24, mode=mode)
    ys = rbpf.gen_data(jax.random.PRNGKey(1), 24)
    key = jax.random.PRNGKey(2)
    fused = ParticleFilter(ssm, fc).run(key, params, ys)
    monkeypatch.setattr(store_lib, "clone_chain", _composed_clone_chain)
    composed = ParticleFilter(ssm, fc).run(key, params, ys)

    assert bool(np.asarray(fused.resampled)[1:].all())
    a, b = fused.store, composed.store
    for leaf in ("tables", "lengths", "peak_blocks"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, leaf)), np.asarray(getattr(b, leaf)), err_msg=leaf
        )
    for leaf in ("refcount", "frozen", "free_stack", "free_top", "data"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.pool, leaf)),
            np.asarray(getattr(b.pool, leaf)),
            err_msg=leaf,
        )
    assert float(fused.log_evidence) == float(composed.log_evidence)


# -- structure: one scatter-add over the tables at the paper's size --------


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_delta_is_one_scatter_add_over_the_tables():
    n, mb, nb = rbpf.PAPER_N, 125, 43262

    def delta_only(key, logw, tables):
        return clone_chain(key, logw, tables, num_blocks=nb, use_kernel=False)[2]

    closed = jax.make_jaxpr(delta_only)(
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((n, mb), jnp.int32),
    )
    update_sizes = [
        int(np.prod(eqn.invars[2].aval.shape))
        for eqn in _eqns(closed.jaxpr)
        if eqn.primitive.name == "scatter-add"
    ]
    assert update_sizes.count(n * mb) == 1, update_sizes
