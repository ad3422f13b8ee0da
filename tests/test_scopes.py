"""The filter step and the store name their layers with ``jax.named_scope``.

XLA copies a scope path into the ``op_name`` metadata of every instruction
it lowers to, fusions included, which is how a device trace's time is
charged to a layer.  These tests compile a small RBPF filter (N 64, T 24,
LAZY_SR, systematic resampling, the default store) and read the optimized
HLO's metadata: every scope is there, nested where the code nests it, and
every scatter-add of the scan body, the passes over the pool, lies under
the scope that owns it.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.config import CopyMode
from repro.smc.filters import FilterConfig, ParticleFilter
from repro.smc.programs import rbpf

SCOPES = (
    "filter.resample",
    "filter.propagate",
    "store.refcount",
    "store.append",
    "store.count",
    "pool.alloc",
    "pool.free_push",
)
SCAN_BODY = "/while/body/"


@pytest.fixture(scope="module")
def paths():
    """The declared-scope path of every instruction in the scan body, with
    the JAX primitive it came from: ``[(scopes, primitive)]``."""
    ssm, params = rbpf.build()
    pf = ParticleFilter(
        ssm, FilterConfig(n_particles=64, n_steps=24, mode=CopyMode.LAZY_SR)
    )
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    ys = jax.ShapeDtypeStruct((24,), jnp.float32)
    text = jax.jit(pf.run).lower(key, params, ys).compile().as_text()
    out = []
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        if SCAN_BODY in op_name:
            parts = op_name.split("/")
            out.append((tuple(p for p in parts if p in SCOPES), parts[-1]))
    return out


def _enclosing(paths, scope):
    """The scope directly around each occurrence of ``scope``."""
    return {
        p[p.index(scope) - 1] if p.index(scope) else None
        for p, _ in paths
        if scope in p
    }


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_is_in_the_metadata(paths, scope):
    assert any(scope in p for p, _ in paths)


def test_refcount_only_under_resample(paths):
    assert _enclosing(paths, "store.refcount") == {"filter.resample"}


def test_free_push_under_resample_and_the_copy_away_release(paths):
    # The clone pushes the blocks its histogram freed; an append pushes
    # the blocks its copy-on-write released (store.sub_refs).
    assert _enclosing(paths, "pool.free_push") == {"filter.resample", "store.append"}


def test_alloc_only_under_append(paths):
    assert _enclosing(paths, "pool.alloc") == {"store.append"}


def test_every_scan_scatter_add_is_scoped(paths):
    adds = [p for p, prim in paths if prim == "scatter-add"]
    assert adds
    assert all(p for p in adds), adds


@pytest.mark.parametrize(
    "owner",
    [
        ("filter.resample", "store.refcount"),  # the refcount histogram
        ("store.append", "pool.alloc"),  # alloc's refcount bump
        ("store.append",),  # the copy-away release (sub_refs)
    ],
    ids=lambda o: o[-1],
)
def test_scatter_add_owner(paths, owner):
    assert owner in {p for p, prim in paths if prim == "scatter-add"}
