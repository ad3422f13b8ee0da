"""Hypothesis property tests for the COW-paged KV cache.

A random program of {append-to-subset, fork, free} operations runs
against both the paged cache and a dense per-sequence reference; after
every operation the observable KV contents must match, and the platform
invariants must hold:

  * refcounts equal the number of table references to each block,
  * no two *writable* (refcount-1 tail) blocks are shared,
  * live blocks never exceed the dense equivalent,
  * freeing is complete (no leaked blocks).

This is the serving-layer analogue of the paper's eager/lazy output
equality check.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis (dev extra)")
from hypothesis import given, settings, strategies as st

from repro.serving import kv_cache as kvc
from repro.serving.kv_cache import KVCacheConfig

N_SEQS = 4
L, KVH, HD, BS, MAXB = 2, 2, 4, 4, 6
CFG = KVCacheConfig(
    n_layers=L, n_kv_heads=KVH, head_dim=HD, block_size=BS,
    max_seqs=N_SEQS, max_blocks_per_seq=MAXB, num_blocks=N_SEQS * MAXB,
)


@st.composite
def cache_programs(draw):
    ops = []
    for _ in range(draw(st.integers(3, 25))):
        kind = draw(st.sampled_from(["append", "append", "fork", "free"]))
        if kind == "append":
            ops.append(("append",
                        tuple(draw(st.booleans()) for _ in range(N_SEQS)),
                        draw(st.integers(0, 999))))
        elif kind == "fork":
            ops.append(("fork",
                        tuple(draw(st.integers(0, N_SEQS - 1)) for _ in range(N_SEQS))))
        else:
            ops.append(("free", tuple(draw(st.booleans()) for _ in range(N_SEQS))))
    return ops


def run_program(ops):
    cache = kvc.create(CFG)
    # dense reference: [N, T, KVH, HD] per layer via numpy
    dense = np.zeros((N_SEQS, BS * MAXB, L, 2, KVH, HD), np.float32)
    lengths = np.zeros(N_SEQS, np.int64)

    for step, op in enumerate(ops):
        if op[0] == "append":
            mask = np.array(op[1])
            mask &= lengths < BS * MAXB
            jmask = jnp.asarray(mask)
            cache, bid, pos = kvc.ensure_writable(CFG, cache, jmask)
            for layer in range(L):
                val = np.fromfunction(
                    lambda s, h, d: op[2] + s * 100 + layer * 10 + h + d,
                    (N_SEQS, KVH, HD),
                ).astype(np.float32)
                cache = kvc.write_kv(
                    CFG, cache, bid, pos, layer,
                    jnp.asarray(val), jnp.asarray(val + 0.5), jmask,
                )
                for s in range(N_SEQS):
                    if mask[s]:
                        dense[s, lengths[s], layer, 0] = val[s]
                        dense[s, lengths[s], layer, 1] = val[s] + 0.5
            cache = kvc.advance(cache, jmask)
            lengths += mask
        elif op[0] == "fork":
            anc = np.array(op[1])
            cache = kvc.fork(cache, jnp.asarray(anc))
            dense = dense[anc].copy()
            lengths = lengths[anc].copy()
        else:
            mask = np.array(op[1])
            cache = kvc.free(cache, jnp.asarray(mask))
            dense[mask] = 0
            lengths[mask] = 0

        check_equiv(cache, dense, lengths)
        check_invariants(cache, lengths)
    return cache, lengths


def check_equiv(cache, dense, lengths):
    tables = np.asarray(cache.tables)
    data = np.asarray(cache.pool.data)  # [nb, L, 2, BS, KVH * HD]
    data = data.reshape(data.shape[:4] + (KVH, HD))
    for s in range(N_SEQS):
        for t in range(int(lengths[s])):
            blk = tables[s, t // BS]
            assert blk >= 0
            got_k = data[blk, :, 0, t % BS]  # [L, KVH, HD]
            np.testing.assert_allclose(got_k, dense[s, t, :, 0], atol=0,
                                       err_msg=f"seq {s} pos {t}")


def check_invariants(cache, lengths):
    tables = np.asarray(cache.tables)
    ref = np.asarray(cache.pool.refcount)
    counts = np.zeros_like(ref)
    for s in range(N_SEQS):
        for b in tables[s]:
            if b >= 0:
                counts[b] += 1
    np.testing.assert_array_equal(counts, ref)
    # live blocks never exceed the dense equivalent
    dense_blocks = sum(-(-int(l) // BS) for l in lengths)
    assert int((ref > 0).sum()) <= dense_blocks


@settings(max_examples=30, deadline=None)
@given(cache_programs())
def test_paged_cache_matches_dense_reference(ops):
    run_program(ops)


def test_full_free_leaves_no_blocks():
    cache = kvc.create(CFG)
    mask = jnp.ones((N_SEQS,), bool)
    for t in range(5):
        cache, bid, pos = kvc.ensure_writable(CFG, cache, mask)
        v = jnp.ones((N_SEQS, KVH, HD))
        for layer in range(L):
            cache = kvc.write_kv(CFG, cache, bid, pos, layer, v, v, mask)
        cache = kvc.advance(cache, mask)
    cache = kvc.fork(cache, jnp.zeros((N_SEQS,), jnp.int32))
    cache = kvc.free(cache, mask)
    assert int(kvc.used_blocks(cache)) == 0
