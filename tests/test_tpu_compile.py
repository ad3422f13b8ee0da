"""Compile-only tests: the main-path Pallas kernels at real widths, for v5e.

Interpret mode runs a kernel's body on the CPU and never asks the TPU
compiler (Mosaic) whether it accepts the kernel's blocks, layouts and
VMEM use; every kernel here once passed its interpret-mode parity tests
and was still refused by Mosaic.  These tests compile each kernel for a
*described* TPU v5e (no chip attached: ``jax.experimental.topologies``)
at the widths the serve and filter paths run, through the public op so
the ops-level reshapes are compiled too.  Nothing runs; a test passes
when the compiler accepts the program and the program really contains
the Pallas kernel (``tpu_custom_call``) rather than an oracle.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler library, and all
of these tests live in this one file so that one test worker holds it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels.clone_chain import clone_chain
from repro.kernels.cow_gather import pool_compact
from repro.kernels.cow_write import cow_write
from repro.kernels.paged_attention import paged_attention
from repro.kernels.refcount_update import refcount_update

# musicgen_large serving: 32 sequences over a 288-page pool of 16-token
# pages, 32 KV heads of 64 (one layer's view), up to 7 pages each.
SEQS, HEADS, HD, BS, PAGES, POOL = 32, 32, 64, 16, 7, 288
LAYERS = 48
# RBPF at the paper's N = 2048, T = 500 under LAZY_SR: 4-item blocks of
# 6-float records, 125 blocks per particle, a 43 262-block pool.
N, ITEM, BLOCK, MAX_BLOCKS, STORE_POOL = 2048, 6, 4, 125, 43262


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler library in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the dispatch policy to its TPU arm (this host is a CPU)."""
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled, count: int = 1):
    assert compiled.as_text().count("tpu_custom_call") >= count


@pytest.mark.parametrize("delta", [False, True], ids=["plain", "delta"])
def test_paged_attention_musicgen_widths(one_chip, on_tpu, delta):
    bf16, i32 = jnp.bfloat16, jnp.int32
    shapes = [
        ((SEQS, HEADS, HD), bf16),
        ((POOL + 1, BS, HEADS, HD), bf16),
        ((POOL + 1, BS, HEADS, HD), bf16),
        ((SEQS, PAGES), i32),
        ((SEQS,), i32),
    ]
    if delta:
        shapes += [((POOL,), i32), ((POOL, BS), jnp.bool_)]

        def fn(q, k, v, t, n, parent, dirty):
            return paged_attention(q, k, v, t, n, parent=parent, dirty=dirty)
    else:
        fn = paged_attention
    _assert_kernel(_compile(fn, one_chip, *shapes))


@pytest.mark.parametrize("delta", [False, True], ids=["plain", "delta"])
def test_cow_write_rbpf_store(one_chip, on_tpu, delta):
    i32 = jnp.int32
    shapes = [
        ((STORE_POOL + 1, BLOCK, ITEM), jnp.float32),
        ((N,), i32),
        ((N,), i32),
        ((N,), i32),
        ((N, ITEM), jnp.float32),
    ]
    if delta:
        shapes.append(((N, BLOCK), jnp.bool_))

        def fn(data, src, dst, pos, vals, keep):
            return cow_write(data, src, dst, pos, vals, keep=keep)
    else:
        fn = cow_write
    _assert_kernel(_compile(fn, one_chip, *shapes))


def test_pool_compact_full_kv_row(one_chip, on_tpu):
    """One KV page row of all 48 layers (6.3 MB in bf16) cannot be staged
    whole in VMEM: the kernel must stream it in legal slices."""
    row = (LAYERS, 2, BS, HEADS * HD)
    compiled = _compile(
        pool_compact,
        one_chip,
        ((POOL + 1, *row), jnp.bfloat16),
        ((POOL,), jnp.int32),
    )
    _assert_kernel(compiled)
    # No relayout copy of the pool around the kernel: the program's
    # temporaries stay far below one pool (1.8 GB).
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_refcount_update_rbpf_store(one_chip, on_tpu):
    i32 = jnp.int32

    def fn(refcount, frozen, new, old):
        return refcount_update(refcount, frozen, new, old, do_freeze=True)

    _assert_kernel(
        _compile(
            fn,
            one_chip,
            ((STORE_POOL,), i32),
            ((STORE_POOL,), jnp.bool_),
            ((N, MAX_BLOCKS), i32),
            ((N, MAX_BLOCKS), i32),
        )
    )


@pytest.mark.parametrize(
    "n,mb,nb", [(4096, 16, 9000), (N, MAX_BLOCKS, STORE_POOL)], ids=["n4096", "rbpf"]
)
def test_clone_chain(one_chip, on_tpu, n, mb, nb):
    def fn(key, logw, tables):
        return clone_chain(key, logw, tables, num_blocks=nb)

    _assert_kernel(
        _compile(
            fn,
            one_chip,
            ((2,), jnp.uint32),
            ((n,), jnp.float32),
            ((n, mb), jnp.int32),
        ),
        count=2,  # the resample/gather kernel and the histogram kernel
    )
