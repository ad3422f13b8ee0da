"""Pallas kernel: fused copy-on-write + item write over the block pool.

The write half of the lazy-copy platform (DESIGN.md §3).  One grid step
per particle: the source block is streamed HBM->VMEM once (scalar-
prefetched index, so the DMA is issued before the body runs), the
written item is merged at its in-block offset on the VPU, and the merged
block is emitted at the destination index — Algorithm 5's GET->COPY and
the item write fused into a single read + single write per touched
block, instead of the gather / block-scatter / item-scatter trio the jnp
path pays.

Routing contract (established by ``store._write_impl``):

* COW rows:       ``src = current block``, ``dst = fresh allocation``;
* in-place/fresh: ``src = dst`` (read-modify-write of the own block);
* masked-out:     ``src = dst = num_blocks`` — the pool's dump row, a
  write-only slab nothing ever reads, so skipped rows cost one
  cache-resident self-copy rather than a branch.

Blocks keep the payload's native shape (see
:mod:`repro.kernels.pool_rows`): a ``[bs, *item]`` row streams whole,
and a row whose items are rank >= 2 streams one slot per grid step, so
the item merge is a select along the slot axis — no flattening relayout
of the pool, and no ``(1, block_elems)`` block that splits a tiled dim.

The output aliases the pool (``input_output_aliases``), so untouched
blocks are not rewritten.  Aliasing is race-free because no row's
``src`` can be another row's ``dst`` within one call: copy sources are
shared (refcount > 1, or frozen under LAZY) while destinations are
fresh (refcount 0) or exclusively owned (refcount 1, unfrozen) — the
dump row excepted, which only ever holds garbage.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pool_rows import row_blocking


def _merge(pos_ref, data_ref, val_ref, keep_ref, out_ref):
    i = pl.program_id(0)
    block = data_ref[...]  # [1, slots, *item] — (a slice of) the source block
    slot = pl.program_id(1) * block.shape[1] + jax.lax.broadcasted_iota(
        jnp.int32, block.shape, 1
    )
    if keep_ref is not None:
        # Delta merge: kept slots copy the source, everything else is
        # zero-filled (the delta-COW invariant).
        block = jnp.where(keep_ref[...] != 0, block, jnp.zeros_like(block))
    # The written item wins at `pos`.
    out_ref[...] = jnp.where(slot == pos_ref[i], val_ref[...], block)


def _kernel(src_ref, dst_ref, pos_ref, data_ref, val_ref, out_ref):
    del src_ref, dst_ref  # consumed by the index maps
    _merge(pos_ref, data_ref, val_ref, None, out_ref)


def _kernel_delta(src_ref, dst_ref, pos_ref, data_ref, val_ref, keep_ref, out_ref):
    del src_ref, dst_ref  # consumed by the index maps
    _merge(pos_ref, data_ref, val_ref, keep_ref, out_ref)


@functools.partial(jax.jit, static_argnums=(0, 7))
def _call(kernel, data, src, dst, pos, values, keep, interpret):
    n = src.shape[0]
    splits, block = row_blocking(data.shape[1:])
    item_block = (1, 1) + tuple(data.shape[2:])
    tail = (0,) * (len(block) - 2)

    def row_of(ref):
        return lambda i, r, src_ref, dst_ref, pos_ref: (ref(src_ref, dst_ref)[i], r) + tail

    in_specs = [
        pl.BlockSpec(block, row_of(lambda s, d: s)),
        pl.BlockSpec(item_block, lambda i, r, *refs: (i, 0) + tail),
    ]
    args = [src, dst, pos, data, values.reshape((n, 1) + data.shape[2:])]
    if keep is not None:
        # [n, bs] -> [n, bs, 1, ...]: a slot mask that broadcasts over items.
        in_specs.append(
            pl.BlockSpec(block[:2] + (1,) * len(tail), lambda i, r, *refs: (i, r) + tail)
        )
        args.append(keep.astype(jnp.int32).reshape((n, keep.shape[1]) + (1,) * len(tail)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n, splits),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(block, row_of(lambda s, d: d)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(data.shape, data.dtype),
        input_output_aliases={3: 0},  # flat operand 3 = `data` (after 3 prefetch args)
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cow_write_delta_pallas(
    data: jax.Array,  # [num_blocks + 1, bs, *item] (rank >= 3); trailing dump row
    src: jax.Array,  # [n] int32 — block to stream (dump for skipped rows)
    dst: jax.Array,  # [n] int32 — block to emit (dump for skipped rows)
    pos: jax.Array,  # [n] int32 — item offset within the block
    values: jax.Array,  # [n, *item]
    keep: jax.Array,  # [n, bs] — slots copied from src
    *,
    interpret: bool = False,
) -> jax.Array:
    return _call(_kernel_delta, data, src, dst, pos, values, keep, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cow_write_pallas(
    data: jax.Array,  # [num_blocks + 1, bs, *item] (rank >= 3); trailing dump row
    src: jax.Array,  # [n] int32 — block to stream (dump for skipped rows)
    dst: jax.Array,  # [n] int32 — block to emit (dump for skipped rows)
    pos: jax.Array,  # [n] int32 — item offset within the block
    values: jax.Array,  # [n, *item]
    *,
    interpret: bool = False,
) -> jax.Array:
    return _call(_kernel, data, src, dst, pos, values, None, interpret)
