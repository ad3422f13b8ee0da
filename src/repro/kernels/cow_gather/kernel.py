"""Pallas kernel: gather blocks from the COW pool by a block table.

The data-movement primitive of the lazy-copy platform: materializing a
particle trajectory / compacting a fragmented pool / eager deep copies
(``materialize``) are all "gather rows of a [num_blocks, *row] pool by
an index vector".  The block table arrives via **scalar prefetch**, so
the index is known before the DMA for each grid step is issued — the
pool block is streamed HBM->VMEM directly at its final position; NULL
(-1) entries produce zero blocks.

Grid: (table entry, row slice) — see :mod:`repro.kernels.pool_rows` for
how a row is cut into legal TPU blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pool_rows import row_blocking


def _kernel(table_ref, pool_ref, out_ref):
    # NULL entries (bid < 0) were clamped to 0 in the index map; zero them.
    valid = table_ref[pl.program_id(0)] >= 0
    block = pool_ref[...]
    out_ref[...] = jnp.where(valid, block, jnp.zeros_like(block))


@functools.partial(jax.jit, static_argnames=("interpret",))
def cow_gather_pallas(
    pool: jax.Array,  # [num_blocks, *row] with rank(row) >= 2
    table: jax.Array,  # [k] int32 (NULL_BLOCK = -1 allowed)
    *,
    interpret: bool = False,
) -> jax.Array:
    k = table.shape[0]
    row = pool.shape[1:]
    splits, block = row_blocking(row)
    tail = (0,) * (len(block) - 2)

    def src(i, r, table_ref):
        return (jnp.maximum(table_ref[i], 0), r) + tail

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k, splits),
        in_specs=[pl.BlockSpec(block, src)],
        out_specs=pl.BlockSpec(block, lambda i, r, table_ref: (i, r) + tail),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k,) + row, pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(table, pool)
