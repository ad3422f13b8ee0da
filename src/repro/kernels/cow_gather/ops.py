"""Public entry points for the COW block gather and pool compaction.

On TPU these dispatch to the Pallas kernel; elsewhere (CPU hosts, and
whenever ``force_ref``) they fall back to the jnp oracle.  ``interpret``
runs the kernel body in interpret mode (used by the test sweeps).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.cow_gather.kernel import cow_gather_pallas
from repro.kernels.cow_gather.ref import cow_gather_ref
from repro.kernels.dispatch import resolve_kernel_mode
from repro.kernels.pool_rows import as_rank3


def cow_gather(
    pool: jax.Array,
    table: jax.Array,
    *,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Gather pool blocks by table; -1 entries yield zero blocks.

    pool: [num_blocks, *block_shape]; table: [k] int32.
    Returns [k, *block_shape].
    """
    use_kernel, interpret = resolve_kernel_mode(use_kernel, interpret)
    if not use_kernel:
        return cow_gather_ref(pool, table)
    out = cow_gather_pallas(as_rank3(pool), table, interpret=interpret)
    return out.reshape((table.shape[0],) + pool.shape[1:])


def pool_compact(
    data: jax.Array,
    perm: jax.Array,
    *,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Relocate pool payload rows for compaction (DESIGN.md §3.1).

    ``data: [num_blocks + 1, *block_shape]`` is a pool's payload
    including its trailing dump row; ``perm: [target] int32`` names the
    old block id feeding each new slot (``-1`` leaves the slot zeroed —
    used both for the free suffix and for capacity growth during a
    resize).  Returns ``[target + 1, *block_shape]`` with a fresh
    kept-zero dump row at the new ``target`` index.  One streamed gather
    pass over the live payload — the same scalar-prefetch kernel that
    materializes trajectories; the dump row is one more NULL entry, so
    no second pass concatenates it.
    """
    perm = jnp.concatenate([perm, jnp.full((1,), -1, perm.dtype)])
    return cow_gather(data, perm, use_kernel=use_kernel, interpret=interpret)
