"""How the COW pool kernels stream one pool row through VMEM.

A pool row is one block: ``[*row_shape]`` of a ``[num_blocks, *row_shape]``
payload.  The TPU's tiling rule wants a kernel block's two minor dims to
be either (8, 128)-aligned or the array's own, so the row kernels keep
the payload in its native shape (no flattening relayout of the whole
pool) and take whole minor dims:

* rows of rank >= 3 (e.g. the KV page ``[L, 2, bs, KVH, d]``) stream one
  leading-dim slice per grid step, so a multi-MB row never has to fit in
  VMEM at once;
* rows of rank 2 (``[bs, item]``) stream whole;
* rows of rank 1 (``[bs]`` scalars) are viewed as ``[bs, 1]`` by
  :func:`as_rank3`, since a block of ``(1, bs)`` over ``[num_blocks, bs]``
  splits the tiled second-minor dim.
"""

from __future__ import annotations

from typing import Tuple

import jax


def as_rank3(x: jax.Array) -> jax.Array:
    """``[n, bs]`` -> ``[n, bs, 1]``; arrays of rank >= 3 pass through."""
    return x.reshape(x.shape + (1,)) if x.ndim == 2 else x


def row_blocking(row_shape: Tuple[int, ...]) -> Tuple[int, Tuple[int, ...]]:
    """``(splits, block)`` for a row of ``row_shape`` (rank >= 2): the grid
    extent over the row's leading dim and the kernel block shape
    (including the leading pool-row dim of 1)."""
    if len(row_shape) >= 3:
        return row_shape[0], (1, 1) + tuple(row_shape[1:])
    return 1, (1,) + tuple(row_shape)
