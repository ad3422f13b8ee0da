"""Public paged-attention entry point."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import resolve_kernel_mode
from repro.kernels.paged_attention.kernel import (
    paged_attention_delta_pallas,
    paged_attention_pallas,
)
from repro.kernels.paged_attention.ref import paged_attention_ref


def paged_attention(
    q: jax.Array,  # [B, H, d]
    k_pool: jax.Array,  # [num_blocks, block_size, KVH, d]
    v_pool: jax.Array,
    tables: jax.Array,  # [B, n_blocks_per_seq] int32 (-1 = NULL)
    lengths: jax.Array,  # [B] int32 valid positions per sequence
    *,
    parent: jax.Array | None = None,  # [num_blocks] int32 delta parents
    dirty: jax.Array | None = None,  # [num_blocks, block_size] bool
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-token paged attention over the COW block pool.

    With ``parent``/``dirty`` (the pool's sub-block delta COW leaves,
    DESIGN.md §3.2) the gather resolves delta pages in place: dirty
    token slots read the page, the rest read its parent — decode never
    materializes shared pages.  ``parent=None`` is byte-for-byte the
    pre-delta path.
    """
    use_kernel, interpret = resolve_kernel_mode(use_kernel, interpret)
    if parent is None:
        if use_kernel:
            return paged_attention_pallas(
                q, k_pool, v_pool, tables, lengths, interpret=interpret
            )
        return paged_attention_ref(q, k_pool, v_pool, tables, lengths)
    if use_kernel:
        return paged_attention_delta_pallas(
            q, k_pool, v_pool, tables, lengths,
            parent, dirty, interpret=interpret,
        )
    return paged_attention_ref(
        q, k_pool, v_pool, tables, lengths, parent=parent, dirty=dirty
    )
