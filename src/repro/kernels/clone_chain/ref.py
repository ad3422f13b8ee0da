"""Pure-jnp path of the fused resample->clone->refcount chain.

The composed path a resampling step takes is three ops over the same
data: systematic resampling (inverse-CDF search over the weight CDF),
the table gather (``tables[ancestors]``), and the clone bookkeeping
histogram (:mod:`repro.kernels.refcount_update`).  Ancestors match
:func:`repro.smc.resampling.resample_systematic` verbatim
(``searchsorted(cum, (arange(n) + u) / n, side="left")``), and
delta/member match :func:`refcount_delta_ref` on the gathered tables,
bit for bit.

The bookkeeping never reads the gathered tables.  Since
``new[i] = old[a[i]]``, old row ``p`` reappears ``c[p] = #{i : a[i] = p}``
times, so for every block ``b``

    delta[b] = #new entries equal to b - #old entries equal to b
             = sum over old entries (p, j) equal to b of (c[p] - 1),

an identity in integers: one scatter of the old tables weighted by the
offspring count less one, and a block is a member of the new generation
iff it sits in an old row with at least one offspring.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def clone_chain_ref(
    cum: jax.Array,  # [n] inclusive weight CDF, cum[-1] == 1
    u: jax.Array,  # scalar uniform in [0, 1)
    tables: jax.Array,  # [n, mb] int32 block tables (NULL = -1 allowed)
    num_blocks: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns ``(ancestors [n], new_tables [n, mb], delta [nb], member [nb])``."""
    n = cum.shape[0]
    positions = (jnp.arange(n) + u) / n
    ancestors = jnp.searchsorted(cum, positions, side="left").astype(jnp.int32)
    new_tables = tables[ancestors]
    offspring = jnp.zeros((n,), jnp.int32).at[ancestors].add(1)
    # NULL entries scatter to num_blocks, one past the end, and drop.
    ids = jnp.where(tables >= 0, tables, num_blocks)
    weight = jnp.broadcast_to((offspring - 1)[:, None], tables.shape)
    delta = (
        jnp.zeros((num_blocks,), jnp.int32)
        .at[ids.reshape(-1)]
        .add(weight.reshape(-1), mode="drop")
    )
    kept = jnp.where(offspring[:, None] > 0, ids, num_blocks)
    member = (
        jnp.zeros((num_blocks,), jnp.bool_)
        .at[kept.reshape(-1)]
        .set(True, mode="drop")
    )
    return ancestors, new_tables, delta, member
