"""Public entry point for the fused resample->clone->refcount chain.

``clone_chain`` collapses a resampling step's three dispatches over the
population tables (systematic resampling, table gather, clone
bookkeeping histogram) into one: the caller hands it log-weights and the
current tables and gets back the ancestors, the cloned tables, and the
refcount delta / freeze membership — everything
:func:`repro.core.store.clone` needs, with the tables read **once**.

The weight math replicates :func:`repro.smc.resampling.resample_systematic`
verbatim (normalize -> exp -> cumsum with tail guard -> one scalar
uniform), so fused and composed paths are ancestor-bit-exact.

The two bodies compute the same delta differently.  The jnp path
(:func:`clone_chain_ref`) scatters the old tables once, each entry
weighted by its row's offspring count less one, and never passes the
gathered tables through a histogram.  The Pallas path gathers in one
kernel and takes the signed histogram ``+new -old`` in the tiled
:mod:`repro.kernels.refcount_update` kernel.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.clone_chain.kernel import clone_chain_pallas
from repro.kernels.clone_chain.ref import clone_chain_ref
from repro.kernels.dispatch import resolve_kernel_mode


def clone_chain(
    key: jax.Array,
    logw: jax.Array,  # [n] log-weights (any normalization)
    tables: jax.Array,  # [n, mb] int32 block tables (NULL = -1 allowed)
    *,
    num_blocks: int,
    use_kernel: bool | None = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns ``(ancestors [n], new_tables [n, mb], delta [nb], member [nb])``."""
    use_kernel, interpret = resolve_kernel_mode(use_kernel, interpret)
    # Exactly resampling.resample_systematic's weight path: normalized
    # log-weights -> weights -> inclusive CDF with the tail guarded
    # against rounding, one scalar uniform for the whole comb.
    logw = logw - jax.scipy.special.logsumexp(logw)
    w = jnp.exp(logw)
    cum = jnp.cumsum(w)
    cum = cum / cum[-1]
    u = jax.random.uniform(key)
    if use_kernel:
        return clone_chain_pallas(
            cum, u.reshape(1), tables, num_blocks=num_blocks, interpret=interpret
        )
    return clone_chain_ref(cum, u, tables, num_blocks)
