"""Pallas kernel: fused resample -> table gather -> clone bookkeeping.

A resampling step of the lazy-copy platform is three dispatches over the
same small tables: the inverse-CDF ancestor search
(:mod:`repro.kernels.resample`), the block-table gather
(``tables[ancestors]``), and the refcount histogram
(:mod:`repro.kernels.refcount_update`).  Here the first two are one
kernel: per row chunk it

  * counts the systematic comb against the full weight CDF
    (``anc[j] = #{i : cum[i] < (j + u) / n}`` — exactly
    ``searchsorted(cum, (j + u) / n, side="left")``; the comb positions
    are computed by the caller with the oracle's own expression, so the
    compare sees bit-identical operands),
  * gathers the ancestors' table rows with one-hot matmuls on the MXU:
    ``id + 1`` (NULL = -1 included) is split into three 8-bit digits,
    each exact in bf16, so every product has one nonzero term and the
    f32 accumulation is exact whatever the matmul precision — block ids
    up to 2**24 - 2,

and the histogram of ``new - old`` plus the freeze membership is the
block-id-tiled :mod:`repro.kernels.refcount_update` kernel.  A single
fused one-hot over ``[rows * mb, num_blocks]`` does not fit VMEM at the
paper's population sizes (2048 x 125 entries against ~43k blocks for
RBPF), which is why the histogram is a second, tiled pass.

Grid: one step per row chunk; the CDF and the full table live in VMEM
(population tables are KB- to MB-scale).  The chunk adapts to ``n`` so
the ``[chunk, n]`` compare and one-hot stay about 1 MB.  Padded rows
gather NULL rows, so they drop out of the histogram for free.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.refcount_update.kernel import refcount_delta_pallas

#: target elements of the per-step ``[chunk, n]`` compare / one-hot
_ELEMS = 1 << 18


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(
    pos_ref,  # [chunk, 1] f32 — this chunk's comb positions
    cum_ref,  # [1, n_pad] f32 — full CDF every step (padding > 1)
    tab_ref,  # [n_pad, mb] int32 — full tables every step (gather source)
    anc_ref,  # [chunk, 1] int32 out
    new_ref,  # [chunk, mb] int32 out
    *,
    chunk: int,
    n: int,
):
    i = pl.program_id(0)
    cnt = jnp.sum(
        (cum_ref[...] < pos_ref[...]).astype(jnp.int32), axis=1, keepdims=True
    )  # [chunk, 1]
    anc = jnp.clip(cnt, 0, n - 1)
    anc_ref[...] = anc
    n_pad = cum_ref.shape[1]
    oh = (anc == jax.lax.broadcasted_iota(jnp.int32, (chunk, n_pad), 1)).astype(
        jnp.bfloat16
    )
    ids = tab_ref[...] + 1  # [n_pad, mb], >= 0
    newt = -1
    for shift in (0, 8, 16):
        digit = ((ids >> shift) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            oh, digit, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [chunk, mb]
        newt = newt + (part.astype(jnp.int32) << shift)
    # Rows past n are grid padding: park them on NULL so the histogram
    # never sees them.
    rows = i * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    new_ref[...] = jnp.where(rows < n, newt, -1)


@functools.partial(jax.jit, static_argnames=("num_blocks", "interpret"))
def clone_chain_pallas(
    cum: jax.Array,  # [n] inclusive weight CDF, cum[-1] == 1
    u: jax.Array,  # [1] uniform in [0, 1)
    tables: jax.Array,  # [n, mb] int32 (NULL = -1 allowed)
    *,
    num_blocks: int,
    interpret: bool = False,
):
    """Returns ``(ancestors [n], new_tables [n, mb], delta [nb], member [nb])``."""
    n, mb = tables.shape
    if num_blocks >= (1 << 24) - 1:
        raise ValueError(f"clone_chain gathers block ids below 2**24 - 1; {num_blocks=}")
    chunk = max(8, min(256, _ELEMS // _round_up(n, 128)) // 8 * 8)
    chunk = min(chunk, _round_up(n, 8))
    n_pad = _round_up(n, chunk)
    # The oracle's comb expression, verbatim (see ref.py).
    pos = (jnp.arange(n) + u[0]) / n
    pos = jnp.pad(pos, (0, n_pad - n)).reshape(n_pad, 1)
    cum_p = jnp.pad(cum, (0, n_pad - n), constant_values=2.0).reshape(1, n_pad)
    tab_p = jnp.pad(tables, ((0, n_pad - n), (0, 0)), constant_values=-1)
    kernel = functools.partial(_kernel, chunk=chunk, n=n)
    anc, new_tables = pl.pallas_call(
        kernel,
        grid=(n_pad // chunk,),
        in_specs=[
            pl.BlockSpec((chunk, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, n_pad), lambda i: (0, 0)),
            pl.BlockSpec((n_pad, mb), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((chunk, 1), lambda i: (i, 0)),
            pl.BlockSpec((chunk, mb), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, mb), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pos, cum_p, tab_p)
    new_tables = new_tables[:n]
    delta, member = refcount_delta_pallas(
        new_tables.reshape(-1), tables.reshape(-1),
        num_blocks=num_blocks, interpret=interpret,
    )
    return anc[:n, 0], new_tables, delta, member
