"""Pallas kernel: fused clone bookkeeping — refcount delta + membership.

The lazy deep copy at resampling (Algorithm 3 + FREEZE of Algorithm 7)
is pure bookkeeping: ``refcount += multiplicity(new_tables) -
multiplicity(old_tables)``, plus the frozen bits for every block the new
generation can reach.  The legacy path made three scatter passes over
the pool (``add_refs``, ``sub_refs``, ``freeze``); here both the signed
histogram and the membership mask accumulate in VMEM in a single pass
over the flattened tables (DESIGN.md §3).

Grid: (block-id tile, table chunk).  The tables arrive lane-dense as
``[rows, 128]``; each step compares its rows against a tile of ``TN``
block ids held on sublanes (``[TN, 128]`` compares on the VPU — both
operands only broadcast, no relayout) and accumulates per-(id, lane)
counts in VMEM scratch.  At the last chunk one transpose folds the lanes
into the ``[1, TN]`` delta / membership outputs of that tile.  Tiling
the ids bounds VMEM at any pool size (a one-hot over the whole pool is
``[entries, num_blocks]``).  NULL (-1) entries and padding match no
block id and drop out for free.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS = 32  # table rows of 128 entries per grid step
_TILE = 512  # block ids per tile


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(new_ref, old_ref, delta_ref, member_ref, dacc_ref, macc_ref):
    t = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        dacc_ref[...] = jnp.zeros_like(dacc_ref)
        macc_ref[...] = jnp.zeros_like(macc_ref)

    tn = dacc_ref.shape[0]
    ids = t * tn + jax.lax.broadcasted_iota(jnp.int32, (tn, 1), 0)
    for r in range(new_ref.shape[0]):
        new_hits = (new_ref[r : r + 1, :] == ids).astype(jnp.int32)  # [tn, 128]
        old_hits = (old_ref[r : r + 1, :] == ids).astype(jnp.int32)
        dacc_ref[...] += new_hits - old_hits
        macc_ref[...] = jnp.maximum(macc_ref[...], new_hits)

    @pl.when(s == pl.num_programs(1) - 1)
    def _fold():
        delta_ref[...] = jnp.sum(dacc_ref[...].T, axis=0, keepdims=True)
        member_ref[...] = jnp.max(macc_ref[...].T, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("num_blocks", "interpret"))
def refcount_delta_pallas(
    new_tables: jax.Array,  # [e] int32, flattened (NULL = -1 allowed)
    old_tables: jax.Array,  # [e] int32
    *,
    num_blocks: int,
    interpret: bool = False,
):
    """Returns ``(delta [num_blocks] int32, member [num_blocks] bool)``."""
    e = new_tables.shape[0]
    rows = min(_ROWS, _round_up(-(-max(e, 1) // _LANES), 8))
    e_pad = _round_up(max(e, 1), rows * _LANES)
    tn = min(_TILE, _round_up(num_blocks, _LANES))
    nb_pad = _round_up(num_blocks, tn)

    def lane_dense(x):
        x = jnp.pad(x.astype(jnp.int32), (0, e_pad - e), constant_values=-1)
        return x.reshape(-1, _LANES)

    chunk = pl.BlockSpec((rows, _LANES), lambda t, s: (s, 0))
    tile = pl.BlockSpec((1, tn), lambda t, s: (0, t))
    delta, member = pl.pallas_call(
        _kernel,
        grid=(nb_pad // tn, e_pad // (rows * _LANES)),
        in_specs=[chunk, chunk],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((1, nb_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, nb_pad), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tn, _LANES), jnp.int32),
            pltpu.VMEM((tn, _LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lane_dense(new_tables), lane_dense(old_tables))
    return delta[0, :num_blocks], member[0, :num_blocks] != 0
