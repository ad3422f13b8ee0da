"""Pallas paged attention: single-token decode over the COW block pool.

This is the paper's lazy-copy platform meeting the TPU: sequences share
KV blocks through refcounted tables (O(1) fork during population-based
decoding), and attention reads KV *through the block table* — the table
arrives via scalar prefetch so each block's HBM->VMEM DMA is issued at
its pool address with no gather materialization.

Grid (B, n_blocks); the block dimension is minor (sequential), so the
flash running-softmax state lives in VMEM scratch.  One grid step reads
one page for *all* KV heads: the K/V block is ``(1, bs, KVH, d)``, whose
two minor dims are the pool's own (the TPU tiling rule — a block's last
two dims are either full or (8, 128)-aligned), and every query head of
every group is served from that one DMA.  Scores are computed per KV
head on the VPU (``[bs, KVH, 1]`` columns), which keeps the head axis on
sublanes and the head dim on lanes throughout: no relayout, no
head-by-head re-fetch.  Blocks past a sequence's length — and NULL (-1)
table entries — are skipped (``pl.when``), so ragged batches cost their
true lengths, not the padded maximum.

Pool layout: [num_blocks, block_size, KVH, d].  Queries are regrouped
to ``[B, G, KVH, d]`` (head ``h = kv * G + g``) so a query group is a
leading-dim index.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attend(q_ref, k, v, m_ref, l_ref, acc_ref, *, j, length, scale):
    """One page of the flash update for every query group.

    k, v: [bs, KVH, d] f32; q_ref: [1, G, KVH, d];
    m_ref/l_ref: [G, KVH, 128] (lane-replicated); acc_ref: [G, KVH, d].
    """
    bs = k.shape[0]
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, k.shape[1], 1), 0)
    for g in range(q_ref.shape[1]):
        q = q_ref[0, g].astype(jnp.float32)  # [KVH, d]
        s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale  # [bs, KVH, 1]
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[g][:, :1]  # [KVH, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.exp(s - m_new[None])  # [bs, KVH, 1]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=0)
        acc_ref[g] = acc_ref[g] * alpha + jnp.sum(p * v, axis=0)
        m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])


def _init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _finalize(o_ref, l_ref, acc_ref):
    for g in range(o_ref.shape[1]):
        l = l_ref[g][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, g] = (acc_ref[g] / l).astype(o_ref.dtype)


def _kernel(
    tables_ref, lens_ref,  # scalar prefetch: [B, nb], [B]
    q_ref,  # [1, G, KVH, d]
    k_ref, v_ref,  # [1, bs, KVH, d]
    o_ref,  # [1, G, KVH, d]
    m_ref, l_ref, acc_ref,  # scratch [G, KVH, 128], [G, KVH, 128], [G, KVH, d]
    *,
    scale: float,
    bs: int,
    nb: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        _init(m_ref, l_ref, acc_ref)

    length = lens_ref[b]

    @pl.when(jnp.logical_and(j * bs < length, tables_ref[b, j] >= 0))
    def _compute():
        _attend(
            q_ref, k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            m_ref, l_ref, acc_ref, j=j, length=length, scale=scale,
        )

    @pl.when(j == nb - 1)
    def _():
        _finalize(o_ref, l_ref, acc_ref)


def _kernel_delta(
    tables_ref, lens_ref, parent_ref, dbits_ref,  # scalar prefetch
    q_ref,  # [1, G, KVH, d]
    k_ref, v_ref,  # [1, bs, KVH, d] — the page itself
    kp_ref, vp_ref,  # [1, bs, KVH, d] — its delta parent (self for full pages)
    o_ref,  # [1, G, KVH, d]
    m_ref, l_ref, acc_ref,
    *,
    scale: float,
    bs: int,
    nb: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        _init(m_ref, l_ref, acc_ref)

    length = lens_ref[b]
    t = tables_ref[b, j]

    @pl.when(jnp.logical_and(j * bs < length, t >= 0))
    def _compute():
        # Per-slot select: dirty slots come from the page, the rest from
        # its parent — uniform (no branch), and a full page selects its
        # own (identical) stream on both sides.  The page's dirty mask
        # arrives as one prefetched bit word.
        bits = dbits_ref[jnp.maximum(t, 0)]
        slot = jax.lax.broadcasted_iota(jnp.int32, (bs, 1, 1), 0)
        dirty = jnp.right_shift(bits, slot) & 1 != 0  # [bs, 1, 1]
        k = jnp.where(dirty, k_ref[0], kp_ref[0]).astype(jnp.float32)
        v = jnp.where(dirty, v_ref[0], vp_ref[0]).astype(jnp.float32)
        _attend(
            q_ref, k, v, m_ref, l_ref, acc_ref, j=j, length=length, scale=scale,
        )

    @pl.when(j == nb - 1)
    def _():
        _finalize(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnums=(0, 5, 6, 7))
def _call(kernel, prefetch, q, k_pool, v_pool, n_kv_streams, interpret, scale):
    b, h, d = q.shape
    nb = prefetch[0].shape[1]
    bs, kvh = k_pool.shape[1], k_pool.shape[2]
    g = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, kvh, g, d).transpose(0, 2, 1, 3)  # [B, G, KVH, d]
    npf = len(prefetch)

    def q_idx(bb, j, *refs):
        return (bb, 0, 0, 0)

    def self_idx(bb, j, tables_ref, *refs):
        return (jnp.maximum(tables_ref[bb, j], 0), 0, 0, 0)

    def parent_idx(bb, j, tables_ref, lens_ref, parent_ref, *refs):
        t = jnp.maximum(tables_ref[bb, j], 0)
        p = parent_ref[t]
        return (jnp.where(p >= 0, p, t), 0, 0, 0)

    kv_spec = functools.partial(pl.BlockSpec, (1, bs, kvh, d))
    kv_specs = [kv_spec(self_idx), kv_spec(self_idx)]
    if n_kv_streams == 2:
        kv_specs += [kv_spec(parent_idx), kv_spec(parent_idx)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=npf,
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, g, kvh, d), q_idx)] + kv_specs,
        out_specs=pl.BlockSpec((1, g, kvh, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((g, kvh, 128), jnp.float32),
            pltpu.VMEM((g, kvh, 128), jnp.float32),
            pltpu.VMEM((g, kvh, d), jnp.float32),
        ],
    )
    pools = (k_pool, v_pool) * n_kv_streams
    out = pl.pallas_call(
        functools.partial(kernel, scale=scale, bs=bs, nb=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, kvh, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*prefetch, qg, *pools)
    return out.transpose(0, 2, 1, 3).reshape(b, h, d)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention_delta_pallas(
    q: jax.Array,  # [B, H, d]
    k_pool: jax.Array,  # [num_blocks (+1), bs, KVH, d]
    v_pool: jax.Array,
    tables: jax.Array,  # [B, nb] int32
    lengths: jax.Array,  # [B] int32
    parent: jax.Array,  # [num_blocks] int32
    dirty: jax.Array,  # [num_blocks, bs] bool/int
    *,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    bs = dirty.shape[1]
    if bs > 32:
        raise ValueError(f"delta paged attention packs dirty masks in 32 bits; bs={bs}")
    # One int32 word per page: bit s set <=> slot s is dirty.
    weights = jnp.left_shift(jnp.int32(1), jnp.arange(bs, dtype=jnp.int32))
    dbits = jnp.sum(jnp.where(dirty != 0, weights, 0), axis=1, dtype=jnp.int32)
    return _call(
        _kernel_delta, (tables, lengths, parent, dbits), q, k_pool, v_pool,
        2, interpret, scale,
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention_pallas(
    q: jax.Array,  # [B, H, d]
    k_pool: jax.Array,  # [num_blocks, bs, KVH, d]
    v_pool: jax.Array,
    tables: jax.Array,  # [B, nb] int32
    lengths: jax.Array,  # [B] int32
    *,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    return _call(
        _kernel, (tables, lengths), q, k_pool, v_pool, 1, interpret, scale
    )
