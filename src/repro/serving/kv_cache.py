"""Paged, copy-on-write KV cache on the lazy-copy block pool.

This is the paper's platform applied to serving: sequences are the
particles, tokens are the generations, and the KV cache is the payload.

  * a **block** holds ``block_size`` token positions across *all* layers
    (pool payload ``[L, 2, bs, KVH * hd]``), so one refcount governs one
    page of context.  Heads and head dim share the minor axis: with
    ``hd < 128`` a separate ``hd`` axis would be padded to the TPU's 128
    lanes, and the device's default layout would then move the block
    axis minor — every kernel over the pool would pay a full relayout;
  * ``fork`` (the resampling clone of population-based decoding, or the
    n-best fan-out of parallel sampling) is a table gather + refcount
    delta — **O(1) data movement** per sequence, Algorithm 3;
  * appending a token *ensures a writable tail block first*: fresh block
    at page boundaries, COW copy if the tail is shared
    (``refcount > 1`` — Algorithm 5 with the single-reference
    optimization), in-place otherwise; every layer then writes its K/V
    slice into the resolved block;
  * memory = live blocks: ``O(D·T + D·N·log N + D·N·B)`` for N particles
    of length T (Jacob et al. bound + one tail block per particle),
    vs ``O(D·N·T)`` for per-sequence dense caches.

Everything is functional and jittable (fixed shapes, masked ops).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import pool as pool_lib
from repro.core.pool import NULL_BLOCK, BlockPool

__all__ = ["KVCacheConfig", "PagedKVCache", "create", "fork", "ensure_writable",
           "write_kv", "advance", "layer_views", "used_blocks", "free_blocks",
           "oom_flag", "grow", "compact", "free"]


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    n_layers: int
    n_kv_heads: int
    head_dim: int
    block_size: int = 16
    max_seqs: int = 8
    max_blocks_per_seq: int = 64
    num_blocks: int = 0  # 0 = auto (sparse-bound sized)
    dtype: str = "float32"
    # Sub-block delta COW (DESIGN.md §3.2): a mid-page fork's COW copy
    # moves only the token slots the tail block has materialized (plus
    # bookkeeping) instead of the whole ``[L, 2, bs, KVH * hd]`` page;
    # the untouched prefix resolves through the parent page.  Paged
    # attention reads through ``pool.parent``/``pool.dirty`` directly
    # (COW-native decode), so no materialization is ever needed.  Off by
    # default — parents stay all-NULL and behavior is value-identical.
    delta_cow: bool = False

    @property
    def pool_blocks(self) -> int:
        if self.num_blocks:
            return self.num_blocks
        import math

        n, t = self.max_seqs, self.max_blocks_per_seq
        bound = t + int(4 * n * max(1.0, math.log(max(n, 2)))) + 2 * n
        return min(n * t, max(bound, 16))

    @property
    def pool_blocks_cap(self) -> int:
        """Capacity at which allocation provably cannot fail: every
        sequence owns at most ``max_blocks_per_seq`` pages plus one
        transient while a COW source and its copy coexist inside
        ``ensure_writable``.  The serving growth ceiling (DESIGN.md §3.1)."""
        return self.max_seqs * self.max_blocks_per_seq + self.max_seqs


class PagedKVCache(NamedTuple):
    pool: BlockPool  # data [num_blocks + 1, L, 2, bs, KVH * hd] (dump row last)
    tables: jax.Array  # [max_seqs, max_blocks_per_seq] int32
    lengths: jax.Array  # [max_seqs] int32


def create(cfg: KVCacheConfig) -> PagedKVCache:
    pool = pool_lib.init(
        cfg.pool_blocks,
        (cfg.n_layers, 2, cfg.block_size, cfg.n_kv_heads * cfg.head_dim),
        jnp.dtype(cfg.dtype),
        npos=cfg.block_size,  # dirty mask tracks the token-position axis
    )
    return PagedKVCache(
        pool=pool,
        tables=jnp.full(
            (cfg.max_seqs, cfg.max_blocks_per_seq), NULL_BLOCK, jnp.int32
        ),
        lengths=jnp.zeros((cfg.max_seqs,), jnp.int32),
    )


def fork(cache: PagedKVCache, ancestors: jax.Array) -> PagedKVCache:
    """Lazy deep copy of sequences (resampling): bookkeeping only."""
    new_tables = cache.tables[ancestors]
    pool = pool_lib.add_refs(cache.pool, new_tables)
    pool = pool_lib.sub_refs(pool, cache.tables)
    return PagedKVCache(
        pool=pool, tables=new_tables, lengths=cache.lengths[ancestors]
    )


def ensure_writable(
    cfg: KVCacheConfig, cache: PagedKVCache, mask: jax.Array
) -> Tuple[PagedKVCache, jax.Array, jax.Array]:
    """Resolve a writable tail block per active sequence (the GET).

    Returns (cache, block_ids [S], pos_in_block [S]); block_ids are valid
    where ``mask``; COW copies happen here, once per token for all
    layers.
    """
    n = cfg.max_seqs
    rows = jnp.arange(n, dtype=jnp.int32)
    bs = cfg.block_size
    idx = cache.lengths // bs
    pos = cache.lengths % bs
    cur = cache.tables[rows, idx]
    fresh = (cur == NULL_BLOCK) & mask
    shared = cache.pool.refcount[jnp.where(cur >= 0, cur, 0)] > 1
    need_copy = (~fresh) & shared & mask
    need_block = fresh | need_copy

    # Rank-compacted allocation: under continuous batching the active
    # slots are a sparse subset of ``max_seqs`` (DESIGN.md §8), and the
    # plain ``alloc`` pairs request i with free-stack candidate i — a
    # request in a high slot could spuriously OOM while blocks are free.
    # ``alloc_compact`` succeeds whenever ``sum(need_block)`` blocks are
    # free, and is bit-identical to ``alloc`` for dense-prefix masks.
    cur_safe = jnp.where(cur >= 0, cur, 0)
    if cfg.delta_cow:
        # Captured before refcount traffic: sub_refs below may free cur
        # and clear its delta bookkeeping.
        dirty_cur = cache.pool.dirty[cur_safe]  # [S, bs]
        par_cur = cache.pool.parent[cur_safe]
        root = jnp.where(need_copy & (par_cur >= 0), par_cur, cur)

    pool, new_bid = pool_lib.alloc_compact(cache.pool, n, commit=need_block)
    if cfg.delta_cow:
        # The child's reference on its parent, added before the writer's
        # reference on cur is released (no transient zero on the parent).
        pool = pool_lib.add_refs(pool, jnp.where(need_copy, root, NULL_BLOCK))
        # Delta copy: move only the token slots cur materialized; rows
        # with nothing to keep read the dump row (a zero page) instead
        # of the shared payload.
        src = jnp.where(need_copy & jnp.any(dirty_cur, axis=1), cur, pool.num_blocks)
        payload = jnp.where(dirty_cur[:, None, None, :, None], pool.data[src], 0)
        pool = pool_lib.write_blocks(pool, new_bid, payload, mask=need_copy)
    else:
        # Rows that don't COW read the dump row instead of materializing a
        # live block's copy (same masked-gather fix as store._write_impl).
        src = jnp.where(need_copy, cur, pool.num_blocks)
        pool = pool_lib.write_blocks(pool, new_bid, pool.data[src], mask=need_copy)
    pool = pool_lib.sub_refs(pool, jnp.where(need_copy, cur, NULL_BLOCK))
    bid = jnp.where(need_block, new_bid, cur)
    tables = cache.tables.at[rows, idx].set(
        jnp.where(mask, bid, cache.tables[rows, idx])
    )
    if cfg.delta_cow:
        # Delta bookkeeping for rows whose resolved block is a delta
        # page: fresh pages are full, COW rows attach to root, in-place
        # rows keep their parent.  The incoming token's slot is marked
        # dirty *here* — every layer's write_kv then lands in a slot the
        # read path already resolves locally, so write_kv is unchanged.
        # A mask filling up degenerates the page back to a full block.
        pa = jnp.where(need_copy, root, jnp.where(fresh, NULL_BLOCK, par_cur))
        mark = mask & (pa >= 0)
        new_dirty = dirty_cur | (
            jnp.arange(bs, dtype=jnp.int32)[None, :] == pos[:, None]
        )
        deg = mark & jnp.all(new_dirty, axis=1)
        dscat = jnp.where(mark, bid, pool.num_blocks)
        dirty = pool.dirty.at[dscat].set(
            jnp.where(deg[:, None], False, new_dirty), mode="drop"
        )
        parent = pool.parent.at[dscat].set(
            jnp.where(deg, NULL_BLOCK, pa), mode="drop"
        )
        pool = pool._replace(dirty=dirty, parent=parent)
        pool = pool_lib.sub_refs(pool, jnp.where(deg, pa, NULL_BLOCK))
    return PagedKVCache(pool=pool, tables=tables, lengths=cache.lengths), bid, pos


def write_kv(
    cfg: KVCacheConfig,
    cache: PagedKVCache,
    bid: jax.Array,  # [S] from ensure_writable
    pos: jax.Array,  # [S]
    layer,
    k: jax.Array,  # [S, KVH, hd]
    v: jax.Array,
    mask: jax.Array,
) -> PagedKVCache:
    sid = jnp.where(mask & (bid >= 0), bid, cache.pool.num_blocks)
    dt = cache.pool.data.dtype
    data = cache.pool.data.at[sid, layer, 0, pos].set(
        k.reshape(k.shape[0], -1).astype(dt), mode="drop"
    )
    data = data.at[sid, layer, 1, pos].set(
        v.reshape(v.shape[0], -1).astype(dt), mode="drop"
    )
    # Masked rows landed in the dump row; re-zero its touched layer so
    # the kept-zero dump-row contract (repro.core.pool) holds here too.
    data = data.at[cache.pool.num_blocks, layer].set(0)
    return cache._replace(pool=cache.pool._replace(data=data))


def advance(cache: PagedKVCache, mask: jax.Array) -> PagedKVCache:
    return cache._replace(lengths=cache.lengths + jnp.where(mask, 1, 0))


def layer_views(
    cfg: KVCacheConfig, cache: PagedKVCache, layer
) -> Tuple[jax.Array, jax.Array]:
    """(k_pool, v_pool) as [num_blocks + 1, bs, KVH, hd] for paged
    attention (the trailing dump row is unreachable through any table)."""
    data = cache.pool.data
    shape = (data.shape[0], cfg.block_size, cfg.n_kv_heads, cfg.head_dim)
    return data[:, layer, 0].reshape(shape), data[:, layer, 1].reshape(shape)


def used_blocks(cache: PagedKVCache) -> jax.Array:
    return pool_lib.blocks_in_use(cache.pool)


def free_blocks(cache: PagedKVCache) -> jax.Array:
    """Allocation headroom in pages (the free-stack depth)."""
    return cache.pool.free_top


def oom_flag(cache: PagedKVCache) -> jax.Array:
    """Sticky allocation-failure flag: when set, page writes have been
    dropped to the dump row and decoded logits are not trustworthy."""
    return cache.pool.oom


def grow(cache: PagedKVCache, new_num_blocks: int) -> PagedKVCache:
    """Expand the page pool (DESIGN.md §3.1); block ids are preserved so
    sequence tables stay valid verbatim.  Host-boundary op: the pool
    shape changes, so the jitted decode step recompiles (shape-keyed) —
    call between decode steps, e.g. when ``free_blocks`` dips under the
    per-step worst case of one page per active sequence."""
    return cache._replace(pool=pool_lib.grow(cache.pool, new_num_blocks))


def compact(cache: PagedKVCache, new_num_blocks: int | None = None) -> PagedKVCache:
    """Relocate live pages to a dense prefix and rewrite the sequence
    tables (optionally shrinking to fit) — observationally invisible to
    paged attention, which only ever reads through the tables."""
    pool, remap = pool_lib.compact(cache.pool, new_num_blocks)
    return cache._replace(
        pool=pool, tables=pool_lib.remap_tables(cache.tables, remap)
    )


def free(cache: PagedKVCache, mask: jax.Array) -> PagedKVCache:
    """Release sequences (refcount GC reclaims unshared blocks)."""
    drop = jnp.where(mask[:, None], cache.tables, NULL_BLOCK)
    pool = pool_lib.sub_refs(cache.pool, drop)
    tables = jnp.where(mask[:, None], NULL_BLOCK, cache.tables)
    lengths = jnp.where(mask, 0, cache.lengths)
    return PagedKVCache(pool=pool, tables=tables, lengths=lengths)
