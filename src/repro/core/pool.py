"""Refcounted block pool — the TPU-native substrate for lazy object copy.

This is the array-world adaptation of the paper's platform (see DESIGN.md
§2): payload lives in fixed-capacity *blocks* (slabs) of a pre-allocated
pool; "objects" are block tables holding indices into the pool; the
paper's operations map as

=====================  ====================================================
paper                  here
=====================  ====================================================
vertex                 block (a row of ``data``)
edge / lazy pointer    a block-table entry (index into the pool)
``R`` (read-only set)  ``frozen`` bitmask
``DEEP-COPY``          refcount increments on a gathered table (O(1) data)
``GET`` (write)        :func:`~repro.core.store` COW append/write
``FREEZE``             ``freeze`` (marks blocks read-only)
reference-count GC     ``refcount``; blocks with refcount 0 are free
single-reference opt   in-place write when ``refcount == 1``
=====================  ====================================================

Everything here is functional and jittable: fixed shapes, no host
round-trips.  Failed allocations surface through the ``oom`` flag rather
than raising, so the caller can handle exhaustion under jit.  The pool is
*not* permanently fixed-capacity, though: the lifecycle layer
(DESIGN.md §3.1) handles exhaustion at host boundaries — :func:`grow`
expands capacity while preserving every block id, refcount, frozen bit
and the pop order of the free stack (the paper's objects are "of random,
and possibly unbounded, size", and Birch's reference-counting GC runs
over a growable heap), and :func:`compact` relocates the live blocks to
a dense ascending prefix (optionally shrinking to fit), returning the
old→new id remap so owners can rewrite their block tables.  Both change
array shapes, so they recompile downstream jits — callers invoke them
*between* jitted generations, never inside one.

Allocation (DESIGN.md §3) pops from a maintained **free stack**: a
``[num_blocks] int32`` array of free block ids plus a ``free_top``
count, updated incrementally by :func:`alloc` (pops) and
:func:`sub_refs` (pushes blocks whose refcount drops to zero).  An
``alloc`` is therefore O(n) gathers instead of the O(num_blocks)
``jnp.nonzero`` free-scan it used to be; the scan survives as the
debug/verify path (:func:`alloc_scan`, :func:`free_stack_consistent`).
Stack invariant: ``free_stack[:free_top]`` holds exactly the ids with
``refcount == 0``, each once.  The one operation that could silently
break it is :func:`add_refs` resurrecting a freed block (refcount
0 -> 1 leaves a stale id in the stack); every caller in this repo only
ever ``add_refs`` blocks reachable from a live table, which by
construction have refcount >= 1.

Masked/NULL entries in every data scatter are routed to the pool's
**dump row** — ``data`` carries ``num_blocks + 1`` rows, and row
``num_blocks`` is a write-only garbage slab that no table can reference
— so duplicate indices cannot clobber live blocks, and the Pallas write
kernels (:mod:`repro.kernels.cow_write`) have an always-safe destination
for masked-out grid steps.  Bookkeeping scatters (refcount / frozen)
still use ``mode="drop"`` on exactly-sized arrays.

The pool composes with ``shard_map``: each device shard owns an
independent pool (per-shard free stacks, no cross-device allocation),
the same way the paper gives each thread its own context stack.  That
composition is built in :mod:`repro.distributed.sharded_store` and
documented in DESIGN.md §6; only trajectories whose resampling ancestor
lives on another shard ever move between pools.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "BlockPool",
    "init",
    "alloc",
    "alloc_scan",
    "alloc_compact",
    "add_refs",
    "sub_refs",
    "release_parents",
    "parent_or_self",
    "freeze",
    "write_blocks",
    "read_blocks",
    "blocks_in_use",
    "blocks_free",
    "grow",
    "compact",
    "next_capacity",
    "remap_tables",
    "push_free_mask",
    "rebuild_free_stack",
    "free_stack_consistent",
    "refcount_matches_tables",
    "check_invariants",
    "NULL_BLOCK",
]

# A NumPy scalar, not a jnp one: same int32 promotion everywhere, and
# importing the pool does not start JAX's backends.
NULL_BLOCK = np.int32(-1)


class BlockPool(NamedTuple):
    """A pool of reference-counted payload blocks.

    Attributes:
      data:       ``[num_blocks + 1, *block_shape]`` payload slabs; the
                  trailing row is the write-only dump row (see module
                  docstring) and is never addressed by a table.
      refcount:   ``[num_blocks] int32`` — 0 means free.
      frozen:     ``[num_blocks] bool`` — the paper's read-only set ``R``.
                  Only consulted in ``CopyMode.LAZY`` (no single-reference
                  optimization); ``LAZY_SR`` uses ``refcount == 1`` instead.
      free_stack: ``[num_blocks] int32`` — LIFO stack of free block ids;
                  ``free_stack[:free_top]`` is exactly the free set.
      free_top:   scalar int32 — number of live entries in ``free_stack``.
      oom:        scalar bool, sticky: an allocation ever failed.
      parent:     ``[num_blocks] int32`` — sub-block delta COW backing
                  block (DESIGN.md §3.2).  ``NULL_BLOCK`` for a *full*
                  block (payload complete in ``data``); a non-NULL entry
                  makes the block a *delta* block whose non-dirty slots
                  resolve through the parent.  Parents are always full
                  blocks (delta depth <= 1) and each delta child holds
                  exactly one refcount reference on its parent.  With
                  ``delta_cow`` off this stays all-NULL and every
                  operation below is value-identical to the pre-delta
                  pool.
      dirty:      ``[num_blocks, npos] bool`` — per-slot dirty mask along
                  the block's position axis.  For a delta block,
                  ``dirty[b, p]`` means slot ``p`` is materialized in
                  ``data[b]``; non-dirty slots of ``data[b]`` are kept
                  zero so pools stay leaf-comparable across write paths.
                  Full blocks carry an all-False mask.
    """

    data: jax.Array
    refcount: jax.Array
    frozen: jax.Array
    free_stack: jax.Array
    free_top: jax.Array
    oom: jax.Array
    parent: jax.Array
    dirty: jax.Array

    @property
    def num_blocks(self) -> int:
        return self.data.shape[0] - 1

    @property
    def block_shape(self) -> Tuple[int, ...]:
        return self.data.shape[1:]


def init(
    num_blocks: int,
    block_shape: Sequence[int],
    dtype: jnp.dtype = jnp.float32,
    npos: int | None = None,
) -> BlockPool:
    """Create an empty pool of ``num_blocks`` blocks (+ the dump row).

    The free stack is seeded descending so pops hand out ascending block
    ids — the same order the legacy ``nonzero`` scan produced on an
    empty pool.  ``npos`` sizes the per-block dirty mask (the length of
    the block's position axis); it defaults to ``block_shape[0]``, which
    is right for the store's ``[block_size, *item]`` blocks — the KV
    cache passes its own position axis explicitly.
    """
    block_shape = tuple(block_shape)
    if npos is None:
        npos = block_shape[0] if block_shape else 1
    return BlockPool(
        data=jnp.zeros((num_blocks + 1, *block_shape), dtype=dtype),
        refcount=jnp.zeros((num_blocks,), dtype=jnp.int32),
        frozen=jnp.zeros((num_blocks,), dtype=jnp.bool_),
        free_stack=jnp.arange(num_blocks - 1, -1, -1, dtype=jnp.int32),
        free_top=jnp.asarray(num_blocks, dtype=jnp.int32),
        oom=jnp.zeros((), dtype=jnp.bool_),
        parent=jnp.full((num_blocks,), NULL_BLOCK, dtype=jnp.int32),
        dirty=jnp.zeros((num_blocks, npos), dtype=jnp.bool_),
    )


def _scatter_ids(
    num_blocks: int, ids: jax.Array, mask: jax.Array | None = None
) -> jax.Array:
    """Route NULL/masked entries to the dump index so scatters skip them.

    Bookkeeping arrays (refcount/frozen/claim) are exactly
    ``num_blocks``-sized and pair this with ``mode="drop"``; ``data``
    scatters land in the dump row instead.
    """
    ok = ids >= 0
    if mask is not None:
        ok = ok & mask
    return jnp.where(ok, ids, num_blocks)


def _gather_ids(ids: jax.Array) -> jax.Array:
    """Clip NULL entries to 0 for gathers (callers mask the result)."""
    return jnp.where(ids >= 0, ids, 0)


def _push_free_ids(
    stack: jax.Array, top: jax.Array, ids: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Push non-NULL ids (must be distinct, and absent from the stack)."""
    with jax.named_scope("pool.free_push"):
        valid = ids >= 0
        rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
        pos = jnp.where(valid, top + rank, stack.shape[0])
        stack = stack.at[pos].set(ids, mode="drop")
        return stack, top + jnp.sum(valid, dtype=jnp.int32)


def push_free_mask(
    stack: jax.Array, top: jax.Array, freed: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Push every block selected by ``freed`` (``[num_blocks] bool``).

    The mask-shaped push used by the fused clone bookkeeping
    (:mod:`repro.kernels.refcount_update` emits the newly-freed mask in
    the same pass that computes the refcount delta).  Ids are pushed in
    ascending order; the caller guarantees none is already in the stack.
    """
    nb = stack.shape[0]
    with jax.named_scope("pool.free_push"):
        ids = jnp.arange(nb, dtype=jnp.int32)
        rank = jnp.cumsum(freed.astype(jnp.int32)) - 1
        pos = jnp.where(freed, top + rank, nb)
        stack = stack.at[pos].set(ids, mode="drop")
        return stack, top + jnp.sum(freed, dtype=jnp.int32)


def alloc(
    pool: BlockPool, n: int, commit: jax.Array | None = None
) -> Tuple[BlockPool, jax.Array]:
    """Allocate up to ``n`` blocks (static ``n``) by popping the free stack.

    Returns the top ``n`` free block ids.  ``commit`` (``[n] bool``,
    default all-true) selects which candidates are actually committed
    (refcount set to 1, unfrozen); uncommitted candidates are pushed
    straight back, which lets callers over-provision candidates for
    data-dependent allocation counts without host synchronization.

    Committed entries of the returned index vector are valid block ids;
    uncommitted entries come back as ``NULL_BLOCK``.  If fewer blocks are
    free than committed requests, the ``oom`` flag goes sticky and the
    unsatisfied entries come back as ``NULL_BLOCK``.

    Cost: O(n) gathers/scatters — no pass over the pool.  The legacy
    free-scan survives as :func:`alloc_scan`.
    """
    with jax.named_scope("pool.alloc"):
        if commit is None:
            commit = jnp.ones((n,), dtype=jnp.bool_)
        nb = pool.num_blocks
        top = pool.free_top
        i = jnp.arange(n, dtype=jnp.int32)
        have = i < top
        cand_pos = jnp.clip(top - 1 - i, 0, max(nb - 1, 0))
        cand = jnp.where(have, pool.free_stack[cand_pos], NULL_BLOCK)
        ok = have & commit
        sids = _scatter_ids(nb, cand, ok)
        refcount = pool.refcount.at[sids].add(1, mode="drop")
        frozen = pool.frozen.at[sids].set(False, mode="drop")
        parent = pool.parent.at[sids].set(NULL_BLOCK, mode="drop")
        dirty = pool.dirty.at[sids].set(False, mode="drop")
        oom = pool.oom | jnp.any(commit & ~have)
        # Remove the committed candidates from the stack window, compacting
        # the uncommitted survivors downward in their original relative
        # order — an alloc whose commits all fail is a bit-exact no-op, which
        # the sharded store's fixed-shape exchange relies on (its all-local
        # steps still trace an alloc_compact of zero blocks).
        keep = have & ~commit
        kept = jnp.cumsum(keep.astype(jnp.int32))
        base = top - jnp.sum(have, dtype=jnp.int32)
        tgt = jnp.where(keep, base + (kept[-1] - kept), nb)
        stack = pool.free_stack.at[tgt].set(cand, mode="drop")
        top = top - jnp.sum(ok, dtype=jnp.int32)
        out_ids = jnp.where(ok, cand, NULL_BLOCK)
        pool = pool._replace(
            refcount=refcount,
            frozen=frozen,
            oom=oom,
            free_stack=stack,
            free_top=top,
            parent=parent,
            dirty=dirty,
        )
        return pool, out_ids


def alloc_scan(
    pool: BlockPool, n: int, commit: jax.Array | None = None
) -> Tuple[BlockPool, jax.Array]:
    """Debug/verify allocator: the legacy O(num_blocks) ``nonzero`` scan.

    Same contract as :func:`alloc`; candidates are the *lowest* free ids
    instead of the stack top.  Rebuilds the free stack canonically
    afterwards so the two allocators can interleave.
    """
    if commit is None:
        commit = jnp.ones((n,), dtype=jnp.bool_)
    free = pool.refcount == 0
    cand = jnp.nonzero(free, size=n, fill_value=-1)[0].astype(jnp.int32)
    ok = (cand >= 0) & commit
    sids = _scatter_ids(pool.num_blocks, cand, ok)
    refcount = pool.refcount.at[sids].add(1, mode="drop")
    frozen = pool.frozen.at[sids].set(False, mode="drop")
    parent = pool.parent.at[sids].set(NULL_BLOCK, mode="drop")
    dirty = pool.dirty.at[sids].set(False, mode="drop")
    oom = pool.oom | jnp.any(commit & (cand < 0))
    out_ids = jnp.where(ok, cand, NULL_BLOCK)
    pool = pool._replace(
        refcount=refcount, frozen=frozen, oom=oom, parent=parent, dirty=dirty
    )
    return rebuild_free_stack(pool), out_ids


def alloc_compact(
    pool: BlockPool, n: int, commit: jax.Array
) -> Tuple[BlockPool, jax.Array]:
    """Like :func:`alloc`, but with rank-compacted candidate assignment.

    :func:`alloc` pairs request ``i`` with the ``i``-th candidate popped
    off the free stack, so a *sparse* commit mask can exhaust the
    candidate list while most of the pool is still free (a committed
    request at position ``i`` needs at least ``i + 1`` free blocks).
    Here committed requests are packed by their rank
    ``cumsum(commit) - 1`` onto the first candidates, so allocation
    succeeds whenever ``sum(commit)`` blocks are free — the shape the
    sharded store's trajectory imports need, where the commit mask is
    scattered over a ``[n_particles, max_blocks]`` grid.  Each shard
    pops from its own free stack (per-shard pools, DESIGN.md §6).
    """
    total = jnp.sum(commit)
    prefix = jnp.arange(n, dtype=jnp.int32) < total
    pool, cand = alloc(pool, n, commit=prefix)
    rank = jnp.cumsum(commit) - 1
    picked = cand[jnp.where(commit, rank, 0)]
    return pool, jnp.where(commit, picked, NULL_BLOCK)


def add_refs(pool: BlockPool, ids: jax.Array, amount: jax.Array | int = 1) -> BlockPool:
    """Increment refcounts (the bookkeeping half of a lazy deep copy).

    ``ids`` may contain repeats and ``NULL_BLOCK`` entries (ignored).
    Every id must reference a *live* block (refcount >= 1): resurrecting
    a freed block would leave a stale entry in the free stack.  All
    in-repo callers satisfy this by construction — they only add refs to
    blocks reachable from a live table.
    """
    ids = ids.reshape(-1)
    amt = jnp.broadcast_to(jnp.asarray(amount, jnp.int32), ids.shape)
    sids = _scatter_ids(pool.num_blocks, ids)
    refcount = pool.refcount.at[sids].add(amt, mode="drop")
    return pool._replace(refcount=refcount)


def _sub_refs_level(
    pool: BlockPool, ids: jax.Array, amount: jax.Array | int = 1
) -> Tuple[BlockPool, jax.Array]:
    """One refcount-decrement pass; returns the deduplicated freed ids.

    The freed array is ``ids``-shaped with ``NULL_BLOCK`` in every slot
    that did not free a block (and in all but the first occurrence of a
    repeated id, so each freed block appears exactly once).
    """
    ids = ids.reshape(-1)
    k = ids.shape[0]
    amt = jnp.broadcast_to(jnp.asarray(amount, jnp.int32), ids.shape)
    nb = pool.num_blocks
    sids = _scatter_ids(nb, ids)
    refcount = pool.refcount.at[sids].add(-amt, mode="drop")
    gids = _gather_ids(ids)
    flip = (ids >= 0) & (pool.refcount[gids] > 0) & (refcount[gids] == 0)
    # One push per freed block: the first occurrence of each id claims it.
    order = jnp.arange(k, dtype=jnp.int32)
    claim = jnp.full((nb + 1,), k, dtype=jnp.int32).at[sids].min(order, mode="drop")
    rep = flip & (claim[gids] == order)
    freed = jnp.where(rep, ids, NULL_BLOCK)
    stack, top = _push_free_ids(pool.free_stack, pool.free_top, freed)
    pool = pool._replace(refcount=refcount, free_stack=stack, free_top=top)
    return pool, freed


def sub_refs(pool: BlockPool, ids: jax.Array, amount: jax.Array | int = 1) -> BlockPool:
    """Decrement refcounts; blocks hitting zero are freed onto the stack.

    (``refcount == 0`` *is* the free set — rule 4 of the paper's count
    scheme collapses to this in a cycle-free pool.)  The newly-freed ids
    are pushed incrementally: O(k) work for ``k = ids.size``, with a
    first-occurrence claim pass deduplicating repeated ids, rather than
    any rescan of the pool.

    Delta cascade (DESIGN.md §3.2): a freed *delta* block releases the
    single reference it held on its parent, which may free the parent in
    turn.  Parents are always full blocks (delta depth <= 1), so the
    cascade terminates after one extra level; the freed children's
    ``parent``/``dirty`` bookkeeping is cleared.  With all-NULL parents
    (``delta_cow`` off) both extra passes are value-level no-ops.
    """
    pool, freed = _sub_refs_level(pool, ids, amount)
    parents = jnp.where(freed >= 0, pool.parent[_gather_ids(freed)], NULL_BLOCK)
    pool, _ = _sub_refs_level(pool, parents, 1)
    sids = _scatter_ids(pool.num_blocks, freed)
    parent = pool.parent.at[sids].set(NULL_BLOCK, mode="drop")
    dirty = pool.dirty.at[sids].set(False, mode="drop")
    return pool._replace(parent=parent, dirty=dirty)


def release_parents(pool: BlockPool, freed: jax.Array) -> BlockPool:
    """Cascade a mask-shaped free (:func:`push_free_mask` callers) to the
    delta parents.

    ``freed`` is a ``[num_blocks] bool`` mask of blocks that were just
    freed by a table-reference pass (fused clone bookkeeping, KV slot
    release).  Each freed *delta* child releases the one reference it
    held on its parent; parents whose refcount hits zero are pushed onto
    the free stack, and the freed children's ``parent``/``dirty``
    bookkeeping is cleared.  Two-phase safe: a parent still holding
    child references cannot have been freed by the table pass, so no id
    is pushed twice.  With all-NULL parents this is a value-level no-op.
    """
    nb = pool.num_blocks
    child_par = jnp.where(freed, pool.parent, NULL_BLOCK)
    sids = _scatter_ids(nb, child_par)
    drops = jnp.zeros((nb,), jnp.int32).at[sids].add(1, mode="drop")
    refcount = pool.refcount - drops
    newly = (drops > 0) & (pool.refcount > 0) & (refcount == 0)
    stack, top = push_free_mask(pool.free_stack, pool.free_top, newly)
    parent = jnp.where(freed, NULL_BLOCK, pool.parent)
    dirty = jnp.where(freed[:, None], False, pool.dirty)
    return pool._replace(
        refcount=refcount,
        free_stack=stack,
        free_top=top,
        parent=parent,
        dirty=dirty,
    )


def parent_or_self(pool: BlockPool, ids: jax.Array) -> jax.Array:
    """Resolve table entries to the block holding their *base* payload.

    Full blocks resolve to themselves, delta blocks to their parent;
    NULL entries stay NULL.  Read paths pair this with the ``dirty``
    mask: ``out[p] = dirty[b, p] ? data[b, p] : data[parent_or_self(b), p]``.
    """
    par = pool.parent[_gather_ids(ids)]
    return jnp.where((ids >= 0) & (par >= 0), par, ids)


def freeze(pool: BlockPool, ids: jax.Array) -> BlockPool:
    """Mark blocks read-only — Algorithm 7's FREEZE over a table.

    Used by ``CopyMode.LAZY``; ``LAZY_SR`` relies on refcounts alone
    (Remark 1 makes the frozen bit redundant for in-degree-1 blocks, which
    is every exclusively-owned block).
    """
    sids = _scatter_ids(pool.num_blocks, ids.reshape(-1))
    frozen = pool.frozen.at[sids].set(True, mode="drop")
    return pool._replace(frozen=frozen)


def write_blocks(
    pool: BlockPool, ids: jax.Array, values: jax.Array, mask: jax.Array | None = None
) -> BlockPool:
    """Overwrite whole blocks (``values: [k, *block_shape]``), masked.

    Valid (unmasked, non-NULL) ids must be distinct; masked/NULL rows
    land in the dump row rather than a live block.  The dump row is
    re-zeroed afterwards, so pools stay comparable leaf-for-leaf across
    code paths that differ only in dropped writes.
    """
    ids = ids.reshape(-1)
    sids = _scatter_ids(pool.num_blocks, ids, mask)
    data = pool.data.at[sids].set(values, mode="drop")
    data = data.at[pool.num_blocks].set(0)
    return pool._replace(data=data)


def read_blocks(pool: BlockPool, ids: jax.Array) -> jax.Array:
    """Gather whole blocks; NULL ids return block 0 (callers mask)."""
    out = pool.data[_gather_ids(ids.reshape(-1))]
    return out.reshape(ids.shape + pool.block_shape)


def blocks_in_use(pool: BlockPool) -> jax.Array:
    """Number of live blocks — the memory metric of the paper's Figures 5-7."""
    return jnp.sum(pool.refcount > 0)


def blocks_free(pool: BlockPool) -> jax.Array:
    """Allocation headroom.  Per-shard headroom matters for the sharded
    store (DESIGN.md §6): cross-shard imports land as fresh allocations on
    the *importing* shard, so a skewed resampling step consumes headroom
    there even while global occupancy is flat."""
    return jnp.sum(pool.refcount == 0)


def grow(pool: BlockPool, new_num_blocks: int) -> BlockPool:
    """Expand capacity to ``new_num_blocks`` blocks (DESIGN.md §3.1).

    A host-boundary operation: the array shapes change, so anything jitted
    over the pool recompiles (shape-keyed) — call it *between* jitted
    generations, never inside one.  Everything observable is preserved:

    * block ids, payload, refcounts and frozen bits are unchanged, so
      existing block tables stay valid verbatim;
    * the kept-zero dump row moves to the new ``num_blocks`` index (the
      old dump index becomes an ordinary free block, zero-filled like any
      freshly allocated block);
    * the live free stack keeps its exact pop order; the fresh ids are
      inserted *below* it (descending, so they pop ascending), which means
      recently-freed hot blocks are still reused before cold new ones;
    * ``oom`` stays sticky — growth adds headroom, it does not declare
      that no allocation ever failed.  Callers that roll back to a
      pre-OOM checkpoint (the filter's lifecycle loop) grow the clean
      checkpoint, so the flag they carry forward is genuine.
    """
    nb = pool.num_blocks
    if new_num_blocks < nb:
        raise ValueError(
            f"grow cannot shrink: {new_num_blocks} < {nb} (use compact "
            "with new_num_blocks for shrink-to-fit)"
        )
    if new_num_blocks == nb:
        return pool
    g = new_num_blocks - nb
    data = jnp.zeros((new_num_blocks + 1, *pool.block_shape), dtype=pool.data.dtype)
    data = data.at[:nb].set(pool.data[:nb])
    refcount = jnp.zeros((new_num_blocks,), jnp.int32).at[:nb].set(pool.refcount)
    frozen = jnp.zeros((new_num_blocks,), jnp.bool_).at[:nb].set(pool.frozen)
    parent = (
        jnp.full((new_num_blocks,), NULL_BLOCK, jnp.int32).at[:nb].set(pool.parent)
    )
    dirty = (
        jnp.zeros((new_num_blocks, pool.dirty.shape[1]), jnp.bool_)
        .at[:nb]
        .set(pool.dirty)
    )
    fresh = jnp.arange(new_num_blocks - 1, nb - 1, -1, dtype=jnp.int32)
    stack = jnp.concatenate([fresh, pool.free_stack])
    return BlockPool(
        data=data,
        refcount=refcount,
        frozen=frozen,
        free_stack=stack,
        free_top=pool.free_top + g,
        oom=pool.oom,
        parent=parent,
        dirty=dirty,
    )


def next_capacity(num_blocks: int, demand: int, cap: int, factor: float) -> int:
    """The growth-sizing policy (DESIGN.md §3.1), shared by every
    lifecycle driver: geometric growth (so total relocation traffic
    telescopes) covering at least ``demand`` more blocks, capped at
    ``cap`` — the dense bound beyond which allocation cannot fail."""
    return min(cap, max(int(num_blocks * factor), num_blocks + demand))


def remap_tables(tables: jax.Array, remap: jax.Array) -> jax.Array:
    """Rewrite block tables through a :func:`compact` remap; NULL entries
    stay NULL (and a dropped block maps to NULL, never out of range)."""
    return jnp.where(
        tables >= 0, remap[jnp.where(tables >= 0, tables, 0)], NULL_BLOCK
    )


def compact(
    pool: BlockPool,
    new_num_blocks: int | None = None,
    use_kernel: bool | None = None,
) -> Tuple[BlockPool, jax.Array]:
    """Relocate live blocks to a dense ascending prefix (DESIGN.md §3.1).

    Returns ``(pool, remap)`` where ``remap[old_id]`` is the block's new
    id (``NULL_BLOCK`` for free blocks); the caller must rewrite every
    block table through it (``store.compact`` / ``kv_cache.compact`` do).
    Payload relocation is one :func:`repro.kernels.cow_gather.pool_compact`
    pass; bookkeeping is rewritten in the same single sweep, and the free
    stack comes back canonical (free ids descending).  Compaction is
    observationally invisible — a table read through the remap yields
    bit-identical payload — but it densifies HBM locality and, with
    ``new_num_blocks``, shrinks the pool to fit.

    Like :func:`grow` this is a host-boundary shape-changing op when
    ``new_num_blocks`` is given; with the default capacity it is jittable
    (fixed shapes) but still an O(num_blocks) pass, not hot-path work.
    If ``new_num_blocks`` is too small for the live set the pool comes
    back with ``oom`` set (blocks are never silently dropped: the remap
    and relocation keep every live block whose new id fits; callers
    should treat the flag as "shrink refused, retry bigger").
    """
    from repro.kernels.cow_gather import pool_compact

    nb = pool.num_blocks
    target = nb if new_num_blocks is None else new_num_blocks
    live = pool.refcount > 0
    n_live = jnp.sum(live, dtype=jnp.int32)
    remap = jnp.where(
        live, jnp.cumsum(live.astype(jnp.int32), dtype=jnp.int32) - 1, NULL_BLOCK
    )
    # A too-small shrink maps the overflow to NULL (and flags oom below)
    # rather than leaving out-of-range ids in the caller's tables.
    remap = jnp.where(remap < target, remap, NULL_BLOCK)
    # perm: old id feeding each new slot (NULL -> stays empty/zero).
    perm = jnp.nonzero(live, size=nb, fill_value=-1)[0].astype(jnp.int32)
    if target < nb:
        perm = perm[:target]
    elif target > nb:
        perm = jnp.concatenate(
            [perm, jnp.full((target - nb,), NULL_BLOCK, jnp.int32)]
        )
    data = pool_compact(pool.data, perm, use_kernel=use_kernel)
    safe = jnp.where(perm >= 0, perm, 0)
    refcount = jnp.where(perm >= 0, pool.refcount[safe], 0)
    frozen = jnp.where(perm >= 0, pool.frozen[safe], False)
    # Delta bookkeeping relocates with the block: rows permute like
    # refcount, and parent *values* are ids, so they go through the
    # remap (a live child's parent is live — the child's reference
    # keeps it so — hence never remaps to NULL).
    par_old = jnp.where(perm >= 0, pool.parent[safe], NULL_BLOCK)
    parent = remap_tables(par_old, remap)
    dirty = jnp.where((perm >= 0)[:, None], pool.dirty[safe], False)
    # Canonical stack over the dense free suffix: ids descending so pops
    # hand out ascending ids, same as a fresh pool.
    n_free = jnp.maximum(target - n_live, 0)
    slot = jnp.arange(target, dtype=jnp.int32)
    stack = jnp.where(slot < n_free, target - 1 - slot, NULL_BLOCK)
    oom = pool.oom | (n_live > target)
    pool = BlockPool(
        data=data,
        refcount=refcount,
        frozen=frozen,
        free_stack=stack,
        free_top=n_free,
        oom=oom,
        parent=parent,
        dirty=dirty,
    )
    return pool, remap


def rebuild_free_stack(pool: BlockPool) -> BlockPool:
    """Recompute the canonical free stack from the refcount mask.

    O(num_blocks); used by :func:`alloc_scan` (the debug allocator) and
    available to tests.  Canonical form: free ids descending, so pops
    yield ascending ids.
    """
    nb = pool.num_blocks
    free = pool.refcount == 0
    count = jnp.sum(free, dtype=jnp.int32)
    asc = jnp.nonzero(free, size=nb, fill_value=-1)[0].astype(jnp.int32)
    pos = jnp.clip(count - 1 - jnp.arange(nb, dtype=jnp.int32), 0, max(nb - 1, 0))
    stack = jnp.where(jnp.arange(nb, dtype=jnp.int32) < count, asc[pos], NULL_BLOCK)
    return pool._replace(free_stack=stack, free_top=count)


def free_stack_consistent(pool: BlockPool) -> jax.Array:
    """Scalar bool: does the free stack agree with the refcount mask?

    True iff ``free_stack[:free_top]`` contains exactly the ids with
    ``refcount == 0``, each once.  The verify half of the debug path —
    jittable, used by the allocator property tests.
    """
    nb = pool.num_blocks
    live = jnp.arange(nb, dtype=jnp.int32) < pool.free_top
    ids = pool.free_stack
    valid = jnp.all(~live | (ids >= 0))
    sids = _scatter_ids(nb, jnp.where(live, ids, NULL_BLOCK))
    counts = jnp.zeros((nb,), jnp.int32).at[sids].add(1, mode="drop")
    free = (pool.refcount == 0).astype(jnp.int32)
    return (valid & (pool.free_top == jnp.sum(free)) & jnp.all(counts == free))


def refcount_matches_tables(pool: BlockPool, tables: jax.Array) -> jax.Array:
    """Scalar bool: refcount conservation against the reference holders.

    Every non-NULL table entry is one reference; conservation says the
    pool's refcount vector equals the histogram of table entries — no
    leaked block (refcount > references: never reclaimed) and no
    premature free (refcount < references: a live page can be handed
    out again).  Jittable; the serving watchdog runs it at token
    boundaries (DESIGN.md §10) over the KV cache's tables.
    """
    nb = pool.num_blocks
    sids = _scatter_ids(nb, tables.reshape(-1).astype(jnp.int32))
    counts = jnp.zeros((nb,), jnp.int32).at[sids].add(1, mode="drop")
    # Each delta child holds one refcount reference on its parent
    # (DESIGN.md §3.2) — count those alongside the table references.
    psids = _scatter_ids(nb, pool.parent)
    counts = counts.at[psids].add(1, mode="drop")
    return jnp.all(counts == pool.refcount)


def check_invariants(
    pool: BlockPool, tables: Optional[jax.Array] = None
) -> List[str]:
    """Run every conservation law over one pool; return the violations.

    The host-side face of the verify path: wraps the jittable predicates
    (:func:`free_stack_consistent` and :func:`refcount_matches_tables`)
    behind one call returning human-readable violation messages — empty
    means clean.  ``tables`` is the optional reference-holder array
    (block tables / trajectory tables); without it only the
    table-independent laws run.  The sticky OOM flag is *not* a
    violation — exhaustion is a legitimate state with its own handling
    path (DESIGN.md §4).  The serving watchdog and the lifecycle tests
    both gate on this.
    """
    problems: List[str] = []
    if not bool(free_stack_consistent(pool)):
        problems.append("free stack disagrees with the refcount mask")
    if tables is not None and not bool(refcount_matches_tables(pool, tables)):
        problems.append("refcount/table reference conservation violated")
    return problems
