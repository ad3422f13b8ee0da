"""ParticleStore: population state with lazy-copy semantics, in JAX.

This is the platform the paper builds, specialized to the array world: a
population of N particles, each owning an append-only (but mutable —
see :func:`write_at`) sequence of items, cloned wholesale at every
resampling step.  Three storage strategies implement the paper's three
evaluation configurations (Section 4):

``CopyMode.EAGER``
    Dense storage ``[N, capacity, *item]``.  ``clone`` physically gathers
    full trajectories (``O(N·T·D)`` per generation — the paper's eager
    deep copy), appends are trivially in place.

``CopyMode.LAZY``
    Block-pool storage.  ``clone`` gathers block *tables* and bumps
    refcounts (O(N·T/B) bookkeeping, zero payload movement — the lazy
    deep copy of Algorithm 3), and *freezes* every block reachable from
    the new generation (Algorithm 7).  A write to a frozen block copies
    it first (Algorithm 5's GET→COPY), even when the writer is the sole
    owner.

``CopyMode.LAZY_SR``
    As LAZY, plus the single-reference optimization of Remark 1: blocks
    with ``refcount == 1`` are written in place (no frozen bit, no copy),
    which is exactly the "thaw for reuse" of Section 3.

The correspondence to the object-graph semantics of
:mod:`repro.core.graph` is: a particle's block table is its fully-Pulled
edge set; because resampling always clones *live* particles (the paper's
motivating tree-structured pattern), the memo chase of Algorithm 4 can be
pre-resolved at clone time, and cross references cannot arise.  The eager
escape hatch that the paper needs for particle-Gibbs reference
trajectories (its VBD experiment) is :func:`materialize`.

All operations are functional, fixed-shape, and jittable; the store
config is a hashable static argument.

DESIGN.md §2 tabulates the full paper→array-world correspondence this
module realizes; §3 specifies the kernelized write path (free-stack
allocation, fused COW write, single-pass clone bookkeeping — the
``use_kernels`` switch); §6 describes how the store scales across devices
(:mod:`repro.distributed.sharded_store`), for which this module supplies
the per-shard halves of the resampling exchange: :func:`clone_partial`
(lazy, within-shard), :func:`materialize_batch` (export) and
:func:`import_trajectories` (import).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import pool as pool_lib
from repro.core.config import CopyMode
from repro.core.pool import NULL_BLOCK, BlockPool
from repro.kernels.clone_chain import clone_chain as clone_chain_op
from repro.kernels.cow_gather import cow_gather
from repro.kernels.cow_write import cow_write
from repro.kernels.refcount_update import refcount_update

__all__ = [
    "StoreConfig",
    "ParticleStore",
    "create",
    "append",
    "write_at",
    "clone",
    "clone_chain",
    "clone_partial",
    "read_at",
    "read_last",
    "trajectory",
    "materialize",
    "materialize_batch",
    "import_trajectories",
    "used_blocks",
    "oom_flag",
    "free_blocks",
    "grow",
    "compact",
]


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    """Static configuration (hashable; safe as a jit static arg)."""

    mode: CopyMode
    n: int  # number of particles
    block_size: int  # items per block (the COW granularity)
    max_blocks: int  # blocks per particle trajectory
    item_shape: Tuple[int, ...] = ()
    dtype: str = "float32"
    num_blocks: int = 0  # pool capacity; 0 = auto
    # Route the write path / clone bookkeeping / batch materialization
    # through the Pallas kernels (cow_write, refcount_update, cow_gather;
    # DESIGN.md §3).  Interpret mode on non-TPU backends; bit-exact with
    # the fused jnp fallback on every non-dump pool row.
    use_kernels: bool = False
    # Sub-block delta COW (DESIGN.md §3.2): a write to a shared block
    # copies only the slots the writer has materialized (the dirty mask)
    # plus the written item, leaving the rest to resolve through the
    # ``parent`` pointer — write-granular copies instead of
    # block-granular ones.  Observationally equivalent to the
    # whole-block path (valid-prefix trajectories, reads, lengths
    # bit-exact); pool internals differ by construction (delta blocks
    # zero-fill non-dirty slots, and parents outliving their children
    # shift the free-stack order, so allocated block ids diverge).  Off
    # by default: parents stay all-NULL and every op is value-identical
    # to the pre-delta store.
    delta_cow: bool = False
    # Opt-in loud-OOM path (DESIGN.md §3.1): trajectory / materialize /
    # materialize_batch refuse to read from a pool whose sticky ``oom``
    # flag is set — a host-side RuntimeError when called eagerly, a
    # ``checkify.check`` under jit (wrap the caller in
    # ``checkify.checkify`` to discharge it).  Off by default: the flag
    # is still surfaced through :func:`oom_flag` / ``FilterResult.oom``.
    strict_oom: bool = False

    @property
    def capacity(self) -> int:
        return self.block_size * self.max_blocks

    @property
    def pool_blocks(self) -> int:
        if self.num_blocks:
            return self.num_blocks
        # Generous default: the sparse bound T/B + c·N·log N blocks, padded.
        t_term = self.max_blocks
        n_term = (
            int(10 * self.n * max(1.0, math.log(max(self.n, 2)))) // self.block_size
        )
        return min(self.n * self.max_blocks, max(t_term + n_term + 2 * self.n, 64))

    @property
    def pool_blocks_cap(self) -> int:
        """Capacity at which allocation provably cannot fail (DESIGN.md
        §3.1): every particle owns at most ``max_blocks`` blocks, plus one
        transient per particle while a COW source and its copy coexist
        within a write step.  The lifecycle layer's growth ceiling."""
        return self.n * self.max_blocks + self.n


class ParticleStore(NamedTuple):
    """The population state (a pytree; shapes fixed by StoreConfig)."""

    pool: BlockPool  # lazy modes ([0]-block dummy under EAGER)
    dense: jax.Array  # eager mode ([N,0]-shaped dummy under lazy modes)
    tables: jax.Array  # [N, max_blocks] int32 block ids (NULL_BLOCK = unset)
    lengths: jax.Array  # [N] int32
    peak_blocks: jax.Array  # running peak of used_blocks (the memory metric)


def create(cfg: StoreConfig) -> ParticleStore:
    dtype = jnp.dtype(cfg.dtype)
    if cfg.mode is CopyMode.EAGER:
        pool = pool_lib.init(1, (cfg.block_size, *cfg.item_shape), dtype)
        dense = jnp.zeros((cfg.n, cfg.capacity, *cfg.item_shape), dtype)
    else:
        pool = pool_lib.init(
            cfg.pool_blocks, (cfg.block_size, *cfg.item_shape), dtype
        )
        dense = jnp.zeros((cfg.n, 0, *cfg.item_shape), dtype)
    return ParticleStore(
        pool=pool,
        dense=dense,
        tables=jnp.full((cfg.n, cfg.max_blocks), NULL_BLOCK, dtype=jnp.int32),
        lengths=jnp.zeros((cfg.n,), dtype=jnp.int32),
        peak_blocks=jnp.zeros((), dtype=jnp.int32),
    )


def _bump_peak(cfg: StoreConfig, store: ParticleStore) -> ParticleStore:
    with jax.named_scope("store.count"):
        return store._replace(
            peak_blocks=jnp.maximum(store.peak_blocks, used_blocks(cfg, store))
        )


# ---------------------------------------------------------------------------
# writes
# ---------------------------------------------------------------------------


def append(cfg: StoreConfig, store: ParticleStore, values: jax.Array) -> ParticleStore:
    """Append one item per particle (``values: [N, *item]``).

    The write path is the paper's GET: blocks that must not be mutated in
    place are copied first (copy-on-write); fresh blocks are allocated at
    block boundaries.
    """
    with jax.named_scope("store.append"):
        store = _write_impl(cfg, store, store.lengths, values, advance=True)
    return _bump_peak(cfg, store)


def write_at(
    cfg: StoreConfig,
    store: ParticleStore,
    positions: jax.Array,
    values: jax.Array,
    mask: jax.Array | None = None,
) -> ParticleStore:
    """Mutate an existing item per particle (COW applies).

    Supports the "mutation of previous states" usage from the paper's
    Section 1 model list.  ``positions: [N]`` must be < lengths.
    """
    if mask is None:
        mask = jnp.ones((cfg.n,), dtype=jnp.bool_)
    with jax.named_scope("store.append"):
        store = _write_impl(cfg, store, positions, values, advance=False, mask=mask)
    return _bump_peak(cfg, store)


def _write_impl(
    cfg: StoreConfig,
    store: ParticleStore,
    positions: jax.Array,
    values: jax.Array,
    advance: bool,
    mask: jax.Array | None = None,
) -> ParticleStore:
    n = cfg.n
    rows = jnp.arange(n, dtype=jnp.int32)
    if mask is None:
        mask = jnp.ones((n,), dtype=jnp.bool_)
    if cfg.mode is CopyMode.EAGER:
        cur = store.dense[rows, positions]
        sel = jnp.where(_expand(mask, values.ndim), values, cur)
        dense = store.dense.at[rows, positions].set(sel)
        lengths = store.lengths + jnp.where(mask, 1, 0) if advance else store.lengths
        return store._replace(dense=dense, lengths=lengths)

    pool = store.pool
    bs = cfg.block_size
    idx = positions // bs
    pos = positions % bs
    cur_bid = store.tables[rows, idx]
    fresh = (cur_bid == NULL_BLOCK) & mask
    if cfg.mode is CopyMode.LAZY:
        # Algorithm 5: any write to a frozen block copies it.
        shared = pool.frozen[jnp.where(cur_bid >= 0, cur_bid, 0)]
    else:
        # Remark 1: only genuinely shared blocks (refcount > 1) copy.
        shared = pool.refcount[jnp.where(cur_bid >= 0, cur_bid, 0)] > 1
    need_copy = (~fresh) & shared & mask
    need_block = fresh | need_copy

    cur_safe = jnp.where(cur_bid >= 0, cur_bid, 0)
    if cfg.delta_cow:
        # Captured before any refcount traffic: sub_refs below may free
        # ``cur`` and clear its delta bookkeeping.
        dirty_cur = pool.dirty[cur_safe]  # [n, block_size]
        par_cur = pool.parent[cur_safe]
        # The new delta child's backing block: cur itself when cur is
        # full, else cur's parent (delta depth stays <= 1).
        root = jnp.where(need_copy & (par_cur >= 0), par_cur, cur_bid)

    pool, new_bid = pool_lib.alloc(pool, n, commit=need_block)
    # Transient peak: COW sources and their copies coexist until the
    # writer's reference is released below (a real allocator pays this).
    with jax.named_scope("store.count"):
        store = store._replace(
            peak_blocks=jnp.maximum(store.peak_blocks, pool_lib.blocks_in_use(pool))
        )
    if cfg.delta_cow:
        # The child's reference on its parent — added *before* the
        # writer's reference on cur is released, so a parent shared only
        # through cur never dips to refcount 0 in between.
        pool = pool_lib.add_refs(pool, jnp.where(need_copy, root, NULL_BLOCK))
    # Release the writer's reference on blocks it copied away from.
    pool = pool_lib.sub_refs(pool, jnp.where(need_copy, cur_bid, NULL_BLOCK))

    bid = jnp.where(need_block, new_bid, cur_bid)
    tables = store.tables.at[rows, idx].set(
        jnp.where(mask, bid, store.tables[rows, idx])
    )
    # Fused COW + item write (DESIGN.md §3): copy rows stream their
    # source block, in-place/fresh rows read-modify-write their own
    # block, masked/NULL rows self-copy the dump row — one gather + one
    # scatter total, instead of the legacy dense gather / copy scatter /
    # item scatter trio.  Two unmasked writers can never share a
    # destination: either the block was exclusively owned, or COW just
    # gave each its own copy.
    dst = jnp.where(mask & (bid >= 0), bid, pool.num_blocks)
    src = jnp.where(need_copy, cur_bid, dst)
    if not cfg.delta_cow:
        data = cow_write(
            pool.data, src, dst, pos, values, use_kernel=cfg.use_kernels
        )
        pool = pool._replace(data=data)
    else:
        # Sub-block delta COW (DESIGN.md §3.2).  A copy row keeps only
        # the slots cur had materialized (its dirty mask; all-False when
        # cur is full — the sparse win); in-place/fresh rows keep
        # everything, recovering the whole-block merge.  Copy rows with
        # nothing to keep stream the dump row instead of their source —
        # the kernel then reads one zero block, not the shared payload.
        keep = jnp.where(need_copy[:, None], dirty_cur, True)
        src = jnp.where(need_copy & ~jnp.any(keep, axis=1), pool.num_blocks, src)
        data = cow_write(
            pool.data, src, dst, pos, values, keep=keep, use_kernel=cfg.use_kernels
        )
        pool = pool._replace(data=data)
        # Dirty/parent bookkeeping for rows whose final block is a delta
        # block: fresh allocations are full (pa = NULL), COW rows attach
        # to root, in-place rows keep their existing parent.  A mask
        # filling up degenerates the child back to a full block: parent
        # cleared, mask cleared, the parent reference released — the
        # payload is complete, so nothing resolves through root anymore.
        pa = jnp.where(need_copy, root, jnp.where(fresh, NULL_BLOCK, par_cur))
        mark = mask & (pa >= 0)
        new_dirty = dirty_cur | (
            jnp.arange(cfg.block_size, dtype=jnp.int32)[None, :] == pos[:, None]
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
    lengths = store.lengths + jnp.where(mask, 1, 0) if advance else store.lengths
    return store._replace(pool=pool, tables=tables, lengths=lengths)


def _expand(mask: jax.Array, ndim: int) -> jax.Array:
    return mask.reshape(mask.shape + (1,) * (ndim - mask.ndim))


# ---------------------------------------------------------------------------
# clone (the deep copy at resampling)
# ---------------------------------------------------------------------------


def _clone_bookkeeping(
    cfg: StoreConfig, pool: BlockPool, old_tables: jax.Array, new_tables: jax.Array
) -> BlockPool:
    """Single-pass clone bookkeeping (DESIGN.md §3).

    ``refcount += multiplicity(new) - multiplicity(old)``, the LAZY
    freeze bits, and the newly-freed push onto the free stack — one
    fused pass over the tables (:mod:`repro.kernels.refcount_update`)
    instead of the legacy ``add_refs`` / ``sub_refs`` / ``freeze``
    triple.  ``new_tables`` must only reference blocks live under
    ``old_tables`` (always true for resampling ancestors), so no block
    is resurrected behind the stack's back.
    """
    with jax.named_scope("store.refcount"):
        refcount, frozen, freed = refcount_update(
            pool.refcount,
            pool.frozen,
            new_tables,
            old_tables,
            do_freeze=cfg.mode is CopyMode.LAZY,
            use_kernel=cfg.use_kernels,
        )
    stack, top = pool_lib.push_free_mask(pool.free_stack, pool.free_top, freed)
    pool = pool._replace(
        refcount=refcount, frozen=frozen, free_stack=stack, free_top=top
    )
    if cfg.delta_cow:
        # Freed delta children release their parent reference (the
        # mask-shaped cascade; a value-level no-op when nothing freed
        # was a delta block).
        with jax.named_scope("store.refcount"):
            pool = pool_lib.release_parents(pool, freed)
    return pool


def clone(
    cfg: StoreConfig, store: ParticleStore, ancestors: jax.Array
) -> ParticleStore:
    """Replace the population by copies of ``ancestors`` (``[N] int32``).

    EAGER: physical gather of whole trajectories (O(N·T·D)).
    LAZY/LAZY_SR: gather of block tables + refcount delta (O(N·T/B)
    bookkeeping, no payload movement) — the lazy deep copy.  LAZY
    additionally freezes every block reachable from the new generation.
    """
    lengths = store.lengths[ancestors]
    if cfg.mode is CopyMode.EAGER:
        dense = store.dense[ancestors]
        store = store._replace(dense=dense, lengths=lengths)
        return _bump_peak(cfg, store)

    # refcount += multiplicity(new) - multiplicity(old); blocks dropping
    # to zero are thereby freed onto the stack (reference-counting GC) —
    # all in one fused bookkeeping pass.
    new_tables = store.tables[ancestors]
    pool = _clone_bookkeeping(cfg, store.pool, store.tables, new_tables)
    store = store._replace(pool=pool, tables=new_tables, lengths=lengths)
    return _bump_peak(cfg, store)


def clone_chain(
    cfg: StoreConfig, store: ParticleStore, key: jax.Array, logw: jax.Array
) -> Tuple[ParticleStore, jax.Array]:
    """Fused resample -> clone: systematic resampling and the lazy deep
    copy in one pass over the tables (:mod:`repro.kernels.clone_chain`).

    Returns ``(store', ancestors)``.  Ancestor-bit-exact with
    ``clone(cfg, store, resampling.resample_systematic(key, logw))`` —
    the fused op replicates that weight math verbatim — and the
    resulting store is leaf-identical to the composed path.  EAGER has
    no tables to fuse over, so it composes.
    """
    if cfg.mode is CopyMode.EAGER:
        from repro.smc import resampling

        ancestors = resampling.resample_systematic(key, logw)
        return clone(cfg, store, ancestors), ancestors

    # One scope for the whole fused op: its kernel path is a single
    # pallas_call, so the comb and the table gather go with the histogram.
    with jax.named_scope("store.refcount"):
        ancestors, new_tables, delta, member = clone_chain_op(
            key,
            logw,
            store.tables,
            num_blocks=store.pool.num_blocks,
            use_kernel=cfg.use_kernels,
        )
        # The same bookkeeping _clone_bookkeeping applies, fed by the fused
        # op's histogram instead of a second table pass.
        pool = store.pool
        refcount = pool.refcount + delta
        freed = (pool.refcount > 0) & (refcount == 0)
        frozen = pool.frozen | member if cfg.mode is CopyMode.LAZY else pool.frozen
    stack, top = pool_lib.push_free_mask(pool.free_stack, pool.free_top, freed)
    pool = pool._replace(
        refcount=refcount, frozen=frozen, free_stack=stack, free_top=top
    )
    if cfg.delta_cow:
        with jax.named_scope("store.refcount"):
            pool = pool_lib.release_parents(pool, freed)
    store = store._replace(
        pool=pool, tables=new_tables, lengths=store.lengths[ancestors]
    )
    return _bump_peak(cfg, store), ancestors


def clone_partial(
    cfg: StoreConfig, store: ParticleStore, ancestors: jax.Array, valid: jax.Array
) -> ParticleStore:
    """Clone where only ``valid`` slots take a (local) ancestor.

    Invalid slots come back *empty* (NULL table / zero length), pending a
    subsequent :func:`import_trajectories`.  The old generation's
    references are released for every slot, valid or not.  With ``valid``
    all-true this is exactly :func:`clone`; it exists for the sharded
    store (DESIGN.md §6), where slots whose ancestor lives on another
    shard are filled by the cross-shard exchange instead of a refcount
    bump.
    """
    lengths = jnp.where(valid, store.lengths[ancestors], 0)
    if cfg.mode is CopyMode.EAGER:
        dense = jnp.where(
            _expand(valid, store.dense.ndim), store.dense[ancestors], 0
        )
        store = store._replace(dense=dense, lengths=lengths)
        return _bump_peak(cfg, store)

    new_tables = jnp.where(valid[:, None], store.tables[ancestors], NULL_BLOCK)
    pool = _clone_bookkeeping(cfg, store.pool, store.tables, new_tables)
    store = store._replace(pool=pool, tables=new_tables, lengths=lengths)
    return _bump_peak(cfg, store)


def import_trajectories(
    cfg: StoreConfig,
    store: ParticleStore,
    trajs: jax.Array,
    new_lengths: jax.Array,
    mask: jax.Array,
) -> ParticleStore:
    """Write dense trajectories (``trajs: [N, capacity, *item]``) into the
    ``mask``-selected slots as fresh, exclusively-owned storage.

    The receiving half of the sharded store's cross-shard exchange: the
    imported particle gets newly allocated blocks (refcount 1) holding the
    materialized payload — the eager finish a shard boundary forces, just
    as a cross reference forces one in the object-graph semantics.  Masked
    slots must already be empty (see :func:`clone_partial`).
    """
    if cfg.mode is CopyMode.EAGER:
        dense = jnp.where(_expand(mask, store.dense.ndim), trajs, store.dense)
        lengths = jnp.where(mask, new_lengths, store.lengths)
        store = store._replace(dense=dense, lengths=lengths)
        return _bump_peak(cfg, store)

    n, mb, bs = cfg.n, cfg.max_blocks, cfg.block_size
    n_needed = -(-jnp.maximum(new_lengths, 0) // bs)  # ceil(len / bs)
    commit = (
        mask[:, None] & (jnp.arange(mb, dtype=jnp.int32)[None, :] < n_needed[:, None])
    ).reshape(-1)
    pool, bids = pool_lib.alloc_compact(store.pool, n * mb, commit=commit)
    payload = trajs.reshape(n * mb, bs, *cfg.item_shape)
    pool = pool_lib.write_blocks(pool, bids, payload, mask=commit)
    if cfg.mode is CopyMode.LAZY:
        # Imports join the new generation: frozen like every cloned block.
        pool = pool_lib.freeze(pool, jnp.where(commit, bids, NULL_BLOCK))
    bids = bids.reshape(n, mb)
    tables = jnp.where(mask[:, None], bids, store.tables)
    lengths = jnp.where(mask, new_lengths, store.lengths)
    store = store._replace(pool=pool, tables=tables, lengths=lengths)
    return _bump_peak(cfg, store)


# ---------------------------------------------------------------------------
# reads (Pull — never copies)
# ---------------------------------------------------------------------------


def _check_oom(cfg: StoreConfig, store: ParticleStore, op: str) -> None:
    """The ``strict_oom`` loud path: refuse to read a corrupted pool.

    Once ``oom`` is sticky, appends have been routed to the dump row and
    tables hold NULL entries — a trajectory read returns zeros where real
    records should be.  Eagerly this raises; under jit it emits a
    ``checkify.check`` (discharge with ``checkify.checkify``; an
    unwrapped jit fails loudly at trace time, which is still loud).
    """
    if not cfg.strict_oom or cfg.mode is CopyMode.EAGER:
        return
    oomv = jnp.any(store.pool.oom)
    msg = (
        f"ParticleStore.{op} on an exhausted pool: the sticky oom flag is "
        "set, so trajectories are corrupt (appends were dropped to the "
        "dump row). Grow the pool at a generation boundary (store.grow / "
        "FilterConfig.grow) or size num_blocks up."
    )
    if isinstance(oomv, jax.core.Tracer):
        from jax.experimental import checkify

        checkify.check(~oomv, msg)
    elif bool(oomv):
        raise RuntimeError(msg)


def read_at(cfg: StoreConfig, store: ParticleStore, positions: jax.Array) -> jax.Array:
    """Read one item per particle at ``positions: [N]`` (or scalar)."""
    positions = jnp.broadcast_to(positions, (cfg.n,))
    rows = jnp.arange(cfg.n, dtype=jnp.int32)
    if cfg.mode is CopyMode.EAGER:
        return store.dense[rows, positions]
    bs = cfg.block_size
    bid = store.tables[rows, positions // bs]
    safe = jnp.where(bid >= 0, bid, 0)
    out = store.pool.data[safe, positions % bs]
    if cfg.delta_cow:
        # Non-dirty slots of a delta block resolve through the parent.
        res = pool_lib.parent_or_self(store.pool, bid)
        base = store.pool.data[jnp.where(res >= 0, res, 0), positions % bs]
        d = store.pool.dirty[safe, positions % bs] & (bid >= 0)
        out = jnp.where(_expand(d, out.ndim), out, base)
    return out


def read_last(cfg: StoreConfig, store: ParticleStore) -> jax.Array:
    return read_at(cfg, store, jnp.maximum(store.lengths - 1, 0))


def _delta_resolve(
    cfg: StoreConfig, pool: BlockPool, tab_flat: jax.Array, blocks: jax.Array
) -> jax.Array:
    """Merge parent payload into the non-dirty slots of gathered blocks.

    ``blocks`` is ``cow_gather(pool.data, tab_flat)``; delta blocks hold
    zeros in their non-dirty slots, which this second gather fills from
    the parent.  Full blocks gather themselves twice (dirty all-False
    picks the identical base), NULL entries stay zero on both sides —
    so with ``delta_cow`` off callers skip this entirely.
    """
    base = cow_gather(
        pool.data, pool_lib.parent_or_self(pool, tab_flat), use_kernel=cfg.use_kernels
    )
    d = pool.dirty[jnp.where(tab_flat >= 0, tab_flat, 0)] & (tab_flat >= 0)[:, None]
    return jnp.where(d.reshape(d.shape + (1,) * (blocks.ndim - 2)), blocks, base)


def trajectory(cfg: StoreConfig, store: ParticleStore, i: int | jax.Array) -> jax.Array:
    """Full path of particle ``i`` as ``[capacity, *item]`` (entries past
    ``lengths[i]`` are unspecified)."""
    if cfg.mode is CopyMode.EAGER:
        return store.dense[i]
    _check_oom(cfg, store, "trajectory")
    tab = store.tables[i]
    blocks = cow_gather(store.pool.data, tab, use_kernel=cfg.use_kernels)
    if cfg.delta_cow:
        blocks = _delta_resolve(cfg, store.pool, tab, blocks)
    return blocks.reshape((cfg.capacity, *cfg.item_shape))


def materialize(
    cfg: StoreConfig, store: ParticleStore, i: int | jax.Array
) -> jax.Array:
    """Eager deep copy of one particle's trajectory, outside the pool.

    This is the escape hatch the paper uses for the particle-Gibbs
    reference trajectory in its VBD experiment ("a deep copy of a single
    particle between iterations that must be completed eagerly").
    """
    return trajectory(cfg, store, i)


def materialize_batch(
    cfg: StoreConfig, store: ParticleStore, ids: jax.Array
) -> jax.Array:
    """Eager deep copies of several trajectories: ``[k, capacity, *item]``.

    Vectorized :func:`materialize`; the sending half of the sharded
    store's cross-shard exchange (only boundary-crossing trajectories are
    ever passed here — within-shard clones stay refcount-only).
    """
    ids = ids.reshape(-1)
    if cfg.mode is CopyMode.EAGER:
        return store.dense[ids]
    _check_oom(cfg, store, "materialize_batch")
    tab = store.tables[ids]  # [k, max_blocks]
    # cow_gather: NULL entries yield zero blocks; kernel path streams one
    # pool block per table entry via scalar prefetch.
    blocks = cow_gather(store.pool.data, tab.reshape(-1), use_kernel=cfg.use_kernels)
    if cfg.delta_cow:
        blocks = _delta_resolve(cfg, store.pool, tab.reshape(-1), blocks)
    return blocks.reshape((ids.shape[0], cfg.capacity, *cfg.item_shape))


# ---------------------------------------------------------------------------
# accounting
# ---------------------------------------------------------------------------


def used_blocks(cfg: StoreConfig, store: ParticleStore) -> jax.Array:
    """Live blocks — the memory metric (paper Figures 5-7).

    EAGER physically owns every element of every trajectory; lazy modes
    own only the pool blocks with nonzero refcount.
    """
    if cfg.mode is CopyMode.EAGER:
        per = (store.lengths + cfg.block_size - 1) // cfg.block_size
        return jnp.sum(per)
    return pool_lib.blocks_in_use(store.pool)


def oom_flag(cfg: StoreConfig, store: ParticleStore) -> jax.Array:
    """Scalar bool: did any allocation ever fail?  (Sticky; any-shard for
    a stacked sharded store, where ``pool.oom`` carries a shard axis.)
    The signal the lifecycle layer (DESIGN.md §3.1) reads at generation
    boundaries, and the ``FilterResult.oom`` / SMC-decode ``oom`` field."""
    if cfg.mode is CopyMode.EAGER:
        return jnp.zeros((), jnp.bool_)
    return jnp.any(store.pool.oom)


def free_blocks(cfg: StoreConfig, store: ParticleStore) -> jax.Array:
    """Allocation headroom in blocks: the free-stack depth (min across
    shards for a stacked store).  EAGER storage never allocates, so its
    headroom is unbounded (int32 max)."""
    if cfg.mode is CopyMode.EAGER:
        return jnp.asarray(jnp.iinfo(jnp.int32).max, jnp.int32)
    return jnp.min(store.pool.free_top)


# ---------------------------------------------------------------------------
# pool lifecycle (DESIGN.md §3.1) — host-boundary, shape-changing ops
# ---------------------------------------------------------------------------


def grow(cfg: StoreConfig, store: ParticleStore, new_num_blocks: int) -> ParticleStore:
    """Expand the pool to ``new_num_blocks`` blocks; tables stay valid
    verbatim (block ids are preserved — see :func:`repro.core.pool.grow`).
    A host-boundary op: the pool shape changes, so downstream jits
    recompile.  Call between jitted generations, never inside one."""
    if cfg.mode is CopyMode.EAGER:
        raise ValueError("EAGER stores are dense; there is no pool to grow")
    return store._replace(pool=pool_lib.grow(store.pool, new_num_blocks))


def compact(
    cfg: StoreConfig,
    store: ParticleStore,
    new_num_blocks: int | None = None,
) -> ParticleStore:
    """Relocate live blocks to a dense prefix and rewrite the tables.

    Observationally invisible: every trajectory reads back bit-exact
    (enforced by ``tests/test_pool_lifecycle.py``).  With
    ``new_num_blocks`` this shrinks the pool to fit (must hold the live
    set: a too-small target surfaces through ``oom`` rather than
    silently dropping blocks).  EAGER storage is already dense — no-op.
    """
    if cfg.mode is CopyMode.EAGER:
        return store
    pool, remap = pool_lib.compact(
        store.pool, new_num_blocks, use_kernel=cfg.use_kernels
    )
    return store._replace(pool=pool, tables=pool_lib.remap_tables(store.tables, remap))


# Convenience jitted entry points (static cfg).
append_jit = partial(jax.jit, static_argnums=0)(append)
clone_jit = partial(jax.jit, static_argnums=0)(clone)
