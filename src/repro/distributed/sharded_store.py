"""Sharded multi-device ParticleStore: per-shard block pools under shard_map.

This module builds the composition that :mod:`repro.core.pool` promises
(DESIGN.md §6): each device shard owns an **independent** block pool and
an ``n_local = N / num_shards`` slice of the population — per-shard free
lists, per-shard refcounts, no cross-device allocation — the array-world
analogue of the paper giving each thread its own context stack so
populations scale without contention.

Resampling is the only cross-shard operation, and it is split into a
cheap global phase and a narrow exchange:

1. **all-gather of the particle weights** (``[N]`` floats — tiny) so
   every shard computes the *same* global ancestor vector from a shared
   key;
2. **within-shard clones stay lazy**: slots whose ancestor lives on the
   same shard are cloned by :func:`repro.core.store.clone_partial` —
   block-table gather + refcount delta, zero payload movement;
3. **a permute-based exchange for boundary crossers**: each shard
   materializes *only* the trajectories that remote shards demand
   (deduplicated by global id, compacted into ``max_exports`` slots),
   the compacted boundary set is all-gathered, and each shard permutes
   the gathered set by global id into its importing slots
   (:func:`repro.core.store.import_trajectories` — fresh refcount-1
   blocks on the importing shard's pool).

A shard boundary thus plays the role a cross reference plays in the
object-graph semantics: it forces an eager finish of exactly the
affected trajectories, while everything tree-local stays lazy.

Two API layers:

* *inside-shard_map* primitives (:func:`sharded_clone`,
  :func:`gather_global`) for code that already runs under
  ``jax.shard_map`` — the sharded particle filter's scan
  (:mod:`repro.smc.filters`) uses these directly so the whole filter
  stays one jitted program;
* *stacked* wrappers (:func:`create`, :func:`append`, :func:`clone`,
  :func:`trajectories`, ...) that take/return a global-view
  :class:`~repro.core.store.ParticleStore` whose leaves carry the shard
  axis (shard-major: global particle ``i`` lives on shard
  ``i // n_local``; pool data is the concatenation of the per-shard
  pools *including each shard's trailing dump row*, so global data row =
  local id + shard * (pool_blocks + 1)).  These
  serve :mod:`repro.serving.smc_decode`, the benchmarks, and tests.

Capacity note: imports land as fresh allocations on the *importing*
shard, so a skewed resampling step can concentrate blocks on one pool
even when global occupancy is flat.  The auto-sized per-shard pool pads
for this; exhaustion and export-slot overflow both surface through the
sticky ``pool.oom`` flag rather than raising (everything here is
jittable, fixed-shape, host-sync-free).  At host boundaries the
lifecycle layer (DESIGN.md §3.1) makes exhaustion recoverable:
:func:`grow` / :func:`compact` apply :mod:`repro.core.pool`'s growth
and compaction to every shard **in lockstep**, so all stacked leaves
keep one shared shape and `store_specs`/`unstack`/`restack` stay
consistent; the sharded filter's chunked driver
(``FilterConfig.grow``) watches the worst shard's headroom.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import pool as pool_lib
from repro.core import store as store_lib
from repro.core.config import CopyMode
from repro.core.pool import BlockPool
from repro.core.store import ParticleStore, StoreConfig

__all__ = [
    "ShardedStoreConfig",
    "sharded_clone",
    "gather_global",
    "create",
    "append",
    "write_at",
    "clone",
    "grow",
    "compact",
    "lifecycle_cap",
    "local_num_blocks",
    "read_last",
    "trajectories",
    "used_blocks_per_shard",
    "peak_blocks_per_shard",
    "store_specs",
    "unstack",
    "restack",
]


@dataclasses.dataclass(frozen=True)
class ShardedStoreConfig:
    """Static configuration of a sharded store (hashable).

    Attributes:
      base:        the *global* :class:`StoreConfig` (``base.n`` = total
                   population size N).
      num_shards:  devices along the shard axis; must divide ``base.n``.
      axis_name:   mesh axis the population is split over.
      max_exports: per-shard export slots for the cross-shard exchange;
                   0 means ``n_local`` (a shard can never be asked for
                   more than its own n_local distinct trajectories, so
                   the default cannot overflow).
    """

    base: StoreConfig
    num_shards: int
    axis_name: str = "shards"
    max_exports: int = 0

    def __post_init__(self):
        if self.base.n % self.num_shards != 0:
            raise ValueError(
                f"population size {self.base.n} not divisible by "
                f"num_shards={self.num_shards}"
            )

    @property
    def n_local(self) -> int:
        return self.base.n // self.num_shards

    @property
    def exports(self) -> int:
        return self.max_exports or self.n_local

    @property
    def local(self) -> StoreConfig:
        """Per-shard StoreConfig (what actually lives on each device)."""
        b = self.base
        if b.num_blocks:
            blocks = -(-b.num_blocks // self.num_shards)
        elif self.num_shards == 1:
            blocks = 0  # keep the single-device auto size → bit-exact
        else:
            nl = self.base.n // self.num_shards
            auto = dataclasses.replace(b, n=nl).pool_blocks
            # Pad for import skew (a resampling step may concentrate up to
            # n_local imported trajectories on one shard's pool), and keep
            # one transient block per particle above the dense bound: LAZY
            # copies even sole-owner frozen blocks, so source and copy
            # coexist within a write step.
            dense = nl * b.max_blocks + nl
            blocks = min(dense, auto + (nl * b.max_blocks) // 4 + nl)
        return dataclasses.replace(
            b, n=self.base.n // self.num_shards, num_blocks=blocks
        )


# ---------------------------------------------------------------------------
# inside-shard_map primitives
# ---------------------------------------------------------------------------


def gather_global(x: jax.Array, axis_name: str) -> jax.Array:
    """Shard-major concatenation of a per-shard leading axis: local
    ``[n_local, ...]`` -> global ``[N, ...]`` (global id = s*n_local + i)."""
    return lax.all_gather(x, axis_name, tiled=True)


def sharded_clone(
    cfg: ShardedStoreConfig, store: ParticleStore, global_ancestors: jax.Array
) -> ParticleStore:
    """Population clone under a *global* ancestor vector (``[N] int32``).

    Must run inside ``shard_map`` over ``cfg.axis_name``; ``store`` is
    this shard's local store and ``global_ancestors`` is replicated
    (every shard computed it from the all-gathered weights with a shared
    key).  Within-shard ancestry is a lazy clone; boundary crossers move
    through the compact materialize/all-gather/permute exchange described
    in the module docstring.
    """
    local = cfg.local
    nl, k, axis = cfg.n_local, cfg.exports, cfg.axis_name
    n_global = cfg.base.n
    s = lax.axis_index(axis)

    anc = lax.dynamic_slice_in_dim(global_ancestors, s * nl, nl)  # my slots
    owner = anc // nl
    is_local = owner == s
    local_anc = jnp.where(is_local, anc - s * nl, 0)

    # --- export side: which of MY particles do remote shards demand?
    slot_shard = jnp.arange(n_global, dtype=jnp.int32) // nl
    cross = slot_shard != (global_ancestors // nl)
    demanded = (
        jnp.zeros((n_global,), jnp.int32)
        .at[global_ancestors]
        .max(cross.astype(jnp.int32))
    )
    my_dem = lax.dynamic_slice_in_dim(demanded, s * nl, nl) > 0
    overflow = jnp.sum(my_dem) > k
    exp_local = jnp.nonzero(my_dem, size=k, fill_value=-1)[0].astype(jnp.int32)
    exp_valid = exp_local >= 0
    safe = jnp.where(exp_valid, exp_local, 0)
    exp_gid = jnp.where(exp_valid, exp_local + s * nl, -1)
    exp_len = jnp.where(exp_valid, store.lengths[safe], 0)
    # Materialize ONLY the boundary set (the exchange's eager finish).
    exp_traj = store_lib.materialize_batch(local, store, safe)

    # --- the exchange: gather the compacted boundary sets of all shards.
    g_traj = gather_global(exp_traj, axis)  # [S*k, capacity, *item]
    g_gid = gather_global(exp_gid, axis)  # [S*k]
    g_len = gather_global(exp_len, axis)  # [S*k]

    # --- import side: permute the gathered set into my remote slots.
    match = g_gid[None, :] == anc[:, None]  # [nl, S*k]
    pos = jnp.argmax(match, axis=1)
    found = jnp.any(match, axis=1)
    do_import = (~is_local) & found
    imp_traj = g_traj[pos]
    imp_len = g_len[pos]

    store = store_lib.clone_partial(local, store, local_anc, is_local)
    store = store_lib.import_trajectories(local, store, imp_traj, imp_len, do_import)
    missing = jnp.any((~is_local) & ~found)
    return store._replace(
        pool=store.pool._replace(oom=store.pool.oom | overflow | missing)
    )


# ---------------------------------------------------------------------------
# stacked (global-view) wrappers
# ---------------------------------------------------------------------------
#
# Leaves of the stacked store carry the shard axis: tables [N, mb] (ids
# LOCAL to each shard's pool), lengths [N], pool.data
# [S*(pool_blocks+1), ...] (each shard's dump row rides along),
# pool.oom / peak_blocks / free_top [S].  `unstack`/`restack` bridge the [1]-leaf
# view shard_map hands a rank-preserving spec and the scalar leaves the
# local store ops expect.


def lifecycle_cap(cfg: ShardedStoreConfig) -> int:
    """Growth ceiling for lockstep per-shard growth (DESIGN.md §3.1/§4):
    the per-shard dense bound, at which allocation provably cannot fail.
    EAGER stores carry a dummy pool — 0 disables growth entirely.  The
    one rule every lifecycle driver of a sharded store (filters, CSMC
    sweeps, the serving token trace) sizes its ``PoolView.cap`` by."""
    return 0 if cfg.base.mode is CopyMode.EAGER else cfg.local.pool_blocks_cap


def local_num_blocks(store: ParticleStore, num_shards: int) -> int:
    """Per-shard pool capacity of a *stacked* store (every shard grows in
    lockstep, so one number).  The stacking convention — per-shard leaves
    concatenated along their leading axis — lives in this module
    (``store_specs``/``unstack``/``restack``); lifecycle drivers read the
    layout through this helper instead of re-deriving it."""
    return store.pool.refcount.shape[0] // num_shards


def unstack(store: ParticleStore) -> ParticleStore:
    """Inside shard_map: [1]-shaped scalar leaves -> local scalars."""
    return store._replace(
        pool=store.pool._replace(
            oom=store.pool.oom.reshape(()),
            free_top=store.pool.free_top.reshape(()),
        ),
        peak_blocks=store.peak_blocks.reshape(()),
    )


def restack(store: ParticleStore) -> ParticleStore:
    """Inside shard_map: local scalar leaves -> [1]-shaped for stacking."""
    return store._replace(
        pool=store.pool._replace(
            oom=store.pool.oom.reshape((1,)),
            free_top=store.pool.free_top.reshape((1,)),
        ),
        peak_blocks=store.peak_blocks.reshape((1,)),
    )


def store_specs(axis_name: str) -> ParticleStore:
    """PartitionSpec pytree: every leaf sharded on its leading axis.

    Pool bookkeeping (refcount, frozen, the free stack and its top) is
    per-shard state: each shard allocates by popping its own stack, so
    ``alloc_compact`` for trajectory imports never contends across
    devices.
    """
    sp = P(axis_name)
    return ParticleStore(
        pool=BlockPool(
            data=sp,
            refcount=sp,
            frozen=sp,
            free_stack=sp,
            free_top=sp,
            oom=sp,
            parent=sp,
            dirty=sp,
        ),
        dense=sp,
        tables=sp,
        lengths=sp,
        peak_blocks=sp,
    )


# The wrapped callables are memoized per (op, cfg, mesh) — both are
# hashable — and jitted, so hot loops (smc_decode appends/clones once
# per token) hit the compile cache instead of re-tracing a fresh
# shard_map closure every call.


@functools.lru_cache(maxsize=None)
def _wrapped(op: str, cfg: ShardedStoreConfig, mesh: Mesh):
    sp = store_specs(cfg.axis_name)
    ax = P(cfg.axis_name)
    fns = {
        "create": (lambda: restack(store_lib.create(cfg.local)), (), sp),
        "append": (
            lambda st, v: restack(store_lib.append(cfg.local, unstack(st), v)),
            (sp, ax),
            sp,
        ),
        "write_at": (
            lambda st, p, v: restack(
                store_lib.write_at(cfg.local, unstack(st), p, v)
            ),
            (sp, ax, ax),
            sp,
        ),
        "clone": (
            lambda st, a: restack(sharded_clone(cfg, unstack(st), a)),
            (sp, P()),
            sp,
        ),
        "read_last": (
            lambda st: store_lib.read_last(cfg.local, unstack(st)),
            (sp,),
            ax,
        ),
        "trajectories": (
            lambda st: store_lib.materialize_batch(
                cfg.local, unstack(st), jnp.arange(cfg.n_local, dtype=jnp.int32)
            ),
            (sp,),
            ax,
        ),
    }
    fn, in_specs, out_specs = fns[op]
    return jax.jit(
        shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )
    )


def create(cfg: ShardedStoreConfig, mesh: Mesh) -> ParticleStore:
    """Per-shard empty pools, stacked into the global view."""
    return _wrapped("create", cfg, mesh)()


def append(
    cfg: ShardedStoreConfig, mesh: Mesh, store: ParticleStore, values: jax.Array
) -> ParticleStore:
    """Append one item per particle (``values: [N, *item]``) — purely local."""
    return _wrapped("append", cfg, mesh)(store, values)


def write_at(
    cfg: ShardedStoreConfig,
    mesh: Mesh,
    store: ParticleStore,
    positions: jax.Array,
    values: jax.Array,
) -> ParticleStore:
    """Mutate one existing item per particle (COW applies) — purely local."""
    return _wrapped("write_at", cfg, mesh)(store, positions, values)


def clone(
    cfg: ShardedStoreConfig, mesh: Mesh, store: ParticleStore, ancestors: jax.Array
) -> ParticleStore:
    """Global resampling clone (``ancestors: [N]`` global ids, replicated)."""
    return _wrapped("clone", cfg, mesh)(store, ancestors)


def read_last(cfg: ShardedStoreConfig, mesh: Mesh, store: ParticleStore) -> jax.Array:
    return _wrapped("read_last", cfg, mesh)(store)


# Lifecycle ops (DESIGN.md §3.1) are cached per target size, not per op
# name: they change leaf shapes, so each capacity is its own compile.


@functools.lru_cache(maxsize=None)
def _wrapped_grow(cfg: ShardedStoreConfig, mesh: Mesh, new_num_blocks: int):
    sp = store_specs(cfg.axis_name)

    def fn(st):
        st = unstack(st)
        return restack(st._replace(pool=pool_lib.grow(st.pool, new_num_blocks)))

    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=(sp,), out_specs=sp, check_vma=False)
    )


@functools.lru_cache(maxsize=None)
def _wrapped_compact(
    cfg: ShardedStoreConfig, mesh: Mesh, new_num_blocks: int | None
):
    sp = store_specs(cfg.axis_name)

    def fn(st):
        return restack(store_lib.compact(cfg.local, unstack(st), new_num_blocks))

    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=(sp,), out_specs=sp, check_vma=False)
    )


def grow(
    cfg: ShardedStoreConfig, mesh: Mesh, store: ParticleStore, new_num_blocks: int
) -> ParticleStore:
    """Grow every shard's pool to ``new_num_blocks`` blocks **in
    lockstep**, so the stacked layout (`store_specs`/`unstack`/`restack`
    — every per-shard leaf keeps one shared shape) stays consistent.
    Block ids are shard-local and preserved, so tables stay valid.  A
    host-boundary op: leaf shapes change, downstream jits recompile."""
    return _wrapped_grow(cfg, mesh, new_num_blocks)(store)


def compact(
    cfg: ShardedStoreConfig,
    mesh: Mesh,
    store: ParticleStore,
    new_num_blocks: int | None = None,
) -> ParticleStore:
    """Per-shard compaction (each shard densifies its own pool and
    rewrites its own tables), in lockstep like :func:`grow`.  With
    ``new_num_blocks``, every shard shrinks to the same capacity — it
    must hold the *worst* shard's live set (a too-small target surfaces
    through that shard's ``oom`` flag, never silent truncation)."""
    return _wrapped_compact(cfg, mesh, new_num_blocks)(store)


def trajectories(
    cfg: ShardedStoreConfig, mesh: Mesh, store: ParticleStore
) -> jax.Array:
    """Materialize the whole population: ``[N, capacity, *item]``."""
    return _wrapped("trajectories", cfg, mesh)(store)


def used_blocks_per_shard(cfg: ShardedStoreConfig, store: ParticleStore) -> jax.Array:
    """Live blocks per shard, ``[num_shards]`` — the bench_sharded metric."""
    s = cfg.num_shards
    if cfg.base.mode is CopyMode.EAGER:
        per = (store.lengths + cfg.base.block_size - 1) // cfg.base.block_size
        return jnp.sum(per.reshape(s, cfg.n_local), axis=1)
    return jnp.sum(store.pool.refcount.reshape(s, -1) > 0, axis=1)


def peak_blocks_per_shard(cfg: ShardedStoreConfig, store: ParticleStore) -> jax.Array:
    """Running per-shard peak, ``[num_shards]`` (stacked ``peak_blocks``)."""
    return store.peak_blocks.reshape(cfg.num_shards)
