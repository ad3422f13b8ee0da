"""Particle filters over the lazy-copy particle store.

The filter is the paper's motivating program: N particles, T generations,
cloned at every resampling step.  Trajectory records live in a
:class:`repro.core.store.ParticleStore`, so the storage strategy
(EAGER / LAZY / LAZY_SR) is a config switch and the filter code is
identical across them — which is precisely the platform's promise:
"copy-on-write for the imperative programmer".

Supports bootstrap and auxiliary (lookahead) filters, adaptive
resampling, an alive-filter rejection loop (bounded retries), a
simulation task (no observations → no resampling → no copies; paper
Section 4's overhead-isolation task), and conditional SMC
(:meth:`ParticleFilter.csmc_sweep` — particle 0 pinned to a reference
trajectory, the sweep inside particle Gibbs).  The per-generation scan
step is the only method-specific code: the host loop that drives it —
chunk jits, pool growth, rollback-retry, trace stitching — is the
shared :class:`repro.smc.executor.PopulationExecutor` (DESIGN.md §4).

Setting ``FilterConfig.mesh`` scales N across devices: the scan runs
under ``shard_map`` with an independent per-shard block pool, resampling
all-gathers only the weight vector, and only trajectories whose ancestor
lives on another shard are materialized and exchanged
(:mod:`repro.distributed.sharded_store`, DESIGN.md §6).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import store as store_lib
from repro.core.config import CopyMode
from repro.core.store import ParticleStore, StoreConfig
from repro.distributed import sharded_store as sharded_lib
from repro.smc import executor as executor_lib
from repro.smc import resampling

__all__ = ["SSMDef", "FilterConfig", "FilterResult", "ParticleFilter"]


class SSMDef(NamedTuple):
    """A vectorized state-space program.

    All callables operate on the whole population at once (leading dim N).

    Attributes:
      init: ``(key, n, params) -> state`` — sample ``x_0^{1:N}``.
      step: ``(key, state, t, obs, params) -> (state, logw, record)`` —
        propagate ``x_t ~ p(x_t | x_{t-1})`` and weight
        ``w_t = p(y_t | x_t)``; ``record: [N, *record_shape]`` is what the
        store appends for the trajectory.
      record_shape: shape of one trajectory item.
      clone_state: optional ``(state, ancestors) -> state`` override for
        models whose state embeds its own ParticleStore (e.g. PCFG
        stacks); default gathers every array leaf.
      lookahead: optional ``(state, t, obs, params) -> logmu`` for the
        auxiliary particle filter's pre-weights (Pitt & Shephard 1999).
      alive: ``(logw) -> dead_mask`` predicate for the alive filter
        (Del Moral et al. 2015); None disables the rejection loop.
    """

    init: Callable[..., Any]
    step: Callable[..., Tuple[Any, jax.Array, jax.Array]]
    record_shape: Tuple[int, ...]
    clone_state: Optional[Callable[[Any, jax.Array], Any]] = None
    lookahead: Optional[Callable[..., jax.Array]] = None
    alive: Optional[Callable[[jax.Array], jax.Array]] = None
    # For conditional SMC (particle Gibbs): pin particle 0 to a reference
    # record — ``(state, ref_record_t) -> state``.
    set_reference: Optional[Callable[[Any, jax.Array], Any]] = None


@dataclasses.dataclass(frozen=True)
class FilterConfig:
    n_particles: int
    n_steps: int
    mode: CopyMode = CopyMode.LAZY_SR
    resampler: str = "systematic"
    ess_threshold: float = 0.5  # resample when ESS < threshold * N
    always_resample: bool = True  # the paper's motivating pattern
    block_size: int = 4  # store COW granularity (items per block)
    pool_blocks: int = 0  # 0 = auto
    max_retries: int = 0  # alive-filter retries (0 = plain PF)
    dtype: str = "float32"
    # Route the store's write path / clone bookkeeping through the Pallas
    # kernels (cow_write / refcount_update / cow_gather, DESIGN.md §3);
    # interpret-mode on CPU, bit-exact with the jnp path.
    use_kernels: bool = False
    # Multi-device scaling (DESIGN.md §6): when ``mesh`` is set, the N
    # particles are split over the ``data_axes`` mesh axis — each shard
    # owns an independent block pool, resampling all-gathers only the
    # [N] weight vector, and only boundary-crossing trajectories are
    # materialized and exchanged.  With a 1-device mesh the sharded path
    # is bit-exact with the single-device one.
    mesh: Optional[Mesh] = None
    data_axes: str = "shards"  # mesh axis carrying the population
    max_exports: int = 0  # per-shard exchange slots; 0 = n_local (safe)
    # Pool lifecycle (DESIGN.md §3.1/§4): with ``grow=True`` the executor
    # runs the scan as jitted generation chunks with a host-side headroom
    # / OOM check between them — a filling pool grows (shape-keyed
    # recompile of the chunk) instead of sticking its ``oom`` flag and
    # corrupting trajectories.  Growth is capped at the dense bound
    # (``StoreConfig.pool_blocks_cap``), beyond which allocation provably
    # cannot fail.  ``jitted()`` returns the host-boundary driver in this
    # mode (its chunks are jitted internally); do not wrap it in jit.
    grow: bool = False
    grow_chunk: int = 8  # generations per jitted chunk between host checks
    grow_factor: float = 2.0  # capacity multiplier per growth event

    def store_config(self, record_shape: Tuple[int, ...]) -> StoreConfig:
        max_blocks = -(-self.n_steps // self.block_size)
        return StoreConfig(
            mode=self.mode,
            n=self.n_particles,
            block_size=self.block_size,
            max_blocks=max_blocks,
            item_shape=record_shape,
            dtype=self.dtype,
            num_blocks=self.pool_blocks,
            use_kernels=self.use_kernels,
        )

    def growth_policy(self) -> executor_lib.GrowthPolicy:
        """The executor policy this config describes (DESIGN.md §4)."""
        return executor_lib.GrowthPolicy(
            grow=self.grow, chunk=self.grow_chunk, factor=self.grow_factor
        )


class FilterResult(NamedTuple):
    store: ParticleStore
    state: Any
    log_weights: jax.Array  # [N], normalized
    log_evidence: jax.Array  # scalar estimate of log p(y_{1:T})
    ess_trace: jax.Array  # [T]
    resampled: jax.Array  # [T] bool
    used_blocks_trace: jax.Array  # [T] memory over time (Figure 7)
    # Lifecycle surface (DESIGN.md §3.1): ``oom`` is the store's sticky
    # allocation-failure flag (any shard) — if it is True the trajectories
    # in ``store`` are NOT trustworthy; ``grew`` counts generation-boundary
    # pool growth events (always 0 when ``FilterConfig.grow`` is off).
    oom: jax.Array  # scalar bool
    grew: jax.Array  # scalar int32


def _default_clone(state: Any, ancestors: jax.Array) -> Any:
    return jax.tree.map(lambda x: x[ancestors], state)


class ParticleFilter:
    """Bootstrap / auxiliary / alive / conditional particle filter over
    the COW store, orchestrated by a shared :class:`PopulationExecutor`."""

    def __init__(self, ssm: SSMDef, config: FilterConfig):
        self.ssm = ssm
        self.config = config
        self.store_cfg = config.store_config(ssm.record_shape)
        self._resample = resampling.RESAMPLERS[config.resampler]
        # The shared population executor (DESIGN.md §4): per-instance
        # chunk-jit cache (repeated runs hit the compile cache; only
        # growth events — new pool shapes — recompile), the lifecycle
        # loop, and telemetry.
        self._exec = executor_lib.PopulationExecutor()
        self.sharded_cfg: Optional[sharded_lib.ShardedStoreConfig] = None
        if config.mesh is not None:
            if ssm.lookahead is not None or (
                ssm.alive is not None and config.max_retries > 0
            ):
                raise NotImplementedError(
                    "sharded filtering covers the bootstrap path; auxiliary "
                    "lookahead and alive-filter retries are single-device only"
                )
            self.sharded_cfg = sharded_lib.ShardedStoreConfig(
                base=self.store_cfg,
                num_shards=config.mesh.shape[config.data_axes],
                axis_name=config.data_axes,
                max_exports=config.max_exports,
            )

    # -- public API ---------------------------------------------------------

    @property
    def executor(self) -> executor_lib.PopulationExecutor:
        """This filter's executor (chunk-jit cache + lifecycle stats)."""
        return self._exec

    def run(self, key: jax.Array, params: Any, observations: jax.Array) -> FilterResult:
        """Inference task: filter against observations ``[T, ...]``."""
        return self._run(key, params, observations, simulate=False)

    def simulate(
        self, key: jax.Array, params: Any, dummy_obs: jax.Array
    ) -> FilterResult:
        """Simulation task: run the model forward with no conditioning.

        No resampling occurs, hence no copies — the paper's second task,
        isolating the overhead of lazy-pointer bookkeeping.
        """
        return self._run(key, params, dummy_obs, simulate=True)

    def csmc_sweep(
        self,
        key: jax.Array,
        params: Any,
        observations: jax.Array,
        reference: jax.Array,
        use_ref: jax.Array,
    ) -> FilterResult:
        """One conditional-SMC sweep (the inner loop of particle Gibbs).

        Particle 0 keeps the reference lineage: its resampling ancestor
        is forced to 0 and its propagated record is overwritten by
        ``reference[t]`` (``SSMDef.set_reference`` pushes the record
        back into the state).  ``reference``/``use_ref`` are data, not
        trace constants, so one compiled sweep serves every iteration —
        and because the sweep runs through the same executor paths as
        :meth:`run`, it inherits ``FilterConfig.grow`` and ``mesh``
        support unchanged (a 1-shard mesh sweep is bit-exact with the
        single-device one).
        """
        if self.ssm.set_reference is None:
            raise ValueError("conditional SMC requires SSMDef.set_reference")
        return self._run(
            key,
            params,
            observations,
            simulate=False,
            csmc=(reference, jnp.asarray(use_ref)),
        )

    def jitted(self, simulate: bool = False):
        fn = self.simulate if simulate else self.run
        if self.config.grow:
            # The lifecycle driver syncs with the host between generation
            # chunks (headroom / OOM checks, shape-changing growth); the
            # chunks themselves are jitted internally.
            return fn
        return jax.jit(fn)

    # -- internals ----------------------------------------------------------

    def _run(
        self,
        key: jax.Array,
        params: Any,
        observations: jax.Array,
        simulate: bool,
        csmc: Optional[Tuple[jax.Array, jax.Array]] = None,
    ) -> FilterResult:
        if self.config.mesh is not None:
            return self._run_sharded(key, params, observations, simulate, csmc)
        cfg, ssm, scfg = self.config, self.ssm, self.store_cfg
        n = cfg.n_particles

        key, init_key = jax.random.split(key)
        state0 = ssm.init(init_key, n, params)
        store0 = store_lib.create(scfg)
        logw0 = jnp.full((n,), -math.log(n))
        init_carry = (key, state0, store0, logw0, jnp.zeros(()))

        chunk = self._exec.jit_chunk(
            ("local", bool(simulate), csmc is not None),
            lambda: self._build_chunk(simulate, csmc is not None),
        )
        extras = csmc if csmc is not None else ()
        chunk_fn = lambda c, ts: chunk(c, ts, params, observations, *extras)

        # Carry layout: (key, state, store, logw, logz) — the store at
        # index 2 is what the lifecycle loop reads and grows.
        pool = executor_lib.PoolView(
            free=lambda c: store_lib.free_blocks(scfg, c[2]),
            num_blocks=lambda c: c[2].pool.num_blocks,
            cap=scfg.pool_blocks_cap,
            grow_to=lambda c, nb: (
                c[0],
                c[1],
                store_lib.grow(scfg, c[2], nb),
                c[3],
                c[4],
            ),
            oom=lambda c: store_lib.oom_flag(scfg, c[2]),
        )
        carry, outs, grew = self._exec.run(
            init_carry,
            n_steps=cfg.n_steps,
            chunk_fn=chunk_fn,
            policy=cfg.growth_policy(),
            need_per_step=n,
            pool=pool,
        )
        _, state, store, logw, logz = carry
        ess_trace, resampled, used_trace = executor_lib.concat_chunk_outs(
            outs, executor_lib.filter_empty_outs()
        )
        return FilterResult(
            store=store,
            state=state,
            log_weights=logw,
            log_evidence=logz,
            ess_trace=ess_trace,
            resampled=resampled,
            used_blocks_trace=used_trace,
            oom=store_lib.oom_flag(scfg, store),
            grew=jnp.asarray(grew, jnp.int32),
        )

    def _build_chunk(self, simulate: bool, csmc: bool):
        """The single-device generation chunk: ``(carry, ts, params,
        observations[, reference, use_ref])``.  Everything dynamic is an
        argument, so one compile serves every run (and every rep of a
        benchmark) — only growth events recompile, shape-keyed on the
        pool leaves."""

        def chunk(carry, ts, params, observations, *extras):
            scan_step = self._make_scan_step(
                params, observations, simulate, extras if csmc else None
            )
            return jax.lax.scan(scan_step, carry, ts)

        return chunk

    def _make_scan_step(self, params, observations, simulate, csmc=None):
        """Build the single-device per-generation scan step.  ``params``
        and ``observations`` may be tracers: the executor's cached chunk
        jit passes them as arguments so one compile serves every run.
        ``csmc`` is an optional ``(reference, use_ref)`` pair that pins
        particle 0 to the reference lineage (conditional SMC)."""
        cfg, ssm, scfg = self.config, self.ssm, self.store_cfg
        n = cfg.n_particles
        clone_state = ssm.clone_state or _default_clone
        # Fused resample->clone (kernels/clone_chain): one pass over the
        # tables instead of three dispatches.  Only the plain systematic
        # path fuses — cSMC rewrites the ancestor vector between the
        # resample and the clone, and EAGER has no tables to fuse over.
        fuse_chain = (
            cfg.resampler == "systematic"
            and csmc is None
            and scfg.mode is not CopyMode.EAGER
        )

        def maybe_resample(key, t, state, store, logw):
            if simulate:
                return state, store, logw, jnp.zeros((), jnp.bool_)
            if cfg.always_resample:
                do = t > 0
            else:
                do = (t > 0) & resampling.should_resample(logw, cfg.ess_threshold)

            @jax.named_scope("filter.resample")
            def yes(operand):
                key, state, store, logw = operand
                lw = logw
                if ssm.lookahead is not None:
                    obs_t = jax.tree.map(lambda o: o[t], observations)
                    lw = resampling.normalize(
                        logw + ssm.lookahead(state, t, obs_t, params)
                    )
                if fuse_chain:
                    store, ancestors = store_lib.clone_chain(scfg, store, key, lw)
                else:
                    ancestors = self._resample(key, lw)
                    if csmc is not None:
                        # Conditional SMC: particle 0 keeps the
                        # reference lineage.
                        _, use_ref = csmc
                        ancestors = jnp.where(
                            use_ref, ancestors.at[0].set(0), ancestors
                        )
                    store = store_lib.clone(scfg, store, ancestors)
                state = clone_state(state, ancestors)
                # APF correction: carried weight becomes w/mu of ancestor.
                new_logw = jnp.full((n,), -math.log(n))
                if ssm.lookahead is not None:
                    new_logw = resampling.normalize(logw[ancestors] - lw[ancestors])
                return state, store, new_logw

            def no(operand):
                _, state, store, logw = operand
                return state, store, logw

            state, store, logw = jax.lax.cond(do, yes, no, (key, state, store, logw))
            return state, store, logw, do

        def propagate(key, state, t, logw):
            obs_t = jax.tree.map(lambda o: o[t], observations)
            state, dlogw, record = ssm.step(key, state, t, obs_t, params)
            if simulate:
                dlogw = jnp.zeros_like(dlogw)
            return state, dlogw, record

        def alive_loop(key, state, t, logw, dlogw, record, prev_state):
            """Bounded rejection loop for the alive particle filter:
            dead particles redraw an ancestor among the living and
            re-propagate, up to ``max_retries`` rounds."""
            if ssm.alive is None or cfg.max_retries == 0 or simulate:
                return state, dlogw, record

            def body(carry):
                i, key, state, dlogw, record = carry
                key, k1, k2 = jax.random.split(key, 3)
                dead = ssm.alive(dlogw)
                alive_w = jnp.where(dead, -jnp.inf, logw)
                # Redraw ancestors for dead particles among the living.
                anc = resampling.resample_multinomial(k1, alive_w)
                anc = jnp.where(dead, anc, jnp.arange(cfg.n_particles))
                re_state = clone_state(prev_state, anc)
                new_state, new_dlogw, new_record = propagate(k2, re_state, t, logw)
                pick = lambda a, b: jnp.where(
                    dead.reshape((-1,) + (1,) * (a.ndim - 1)), a, b
                )
                state = jax.tree.map(pick, new_state, state)
                dlogw = jnp.where(dead, new_dlogw, dlogw)
                record = pick(new_record, record)
                return i + 1, key, state, dlogw, record

            def cond(carry):
                i, _, _, dlogw, _ = carry
                return (i < cfg.max_retries) & jnp.any(ssm.alive(dlogw))

            _, _, state, dlogw, record = jax.lax.while_loop(
                cond, body, (0, key, state, dlogw, record)
            )
            return state, dlogw, record

        def scan_step(carry, t):
            key, state, store, logw, logz = carry
            key, k_res, k_prop, k_alive = jax.random.split(key, 4)
            state, store, logw, did = maybe_resample(k_res, t, state, store, logw)
            prev_state = state
            with jax.named_scope("filter.propagate"):
                state, dlogw, record = propagate(k_prop, state, t, logw)
                state, dlogw, record = alive_loop(
                    k_alive, state, t, logw, dlogw, record, prev_state
                )
            if csmc is not None:
                # Pin particle 0 to the reference record.
                reference, use_ref = csmc
                ref_t = reference[t]
                record = jnp.where(use_ref, record.at[0].set(ref_t), record)
                state = jax.lax.cond(
                    use_ref,
                    lambda s: ssm.set_reference(s, ref_t),
                    lambda s: s,
                    state,
                )
            lw = logw + dlogw
            logz = logz + jax.scipy.special.logsumexp(lw)
            logw = resampling.normalize(lw)
            store = store_lib.append(scfg, store, record)
            ess = resampling.ess(logw)
            with jax.named_scope("store.count"):
                used = store_lib.used_blocks(scfg, store)
            out = (ess, did, used)
            return (key, state, store, logw, logz), out

        return scan_step

    def _run_sharded(
        self,
        key: jax.Array,
        params: Any,
        observations: jax.Array,
        simulate: bool,
        csmc: Optional[Tuple[jax.Array, jax.Array]] = None,
    ) -> FilterResult:
        """The filter scan under ``shard_map`` (DESIGN.md §6), on the
        same executor loop as the single-device path.

        Mirrors :meth:`_run` operation for operation: with a 1-device
        mesh every collective is the identity and the same keys drive the
        same samplers, so the result is bit-exact with the single-device
        path.  Multi-shard runs draw per-shard propagation noise (keys
        folded with the shard index) and therefore agree statistically —
        same log-evidence estimand, independent randomness.

        Under ``FilterConfig.grow`` the per-shard pools grow **in
        lockstep**: every shard's pool keeps an identical capacity, so
        the stacked-store layout (`store_specs`/`unstack`/`restack`)
        stays consistent across growth events.  The executor reads the
        stacked per-shard ``free_top``/``oom`` leaves, takes the worst
        shard, and grows all pools together — cross-shard import skew
        (DESIGN.md §6's capacity note) is exactly why the rollback-retry
        backstop exists: a skewed resampling step can concentrate more
        than the watermark's worth of imports on one shard.

        The returned ``FilterResult.store`` is the stacked global view
        (see :mod:`repro.distributed.sharded_store`): block tables hold
        shard-local ids and ``peak_blocks`` is ``[num_shards]``; read
        trajectories through ``sharded_store.trajectories``.
        """
        cfg, ssm = self.config, self.ssm
        shcfg = self.sharded_cfg
        assert shcfg is not None
        mesh, axis = cfg.mesh, cfg.data_axes
        n, n_shards, nl = cfg.n_particles, shcfg.num_shards, shcfg.n_local
        local = shcfg.local
        sp = sharded_lib.store_specs(axis)
        ax = P(axis)

        def build_init():
            def init_body(key, params):
                s = lax.axis_index(axis)
                key, init_key = jax.random.split(key)
                if n_shards > 1:  # 1-shard keeps the single-device stream
                    init_key = jax.random.fold_in(init_key, s)
                state0 = ssm.init(init_key, nl, params)
                return key, state0, sharded_lib.restack(store_lib.create(local))

            return shard_map(
                init_body,
                mesh=mesh,
                in_specs=(P(), P()),
                out_specs=(P(), ax, sp),
                check_vma=False,
            )

        init_fn = self._exec.jit_chunk("sharded_init", build_init)
        key, state, store = init_fn(key, params)
        logw = jnp.full((n,), -math.log(n))
        carry = (key, state, store, logw, jnp.zeros(()))

        n_extras = 2 if csmc is not None else 0

        def build_chunk():
            def chunk_body(
                key, state, store, logw, logz, ts, params, observations, *extras
            ):
                scan_step, _ = self._make_sharded_step(
                    params, observations, simulate, extras if csmc is not None else None
                )
                carry = (key, state, sharded_lib.unstack(store), logw, logz)
                carry, (ess, did, used) = jax.lax.scan(scan_step, carry, ts)
                key_, state_, store_, logw_, logz_ = carry
                return (
                    key_,
                    state_,
                    sharded_lib.restack(store_),
                    logw_,
                    logz_,
                    ess,
                    did,
                    used,
                )

            return shard_map(
                chunk_body,
                mesh=mesh,
                in_specs=(P(), ax, sp, ax, P(), P(), P(), P()) + (P(),) * n_extras,
                out_specs=(P(), ax, sp, ax, P(), P(), P(), P()),
                check_vma=False,
            )

        chunk = self._exec.jit_chunk(
            ("sharded", bool(simulate), csmc is not None), build_chunk
        )
        extras = csmc if csmc is not None else ()

        def chunk_fn(c, ts):
            key, state, store, logw, logz, ess, did, used = chunk(
                *c, ts, params, observations, *extras
            )
            return (key, state, store, logw, logz), (ess, did, used)

        pool = executor_lib.PoolView(
            free=lambda c: store_lib.free_blocks(local, c[2]),  # worst shard
            num_blocks=lambda c: sharded_lib.local_num_blocks(c[2], n_shards),
            cap=sharded_lib.lifecycle_cap(shcfg),
            grow_to=lambda c, nb: (
                c[0],
                c[1],
                sharded_lib.grow(shcfg, mesh, c[2], nb),
                c[3],
                c[4],
            ),
            oom=lambda c: jnp.any(c[2].pool.oom),
        )
        carry, outs, grew = self._exec.run(
            carry,
            n_steps=cfg.n_steps,
            chunk_fn=chunk_fn,
            policy=cfg.growth_policy(),
            need_per_step=nl,
            pool=pool,
        )
        _, state, store, logw, logz = carry
        ess_trace, resampled, used_trace = executor_lib.concat_chunk_outs(
            outs, executor_lib.filter_empty_outs()
        )
        return FilterResult(
            store=store,
            state=state,
            log_weights=logw,
            log_evidence=logz,
            ess_trace=ess_trace,
            resampled=resampled,
            used_blocks_trace=used_trace,
            oom=jnp.any(store.pool.oom),
            grew=jnp.asarray(grew, jnp.int32),
        )

    def _make_sharded_step(self, params, observations, simulate, csmc=None):
        """Build the per-generation scan step that runs *inside*
        ``shard_map`` (the sharded twin of :meth:`_make_scan_step`).
        Carry: ``(key, state, local store, logw, logz)``; the shard
        index is re-derived from ``lax.axis_index`` on every call, so
        the step closes over nothing shard-specific.  ``csmc`` pins the
        reference lineage: the ancestor pin is global (every shard
        computes the same ancestor vector), the record/state pin applies
        on shard 0 only — where global particle 0 lives."""
        cfg, ssm = self.config, self.ssm
        shcfg = self.sharded_cfg
        mesh, axis = cfg.mesh, cfg.data_axes
        n, n_shards, nl = cfg.n_particles, shcfg.num_shards, shcfg.n_local
        local = shcfg.local
        clone_state = ssm.clone_state or _default_clone

        def shard_key(k, s):
            # 1-shard meshes keep the exact single-device key stream.
            return k if n_shards == 1 else jax.random.fold_in(k, s)

        def maybe_resample(key, t, state, store, logw, s, lo):
            if simulate:
                return state, store, logw, jnp.zeros((), jnp.bool_)
            if cfg.always_resample:
                do = t > 0
            else:
                glogw = sharded_lib.gather_global(logw, axis)
                do = (t > 0) & resampling.should_resample(glogw, cfg.ess_threshold)

            @jax.named_scope("filter.resample")
            def yes(operand):
                key, state, store, logw = operand
                # Weights are globally normalized in the carry, so the
                # gathered vector is the full population's weights.
                glw = sharded_lib.gather_global(logw, axis)
                ancestors = self._resample(key, glw)  # [N]; same on
                # every shard (shared key, replicated weights).
                if csmc is not None:
                    # Conditional SMC: global particle 0 keeps the
                    # reference lineage (same pin on every shard).
                    _, use_ref = csmc
                    ancestors = jnp.where(use_ref, ancestors.at[0].set(0), ancestors)
                full_state = jax.tree.map(
                    lambda x: sharded_lib.gather_global(x, axis), state
                )
                state = jax.tree.map(
                    lambda x: lax.dynamic_slice_in_dim(x, lo, nl),
                    clone_state(full_state, ancestors),
                )
                store = sharded_lib.sharded_clone(shcfg, store, ancestors)
                new_logw = jnp.full((nl,), -math.log(n))
                return state, store, new_logw

            def no(operand):
                _, state, store, logw = operand
                return state, store, logw

            state, store, logw = jax.lax.cond(do, yes, no, (key, state, store, logw))
            return state, store, logw, do

        def propagate(key, state, t, logw, s):
            obs_t = jax.tree.map(lambda o: o[t], observations)
            state, dlogw, record = ssm.step(
                shard_key(key, s), state, t, obs_t, params
            )
            if simulate:
                dlogw = jnp.zeros_like(dlogw)
            return state, dlogw, record

        def scan_step(carry, t):
            key, state, store, logw, logz = carry
            s = lax.axis_index(axis)
            lo = s * nl
            key, k_res, k_prop, _k_alive = jax.random.split(key, 4)
            state, store, logw, did = maybe_resample(
                k_res, t, state, store, logw, s, lo
            )
            with jax.named_scope("filter.propagate"):
                state, dlogw, record = propagate(k_prop, state, t, logw, s)
            if csmc is not None:
                # Pin local row 0 of shard 0 — global particle 0 — to
                # the reference record.
                reference, use_ref = csmc
                ref_t = reference[t]
                pin = use_ref & (s == 0)
                record = jnp.where(pin, record.at[0].set(ref_t), record)
                state = jax.lax.cond(
                    pin,
                    lambda st: ssm.set_reference(st, ref_t),
                    lambda st: st,
                    state,
                )
            lw = logw + dlogw
            glw = sharded_lib.gather_global(lw, axis)
            logz = logz + jax.scipy.special.logsumexp(glw)
            glw_norm = resampling.normalize(glw)
            logw = lax.dynamic_slice_in_dim(glw_norm, lo, nl)
            store = store_lib.append(local, store, record)
            ess = resampling.ess(glw_norm)
            with jax.named_scope("store.count"):
                used = lax.psum(store_lib.used_blocks(local, store), axis)
            out = (ess, did, used)
            return (key, state, store, logw, logz), out

        return scan_step, shard_key
