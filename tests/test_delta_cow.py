"""Sub-block delta COW + fused clone chain (DESIGN.md §3.2).

The contracts under test:

* ``delta_cow=True`` is **observationally** equivalent to the
  whole-block path: valid-prefix trajectories, point reads, and lengths
  are bit-exact.  Pool internals legitimately diverge (delta parents
  outlive their children, shifting the free-stack order and hence the
  allocated block ids), so tables and payload are *not* compared across
  the switch.
* Within ``delta_cow=True``, ``use_kernels=True`` is **leaf**-exact
  with the jnp fallback — data, parent, dirty, refcount, free stack,
  tables all bitwise equal.
* The fused ``clone_chain`` is ancestor-bit-exact with
  ``resample_systematic`` + ``clone`` and produces a leaf-identical
  store, across every CopyMode, NULL table entries, and a 1-shard
  sharded trace (which composes).
* ``kv_cache.ensure_writable`` keeps its invariants when a write's
  dirty slice straddles the last valid row and the dump row (masked
  rows, degeneration at the block boundary).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import pool as pool_lib
from repro.core import store as store_lib
from repro.core.config import CopyMode
from repro.core.pool import NULL_BLOCK
from repro.core.store import StoreConfig
from repro.serving import kv_cache as kv_lib
from repro.serving.kv_cache import KVCacheConfig
from repro.smc import resampling

KEY = jax.random.PRNGKey(0)
LAZY_MODES = [CopyMode.LAZY, CopyMode.LAZY_SR]
ALL_MODES = [CopyMode.EAGER, CopyMode.LAZY, CopyMode.LAZY_SR]


def _delta_program(cfg: StoreConfig):
    """COW-heavy program: clones force sharing, mid-block writes force
    sub-block copies, masked writes leave rows untouched."""
    s = store_lib.create(cfg)
    rows = jnp.arange(cfg.n, dtype=jnp.float32)
    for t in range(4):
        s = store_lib.append(cfg, s, rows * 10 + t)
    # Mid-block clone: every survivor's tail block is shared mid-page.
    s = store_lib.clone(cfg, s, jnp.zeros((cfg.n,), jnp.int32))
    s = store_lib.append(cfg, s, rows + 100)  # divergence -> delta COW
    s = store_lib.write_at(
        cfg,
        s,
        jnp.full((cfg.n,), 1, jnp.int32),
        -rows,
        mask=jnp.asarray([i % 2 == 0 for i in range(cfg.n)]),
    )
    # Fill the tail block: the delta pages degenerate back to full.
    for t in range(cfg.block_size):
        s = store_lib.append(cfg, s, rows + 200 + t)
    s = store_lib.clone(
        cfg, s, jnp.asarray((np.arange(cfg.n) // 2).astype(np.int32))
    )
    return s


def _valid_prefix(cfg: StoreConfig, s) -> np.ndarray:
    """Batch trajectories with positions past each length zeroed."""
    mats = store_lib.materialize_batch(cfg, s, jnp.arange(cfg.n, dtype=jnp.int32))
    valid = np.arange(cfg.capacity)[None, :] < np.asarray(s.lengths)[:, None]
    out = np.asarray(mats).copy()
    out[~valid] = 0
    return out


class TestDeltaStoreObservational:
    @pytest.mark.parametrize("mode", LAZY_MODES)
    @pytest.mark.parametrize("use_kernels", [False, True])
    def test_delta_on_off_equivalent(self, mode, use_kernels):
        base = dict(
            mode=mode, n=4, block_size=3, max_blocks=6, num_blocks=40,
            use_kernels=use_kernels,
        )
        s_off = _delta_program(StoreConfig(**base))
        s_on = _delta_program(StoreConfig(**base, delta_cow=True))
        np.testing.assert_array_equal(
            np.asarray(s_off.lengths), np.asarray(s_on.lengths)
        )
        cfg_off = StoreConfig(**base)
        cfg_on = StoreConfig(**base, delta_cow=True)
        np.testing.assert_array_equal(
            _valid_prefix(cfg_off, s_off), _valid_prefix(cfg_on, s_on)
        )
        # Point reads resolve through parent pages identically.
        for t in (0, 2, 4, 5):
            idx = jnp.full((4,), t, jnp.int32)
            np.testing.assert_array_equal(
                np.asarray(store_lib.read_at(cfg_off, s_off, idx)),
                np.asarray(store_lib.read_at(cfg_on, s_on, idx)),
            )
        # Pool invariants hold with parents in play.
        assert bool(pool_lib.free_stack_consistent(s_on.pool))
        assert bool(pool_lib.refcount_matches_tables(s_on.pool, s_on.tables))

    @pytest.mark.parametrize("mode", LAZY_MODES)
    def test_delta_pages_actually_created(self, mode):
        """The program must exercise the delta path, not degenerate to
        whole-block copies (otherwise the parity above is vacuous)."""
        cfg = StoreConfig(
            mode=mode, n=4, block_size=3, max_blocks=6, num_blocks=40,
            delta_cow=True,
        )
        s = store_lib.create(cfg)
        rows = jnp.arange(4, dtype=jnp.float32)
        for t in range(4):
            s = store_lib.append(cfg, s, rows + t)
        s = store_lib.clone(cfg, s, jnp.zeros((4,), jnp.int32))
        s = store_lib.append(cfg, s, rows + 100)
        assert int((np.asarray(s.pool.parent) >= 0).sum()) > 0
        assert bool(np.asarray(s.pool.dirty).any())

    @pytest.mark.parametrize("mode", LAZY_MODES)
    def test_kernel_leaf_exact_under_delta(self, mode):
        """use_kernels flips the implementation, not the state: every
        pool leaf (including parent/dirty) is bitwise identical."""
        base = dict(
            mode=mode, n=4, block_size=3, max_blocks=6, num_blocks=40,
            delta_cow=True,
        )
        sj = _delta_program(StoreConfig(**base, use_kernels=False))
        sk = _delta_program(StoreConfig(**base, use_kernels=True))
        np.testing.assert_array_equal(np.asarray(sj.tables), np.asarray(sk.tables))
        np.testing.assert_array_equal(np.asarray(sj.lengths), np.asarray(sk.lengths))
        for leaf in ("data", "refcount", "frozen", "free_stack", "parent", "dirty"):
            np.testing.assert_array_equal(
                np.asarray(getattr(sj.pool, leaf)),
                np.asarray(getattr(sk.pool, leaf)),
                err_msg=leaf,
            )
        assert int(sj.pool.free_top) == int(sk.pool.free_top)

    def test_degeneration_clears_bookkeeping(self):
        """Filling a delta page's mask degenerates it to a full block:
        parent cleared, mask cleared, the parent reference released."""
        cfg = StoreConfig(
            mode=CopyMode.LAZY_SR, n=2, block_size=3, max_blocks=4,
            num_blocks=20, delta_cow=True,
        )
        s = store_lib.create(cfg)
        rows = jnp.arange(2, dtype=jnp.float32)
        s = store_lib.append(cfg, s, rows)  # pos 0 of block 0
        s = store_lib.clone(cfg, s, jnp.zeros((2,), jnp.int32))  # share
        for t in range(1, 3):  # pos 1: COW-delta; pos 2: in-place mark
            s = store_lib.append(cfg, s, rows + t)
        # The pre-share slot still resolves through the parent...
        assert (np.asarray(s.pool.parent) >= 0).any()
        # ...until a history rewrite fills the mask: the pages turn into
        # full blocks and the now-unreferenced parent is reclaimed.
        s = store_lib.write_at(cfg, s, jnp.zeros((2,), jnp.int32), rows + 50)
        assert not (np.asarray(s.pool.parent) >= 0).any()
        assert not np.asarray(s.pool.dirty).any()
        assert bool(pool_lib.free_stack_consistent(s.pool))
        assert bool(pool_lib.refcount_matches_tables(s.pool, s.tables))


def _effective_kv(cache, delta: bool) -> np.ndarray:
    """Per-sequence effective payload: ``[S, mb, L, 2, bs, KVH * hd]``
    with NULL blocks zeroed — delta pages resolved through parent."""
    pool = cache.pool
    tab = np.asarray(cache.tables)
    safe = np.maximum(tab, 0)
    data = np.asarray(pool.data)[safe]
    if delta:
        par = np.asarray(pool.parent)[safe]
        res = np.where(par >= 0, par, safe)
        sel = np.asarray(pool.dirty)[safe][:, :, None, None, :, None]
        data = np.where(sel, data, np.asarray(pool.data)[res])
    data[tab < 0] = 0
    # Zero positions at or past each sequence's length.
    s, mb = tab.shape
    bs = data.shape[4]
    pos = (np.arange(mb * bs).reshape(mb, bs))[None]  # [1, mb, bs]
    ok = pos < np.asarray(cache.lengths)[:, None, None]
    data = np.where(ok[:, :, None, None, :, None], data, 0)
    return data


def _kv_program(cfg: KVCacheConfig, steps: int = 5):
    """Token-by-token KV writes with a mid-block fork and masked rows."""
    cache = kv_lib.create(cfg)
    S = cfg.max_seqs
    k = jax.random.normal(KEY, (steps, cfg.n_layers, S, cfg.n_kv_heads, cfg.head_dim))
    for t in range(steps):
        if t == 2:  # mid-block fork: tails become shared mid-page
            cache = kv_lib.fork(cache, jnp.zeros((S,), jnp.int32))
        mask = jnp.asarray([True] * (S - 1) + [t % 2 == 0])
        cache, bid, pos = kv_lib.ensure_writable(cfg, cache, mask)
        for layer in range(cfg.n_layers):
            cache = kv_lib.write_kv(
                cfg, cache, bid, pos, layer, k[t, layer], -k[t, layer], mask
            )
        cache = kv_lib.advance(cache, mask)
    return cache


class TestKVCacheDelta:
    def _cfg(self, **kw):
        base = dict(
            n_layers=2, n_kv_heads=1, head_dim=4, block_size=4, max_seqs=3,
            max_blocks_per_seq=4, num_blocks=16,
        )
        base.update(kw)
        return KVCacheConfig(**base)

    def test_observational_parity_with_whole_block(self):
        c_off = self._cfg()
        c_on = self._cfg(delta_cow=True)
        cache_off = _kv_program(c_off)
        cache_on = _kv_program(c_on)
        np.testing.assert_array_equal(
            np.asarray(cache_off.lengths), np.asarray(cache_on.lengths)
        )
        np.testing.assert_array_equal(
            _effective_kv(cache_off, delta=False),
            _effective_kv(cache_on, delta=True),
        )
        assert int((np.asarray(cache_on.pool.parent) >= 0).sum()) > 0
        assert bool(pool_lib.free_stack_consistent(cache_on.pool))
        assert bool(pool_lib.refcount_matches_tables(cache_on.pool, cache_on.tables))

    def test_boundary_straddle_and_dump_row(self):
        """Regression: a step whose dirty slice straddles the last valid
        row and the dump row — masked rows park their delta bookkeeping
        scatter on the dump index (dropped), and the write that fills
        the page at the block boundary degenerates it cleanly."""
        cfg = self._cfg(delta_cow=True, block_size=3)
        cache = kv_lib.create(cfg)
        S = 3
        # Two tokens, fork at pos 2 -> shared mid-block tails.
        for t in range(2):
            mask = jnp.asarray([True, True, True])
            cache, bid, pos = kv_lib.ensure_writable(cfg, cache, mask)
            payload = jnp.full((S, 1, 4), float(t + 1))
            for layer in range(2):
                cache = kv_lib.write_kv(
                    cfg, cache, bid, pos, layer, payload, -payload, mask
                )
            cache = kv_lib.advance(cache, mask)
        cache = kv_lib.fork(cache, jnp.asarray([0, 0, 1], jnp.int32))
        # The straddling step: rows 0/1 delta-COW the shared tail (their
        # write lands at pos 2 — the page's last row), row 2 is masked
        # (its scatters must land on the dump row and be dropped).
        mask = jnp.asarray([True, True, False])
        cache, bid, pos = kv_lib.ensure_writable(cfg, cache, mask)
        payload = jnp.full((S, 1, 4), 9.0)
        for layer in range(2):
            cache = kv_lib.write_kv(
                cfg, cache, bid, pos, layer, payload, -payload, mask
            )
        cache = kv_lib.advance(cache, mask)
        pool = cache.pool
        nb = pool.num_blocks
        # Dump row stayed kept-zero, and its bookkeeping was dropped,
        # not written (the dirty/parent scatters have no row nb).
        assert not np.asarray(pool.data[nb]).any()
        # Rows 0/1 hold a delta page: only the boundary row is local,
        # slots 0..1 resolve through the still-live parent (KV appends
        # never rewrite history, so the page never degenerates).
        rows = np.arange(3)
        idx = np.asarray(cache.lengths) // 3
        tails = np.asarray(cache.tables)[rows, np.maximum(idx - 1, 0)]
        for s_i in (0, 1):
            b = tails[s_i]
            assert int(np.asarray(pool.parent)[b]) >= 0
            np.testing.assert_array_equal(
                np.asarray(pool.dirty)[b], np.asarray([False, False, True])
            )
        assert bool(pool_lib.free_stack_consistent(pool))
        assert bool(pool_lib.refcount_matches_tables(pool, cache.tables))
        # And the payload is what the whole-block path would hold:
        # tokens 1, 2 from the shared prefix, 9 at the boundary row.
        eff = _effective_kv(cache, delta=True)
        got = eff[0, 0, 0, 0, :3, 0]  # seq 0, block 0, layer 0, K, head 0 dim 0
        np.testing.assert_array_equal(got, np.asarray([1.0, 2.0, 9.0]))

    def test_free_cascade_reclaims_everything(self):
        cfg = self._cfg(delta_cow=True)
        cache = _kv_program(cfg)
        cache = kv_lib.free(cache, jnp.asarray([True] * 3))
        assert int(pool_lib.blocks_in_use(cache.pool)) == 0
        assert not (np.asarray(cache.pool.parent) >= 0).any()
        assert not np.asarray(cache.pool.dirty).any()
        assert bool(pool_lib.free_stack_consistent(cache.pool))


class TestCloneChainParity:
    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_op_matches_composed(self, use_kernel):
        """Fused op vs resample_systematic + gather + histogram, with
        NULL entries in the tables."""
        from repro.kernels.clone_chain import clone_chain
        from repro.kernels.refcount_update.ref import refcount_delta_ref

        for n, mb, nb, seed in [(8, 4, 30, 0), (33, 5, 40, 1), (256, 3, 64, 2)]:
            key = jax.random.PRNGKey(seed)
            logw = jax.random.normal(jax.random.PRNGKey(seed + 50), (n,))
            tables = jax.random.randint(
                jax.random.PRNGKey(seed + 99), (n, mb), -1, nb
            ).astype(jnp.int32)
            anc0 = resampling.resample_systematic(key, logw)
            new0 = tables[anc0]
            d0, m0 = refcount_delta_ref(new0.reshape(-1), tables.reshape(-1), nb)
            anc, new, d, m = clone_chain(
                key, logw, tables, num_blocks=nb,
                use_kernel=use_kernel, interpret=use_kernel,
            )
            np.testing.assert_array_equal(np.asarray(anc), np.asarray(anc0))
            np.testing.assert_array_equal(np.asarray(new), np.asarray(new0))
            np.testing.assert_array_equal(np.asarray(d), np.asarray(d0))
            np.testing.assert_array_equal(np.asarray(m), np.asarray(m0))

    @pytest.mark.parametrize("mode", ALL_MODES)
    @pytest.mark.parametrize("use_kernels", [False, True])
    @pytest.mark.parametrize("delta_cow", [False, True])
    def test_store_matches_composed(self, mode, use_kernels, delta_cow):
        if mode is CopyMode.EAGER and (use_kernels or delta_cow):
            pytest.skip("EAGER has no pool/kernels")
        cfg = StoreConfig(
            mode=mode, n=6, block_size=3, max_blocks=4, num_blocks=40,
            use_kernels=use_kernels, delta_cow=delta_cow,
        )
        s = store_lib.create(cfg)
        rows = jnp.arange(6, dtype=jnp.float32)
        for t in range(7):  # trailing table entries stay NULL
            s = store_lib.append(cfg, s, rows + t)
        logw = jax.random.normal(jax.random.PRNGKey(7), (6,))
        k = jax.random.PRNGKey(42)
        s0 = store_lib.clone(cfg, s, resampling.resample_systematic(k, logw))
        s1, anc = store_lib.clone_chain(cfg, s, k, logw)
        np.testing.assert_array_equal(
            np.asarray(anc),
            np.asarray(resampling.resample_systematic(k, logw)),
        )
        np.testing.assert_array_equal(np.asarray(s0.lengths), np.asarray(s1.lengths))
        if mode is CopyMode.EAGER:
            np.testing.assert_array_equal(np.asarray(s0.dense), np.asarray(s1.dense))
            return
        np.testing.assert_array_equal(np.asarray(s0.tables), np.asarray(s1.tables))
        for leaf in ("data", "refcount", "frozen", "free_stack", "parent", "dirty"):
            np.testing.assert_array_equal(
                np.asarray(getattr(s0.pool, leaf)),
                np.asarray(getattr(s1.pool, leaf)),
                err_msg=leaf,
            )
        assert int(s0.pool.free_top) == int(s1.pool.free_top)

    def test_sharded_1shard_trace_composes(self):
        """A 1-shard sharded token trace routes clone_chain through the
        composed sharded clone with the identical ancestors."""
        from repro.serving.smc_decode import _TokenTrace

        mesh = Mesh(np.array(jax.devices()[:1]), ("shards",))
        steps = 6
        tr_sh = _TokenTrace(4, steps, CopyMode.LAZY_SR, 3, mesh, "shards")
        tr_1d = _TokenTrace(4, steps, CopyMode.LAZY_SR, 3, None, "shards")
        for t in range(4):
            tok = jnp.arange(4, dtype=jnp.int32) + 10 * t
            tr_sh.append(tok)
            tr_1d.append(tok)
        logw = jax.random.normal(jax.random.PRNGKey(3), (4,))
        k = jax.random.PRNGKey(11)
        anc_sh = tr_sh.clone_chain(k, logw)
        anc_1d = tr_1d.clone_chain(k, logw)
        np.testing.assert_array_equal(np.asarray(anc_sh), np.asarray(anc_1d))
        np.testing.assert_array_equal(
            np.asarray(tr_sh.tokens(4)), np.asarray(tr_1d.tokens(4))
        )

    def test_scheduler_fork_unchanged_by_fusion(self):
        """The fused fork path must leave the scheduled decode
        token-bit-exact: smc_token_update's ancestors and the trace's
        clone_chain ancestors are drawn from the same key."""
        from repro.serving.smc_decode import smc_token_update

        key = jax.random.PRNGKey(5)
        logits = jax.random.normal(jax.random.PRNGKey(6), (4, 11))
        logw = jnp.full((4,), -np.log(4.0))
        out = smc_token_update(
            key, logits, logw, jnp.zeros(()), n=4,
            target_temp=0.3, proposal_temp=1.0, ess_threshold=1.1,
        )
        _, _, new_logw, _, _, do_res, anc, k_res = out
        assert do_res and anc is not None
        np.testing.assert_array_equal(
            np.asarray(anc),
            np.asarray(resampling.resample_systematic(k_res, new_logw)),
        )
