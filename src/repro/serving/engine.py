"""Batched decode engine over the COW-paged KV cache.

Supports the full-attention families (dense / audio / moe).  The decode
step is a single jitted function (params, cache, tokens, mask) ->
(logits, cache): per token it resolves one writable block (the COW GET),
then every layer projects K/V for the new token, writes them into the
block, and attends through the block table (the Pallas paged-attention
kernel on TPU; its jnp oracle on CPU hosts).

``prefill`` bulk-writes a prompt's K/V pages (all sequences share code
with the training forward), after which ``fork`` can replicate the
prompt across a population for O(1) — see smc_decode.py.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.ops import paged_attention
from repro.models import attention as attn_lib
from repro.models.config import ModelConfig
from repro.models.layers import embed, mlp, rms_norm, unembed
from repro.models.model import LanguageModel
from repro.models import moe as moe_lib
from repro.serving import kv_cache as kvc
from repro.serving.kv_cache import KVCacheConfig, PagedKVCache

SUPPORTED_FAMILIES = ("dense", "audio", "moe")


class ServeEngine:
    def __init__(
        self,
        lm: LanguageModel,
        params,
        cache_cfg: Optional[KVCacheConfig] = None,
        *,
        max_seqs: int = 8,
        max_len: int = 256,
    ):
        cfg = lm.cfg
        if cfg.family not in SUPPORTED_FAMILIES:
            raise NotImplementedError(
                f"paged serving for family '{cfg.family}' uses the dense-cache "
                "decode path (LanguageModel.decode_step); paged support covers "
                f"{SUPPORTED_FAMILIES}"
            )
        self.lm = lm
        self.params = params
        if cache_cfg is None:
            cache_cfg = KVCacheConfig(
                n_layers=cfg.n_layers,
                n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.hd,
                max_seqs=max_seqs,
                max_blocks_per_seq=-(-max_len // 16),
                dtype=cfg.dtype,
            )
        self.cache_cfg = cache_cfg
        self.cache = kvc.create(cache_cfg)
        self._step = jax.jit(partial(_decode_step, lm.cfg, cache_cfg))
        self._prefill = jax.jit(partial(_prefill, lm.cfg, cache_cfg))

    # -- stateful convenience wrappers -----------------------------------
    def prefill(self, tokens: jax.Array, seq_ids: jax.Array) -> jax.Array:
        logits, self.cache = self._prefill(self.params, self.cache, tokens, seq_ids)
        return logits

    def decode(self, tokens: jax.Array, mask: Optional[jax.Array] = None) -> jax.Array:
        if mask is None:
            mask = self.cache.lengths > 0
        logits, self.cache = self._step(self.params, self.cache, tokens, mask)
        return logits

    def fork(self, ancestors: jax.Array) -> None:
        self.cache = kvc.fork(self.cache, ancestors)

    def free(self, mask: jax.Array) -> None:
        self.cache = kvc.free(self.cache, mask)

    # -- slot-range ops (the scheduler's packed slot table, DESIGN.md §8) ----
    def fork_slots(self, lo: int, ancestors_local: jax.Array) -> None:
        """Fork within the slot range ``[lo, lo + len(ancestors_local))``.

        The global ancestor vector is the identity outside the range, so
        other requests' sequences are untouched (an identity row adds
        then removes one reference — never frees, never reorders the
        free stack).  With a single request spanning the whole table
        this is exactly ``fork(ancestors_local)``.
        """
        n = ancestors_local.shape[0]
        anc = jnp.arange(self.cache_cfg.max_seqs, dtype=jnp.int32)
        anc = anc.at[lo : lo + n].set(lo + ancestors_local.astype(jnp.int32))
        self.cache = kvc.fork(self.cache, anc)

    def free_slots(self, lo: int, n: int) -> None:
        """Release the sequences in slot range ``[lo, lo + n)`` (refcount
        GC reclaims every page not shared outside the range)."""
        mask = jnp.zeros((self.cache_cfg.max_seqs,), jnp.bool_)
        self.cache = kvc.free(self.cache, mask.at[lo : lo + n].set(True))

    def compact_cache(self, new_num_blocks: int | None = None) -> None:
        """Densify live pages (optionally shrink-to-fit) between decode
        steps; observationally invisible — attention reads through the
        rewritten tables (DESIGN.md §3.1)."""
        self.cache = kvc.compact(self.cache, new_num_blocks)

    def grow_cache(self, new_num_blocks: int) -> None:
        """Expand the KV page pool between decode steps (DESIGN.md §3.1).

        Sequence tables stay valid (ids preserved); the jitted decode /
        prefill recompile on the next call (shape-keyed) since the cache
        leaves change shape.  Capped growth loops live in the callers
        (e.g. ``SMCDecoder``), which watch ``free_blocks`` per token.
        """
        self.cache = kvc.grow(self.cache, new_num_blocks)

    @property
    def used_blocks(self) -> int:
        return int(kvc.used_blocks(self.cache))

    @property
    def free_blocks(self) -> int:
        return int(kvc.free_blocks(self.cache))

    @property
    def oom(self) -> bool:
        return bool(kvc.oom_flag(self.cache))

    @property
    def num_blocks(self) -> int:
        return self.cache.pool.num_blocks


# ---------------------------------------------------------------------------
# functional core
# ---------------------------------------------------------------------------


def _attn_block(
    cfg: ModelConfig, ccfg: KVCacheConfig, p, h, cache, bid, pos, layer, mask,
    lengths_incl,
):
    """One attention sub-block in paged-decode mode. h: [S, 1, D]."""
    hn = rms_norm(h, p["ln1"]["scale"], cfg.norm_eps)
    q, k_new, v_new = attn_lib.qkv_proj(p["attn"], hn, cfg)
    position = cache.lengths  # pre-append position of the new token
    q = attn_lib.apply_rope(q, position[:, None], cfg.rope_theta)
    k_new = attn_lib.apply_rope(k_new, position[:, None], cfg.rope_theta)
    cache = kvc.write_kv(
        ccfg, cache, bid, pos, layer, k_new[:, 0], v_new[:, 0], mask
    )
    k_pool, v_pool = kvc.layer_views(ccfg, cache, layer)
    # COW-native decode: under delta COW the attention gather resolves
    # delta pages through parent/dirty in place — no materialize pass.
    delta = dict(
        parent=cache.pool.parent, dirty=cache.pool.dirty
    ) if ccfg.delta_cow else {}
    out = paged_attention(
        q[:, 0], k_pool, v_pool, cache.tables, lengths_incl, **delta
    )
    h = h + attn_lib.out_proj(p["attn"], out[:, None])
    return h, cache


def _decode_step(
    cfg: ModelConfig,
    ccfg: KVCacheConfig,
    params,
    cache: PagedKVCache,
    tokens: jax.Array,  # [S, 1]
    mask: jax.Array,  # [S]
):
    dt = jnp.dtype(cfg.dtype)
    x = embed(params["embed"], tokens, dt)  # [S, 1, D]
    cache, bid, pos = kvc.ensure_writable(ccfg, cache, mask)
    lengths_incl = cache.lengths + jnp.where(mask, 1, 0)  # include new token

    n_scan = cfg.n_layers - (
        1 if (cfg.family == "moe" and cfg.first_layer_dense) else 0
    )
    layer_offset = cfg.n_layers - n_scan

    if cfg.family == "moe" and cfg.first_layer_dense:
        p0 = params["block0"]
        x, cache = _attn_block(
            cfg, ccfg, p0, x, cache, bid, pos, 0, mask, lengths_incl
        )
        x = x + mlp(p0["mlp"], rms_norm(x, p0["ln2"]["scale"], cfg.norm_eps), cfg.act)

    # scan over layers with the cache data threaded through the carry
    def body(carry, inp):
        h, data = carry
        p, layer_idx = inp
        cache_l = cache._replace(pool=cache.pool._replace(data=data))
        h, cache_l = _attn_block(
            cfg, ccfg, p, h, cache_l, bid, pos, layer_idx, mask, lengths_incl
        )
        hn = rms_norm(h, p["ln2"]["scale"], cfg.norm_eps)
        if cfg.family == "moe":
            h = h + moe_lib.moe_layer(p["moe"], hn, cfg)
        else:
            h = h + mlp(p["mlp"], hn, cfg.act)
        return (h, cache_l.pool.data), None

    layer_ids = jnp.arange(n_scan, dtype=jnp.int32) + layer_offset
    (x, data), _ = jax.lax.scan(
        body, (x, cache.pool.data), (params["blocks"], layer_ids)
    )
    cache = cache._replace(pool=cache.pool._replace(data=data))

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    table = params.get("unembed", params["embed"])
    logits = unembed(table, x)[:, 0]
    cache = kvc.advance(cache, mask)
    return logits, cache


def _prefill(
    cfg: ModelConfig,
    ccfg: KVCacheConfig,
    params,
    cache: PagedKVCache,
    tokens: jax.Array,  # [B, S] (S % block_size == 0 is not required)
    seq_ids: jax.Array,  # [B] slots to fill
):
    """Run the training forward and bulk-write K/V pages for the prompt."""
    b, s = tokens.shape
    bs = ccfg.block_size
    nb = -(-s // bs)
    pad = nb * bs - s

    # collect per-layer K/V via the same replay the dense-cache path uses
    dt = jnp.dtype(cfg.dtype)
    x = embed(params["embed"], tokens, dt)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def layer_kv(p, h):
        hn = rms_norm(h, p["ln1"]["scale"], cfg.norm_eps)
        _, k_new, v_new = attn_lib.qkv_proj(p["attn"], hn, cfg)
        k_new = attn_lib.apply_rope(k_new, positions, cfg.rope_theta)
        h = h + attn_lib.attention_train(p["attn"], hn, cfg, positions)
        hn2 = rms_norm(h, p["ln2"]["scale"], cfg.norm_eps)
        if cfg.family == "moe":
            h = h + moe_lib.moe_layer(p["moe"], hn2, cfg)
        else:
            h = h + mlp(p["mlp"], hn2, cfg.act)
        return h, (k_new, v_new)

    kvs = []
    if cfg.family == "moe" and cfg.first_layer_dense:
        x, kv0 = layer_kv(params["block0"], x)
        kvs.append(kv0)
    x, (k_all, v_all) = jax.lax.scan(
        lambda h, p: layer_kv(p, h), x, params["blocks"]
    )
    if kvs:
        k_all = jnp.concatenate([kvs[0][0][None], k_all], 0)
        v_all = jnp.concatenate([kvs[0][1][None], v_all], 0)

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(params.get("unembed", params["embed"]), x)[:, -1]

    # allocate nb blocks per prompt sequence and write pages
    pool, tables, lengths = cache.pool, cache.tables, cache.lengths
    from repro.core import pool as pool_lib

    for j in range(nb):
        pool, bids = pool_lib.alloc(pool, b)
        tables = tables.at[seq_ids, j].set(bids)
    # [L, B, S, KVH, hd] -> pad, reshape into pages [B * nb, L, bs, KVH * hd]
    def pages(arr):
        arr = jnp.pad(arr, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        L = arr.shape[0]
        arr = arr.reshape(L, b, nb, bs, cfg.n_kv_heads * cfg.hd)
        return arr.transpose(1, 2, 0, 3, 4).reshape(b * nb, L, bs, -1)

    kp, vp = pages(k_all), pages(v_all)
    page_bids = tables[seq_ids, :nb].reshape(-1)  # [b*nb]
    data = pool.data.at[page_bids, :, 0].set(kp.astype(pool.data.dtype))
    data = data.at[page_bids, :, 1].set(vp.astype(pool.data.dtype))
    pool = pool._replace(data=data)
    lengths = lengths.at[seq_ids].set(s)
    return logits, PagedKVCache(pool=pool, tables=tables, lengths=lengths)
