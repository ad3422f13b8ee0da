#!/usr/bin/env python3
"""Bring-up smoke test: drive the served path and the filter path on a TPU.

    python chip_smoke.py             # one chip: the serve and filter phases
    python chip_smoke.py --chips 4   # four chips: sharded filter + router only

One process drives the chip for every phase.  Each phase prints what it
measured (device kind, compile and wall seconds, ``peak_bytes_in_use``,
and every check with its value and limit); a failed check raises and the
script exits non-zero.  So does a host without a TPU: there is no CPU
fallback and no interpret mode here.  The last line of standard output
is one JSON object, ``{"ok": true, "device": {...}}``.

Phases on one chip:

* **serve** — ``musicgen_large`` at its published widths (depth cut to
  fit one 16 GB chip, printed), random weights from ``--seed``, through
  ``ServeEngine`` and ``Scheduler``: 4 requests x 8 particles, prompt
  64, 48 steps, once on a plain and once on a delta-COW KV pool.  Before
  each schedule, one decode step through the Pallas paged attention is
  compared with the same step through its jnp oracle, and on the plain
  pool one forced compaction (the ``pool_compact`` kernel) must leave
  the next step's logits bit-identical.
* **filter** — the RBPF program at the paper's N = 2048, T = 500 under
  LAZY_SR, with the store kernels on (and delta COW) against the jnp
  store; then each store kernel is compared with its oracle on the
  final store state.

With ``--chips 4``: the sharded ``ParticleFilter`` over a 4-chip mesh
against one device, and a ``Router`` over four one-chip replicas
against a one-replica fleet.

Numbers printed here are bring-up measurements of one cold run, not
benchmark results.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# musicgen_large at 48 layers needs 9.7 GB of f32 weights, their 4.8 GB
# bf16 cast and two 1.8 GB KV pools (step input and output) at once:
# 18.2 GB in the compiled decode step, more than the chip's 16 GB.
SERVE_LAYERS = 32
ROUTER_LAYERS = 4  # the router phase exercises placement, not depth
KV_BLOCK = 16
KV_POOL_BLOCKS = 288  # > 32 sequences x 7 pages + one COW transient each
N_REQUESTS, N_PARTICLES, PROMPT, STEPS = 4, 8, 64, 48
# bf16 activations: the kernel and the oracle sum attention in a
# different order, so the cast outputs differ in last bits per layer.
LOGITS_REL_TOL = 3e-2
SHARDED_LOGZ_TOL = 3.0  # benchmarks/bench_sharded.py's agreement bound

_COMPILE_S = [0.0]


def _on_duration(event: str, secs: float, **_) -> None:
    if event.endswith("backend_compile_duration"):
        _COMPILE_S[0] += secs


class Phase:
    """Times a phase and prints its record when it ends."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "Phase":
        import jax

        jax.effects_barrier()
        print(f"== phase {self.name}", flush=True)
        self.t0, self.c0 = time.perf_counter(), _COMPILE_S[0]
        return self

    def check(self, name: str, value, ok: bool, limit) -> None:
        print(f"   check {self.name}.{name} = {value} (limit {limit}): "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        if not ok:
            raise SystemExit(f"chip_smoke: check {self.name}.{name} failed")

    def note(self, text: str) -> None:
        print(f"   {text}", flush=True)

    def __exit__(self, exc_type, *_) -> None:
        import jax

        if exc_type is not None:
            return
        jax.effects_barrier()
        dev = jax.devices()[0]
        record = {
            "phase": self.name,
            "device_kind": dev.device_kind,
            "compile_s": _COMPILE_S[0] - self.c0,
            "wall_s": time.perf_counter() - self.t0,
            "peak_bytes_in_use": [
                (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()
            ],
        }
        print(f"   record {json.dumps(record)}", flush=True)


def _kernel_calls(fn, *args) -> int:
    """Pallas kernels compiled into ``fn``'s program (0 = an oracle ran)."""
    import jax

    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


# -- serve ---------------------------------------------------------------------


def _decode_oracle(eng):
    """The engine's decode step with paged attention routed to its jnp
    oracle (traced under the patch on first call, cached after)."""
    import jax

    from repro.kernels.paged_attention.ops import paged_attention
    from repro.serving import engine as engine_lib

    step = jax.jit(
        functools.partial(engine_lib._decode_step, eng.lm.cfg, eng.cache_cfg)
    )
    oracle = functools.partial(paged_attention, use_kernel=False)

    def run(*args):
        with mock.patch.object(engine_lib, "paged_attention", oracle):
            return step(*args)

    return run


def _attention_parity(ph: Phase, eng, key, compact: bool) -> None:
    """One decode step after a mid-page fork: Pallas vs oracle logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import kv_cache as kvc

    cfg, ccfg = eng.lm.cfg, eng.cache_cfg
    s = ccfg.max_seqs
    k1, k2 = jax.random.split(key)
    plen = PROMPT - KV_BLOCK // 2  # the shared tail page is half full
    eng.prefill(
        jax.random.randint(k1, (s, plen), 0, cfg.vocab_size),
        jnp.arange(s, dtype=jnp.int32),
    )
    # Families of N_PARTICLES share one prompt, tail page included: the
    # next token copies that page (a delta page under delta COW).
    eng.fork(jnp.arange(s, dtype=jnp.int32) // N_PARTICLES * N_PARTICLES)
    tok = jax.random.randint(k2, (s, 1), 0, cfg.vocab_size)
    mask = jnp.ones((s,), jnp.bool_)
    pre = eng.cache
    calls = _kernel_calls(eng._step, eng.params, pre, tok, mask)
    ph.check("paged_attention_kernels_in_step", calls, calls >= 1, ">= 1")
    logits_k, out = eng._step(eng.params, pre, tok, mask)
    delta_pages = int(jnp.sum(out.pool.parent >= 0))
    del out
    logits_r, out = _decode_oracle(eng)(eng.params, pre, tok, mask)
    del out
    a = np.asarray(logits_k, np.float32)
    b = np.asarray(logits_r, np.float32)
    finite = bool(np.isfinite(a).all())
    ph.check("logits_finite", finite, finite, True)
    rel = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    ph.check("logits_rel_l2_kernel_vs_oracle", rel, rel <= LOGITS_REL_TOL,
             LOGITS_REL_TOL)
    ph.note(f"argmax agreement kernel vs oracle: "
            f"{float(np.mean(a.argmax(-1) == b.argmax(-1)))}")
    if ccfg.delta_cow:
        ph.check("delta_pages_read", delta_pages, delta_pages > 0, "> 0")
    if compact:
        del pre
        calls = _kernel_calls(kvc.compact, eng.cache)
        ph.check("pool_compact_kernels", calls, calls >= 1, ">= 1")
        eng.compact_cache()
        logits_c, out = eng._step(eng.params, eng.cache, tok, mask)
        del out
        diff = float(np.max(np.abs(np.asarray(logits_c, np.float32) - a)))
        ph.check("logits_max_abs_diff_after_compaction", diff, diff == 0.0, 0.0)
    eng.free(mask)


def _schedule(ph: Phase, eng, key) -> None:
    import jax
    import numpy as np

    from repro.serving.scheduler import DecodeRequest, Scheduler

    cfg = eng.lm.cfg
    # Growth stays on: the per-request token-history stores start small
    # and grow; the KV pool is sized so that it never has to.
    sched = Scheduler(eng)
    keys = jax.random.split(key, 2 * N_REQUESTS)
    for i in range(N_REQUESTS):
        sched.submit(DecodeRequest(
            rid=f"req{i}",
            prompt=jax.random.randint(keys[2 * i], (PROMPT,), 0, cfg.vocab_size),
            n_particles=N_PARTICLES,
            steps=STEPS,
            key=keys[2 * i + 1],
        ))
    t0 = time.perf_counter()
    res = sched.run()
    wall = time.perf_counter() - t0
    statuses = sorted({r.status for r in res.values()})
    ph.check("requests_done", len(res), len(res) == N_REQUESTS, N_REQUESTS)
    ph.check("statuses", statuses, statuses == ["ok"], ["ok"])
    oom = any(bool(r.oom) for r in res.values())
    ph.check("oom", oom, not oom, False)
    toks = np.stack([np.asarray(r.tokens) for r in res.values()])
    ok = bool(((toks >= 0) & (toks < cfg.vocab_size)).all())
    ph.check("tokens_in_vocab", ok, ok, True)
    ph.check("kv_pool_blocks", eng.num_blocks, eng.num_blocks == KV_POOL_BLOCKS,
             f"{KV_POOL_BLOCKS} (no growth, no recompile)")
    peak = max(int(np.max(np.asarray(r.used_blocks_trace))) for r in res.values())
    ph.note(f"schedule: {N_REQUESTS} x {N_PARTICLES} particles x {STEPS} "
            f"tokens in {wall:.3f} s wall (compiles included), "
            f"{sched.stats.ticks} ticks, peak KV blocks {peak}")


def serve_phase(seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.models.model import LanguageModel
    from repro.serving.engine import ServeEngine
    from repro.serving.kv_cache import KVCacheConfig

    full = get_config("musicgen_large")
    cfg = full.scaled(n_layers=SERVE_LAYERS)
    print(f"serve: {full.name} widths d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size}; depth cut "
          f"{full.n_layers} -> {cfg.n_layers} layers to fit one chip", flush=True)
    lm = LanguageModel(cfg)
    with Phase("serve_init") as ph:
        params, _ = lm.init(jax.random.PRNGKey(seed))
        jax.block_until_ready(params)
        ph.note(f"params: {sum(x.size for x in jax.tree.leaves(params))} "
                f"({cfg.param_dtype})")
    max_blocks = -(-(PROMPT + STEPS) // KV_BLOCK)
    for delta in (False, True):
        ccfg = KVCacheConfig(
            n_layers=cfg.n_layers,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.hd,
            block_size=KV_BLOCK,
            max_seqs=N_REQUESTS * N_PARTICLES,
            max_blocks_per_seq=max_blocks,
            num_blocks=KV_POOL_BLOCKS,
            dtype=cfg.dtype,
            delta_cow=delta,
        )
        name = "serve_delta" if delta else "serve"
        with Phase(name) as ph:
            eng = ServeEngine(lm, params, ccfg)
            _attention_parity(ph, eng, jax.random.PRNGKey(seed + 1), compact=not delta)
            _schedule(ph, eng, jax.random.PRNGKey(seed + 2))
            del eng


# -- filter --------------------------------------------------------------------


def _run_filter(ssm, params, ys, key, *, use_kernels, delta_cow, mesh=None):
    import jax

    from repro.core.config import CopyMode
    from repro.smc.filters import FilterConfig, ParticleFilter
    from repro.smc.programs import rbpf

    pf = ParticleFilter(ssm, FilterConfig(
        n_particles=rbpf.PAPER_N, n_steps=rbpf.PAPER_T, mode=CopyMode.LAZY_SR,
        use_kernels=use_kernels, mesh=mesh,
    ))
    if delta_cow:
        pf.store_cfg = dataclasses.replace(pf.store_cfg, delta_cow=True)
    t0 = time.perf_counter()
    res = pf.jitted()(key, params, ys)
    jax.block_until_ready(res.log_evidence)
    return pf, res, time.perf_counter() - t0


def _store_kernel_parity(ph: Phase, scfg, store, key) -> None:
    """Each store kernel against its oracle on the final store state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import store as store_lib
    from repro.kernels.clone_chain import clone_chain
    from repro.kernels.cow_gather import cow_gather, pool_compact
    from repro.kernels.refcount_update import refcount_update

    n, nb = scfg.n, store.pool.num_blocks
    k1, k2, k3, k4 = jax.random.split(key, 4)
    perm = jax.random.permutation(k1, n).astype(jnp.int32)
    pos = jax.random.randint(k2, (n,), 0, scfg.capacity)
    vals = jax.random.normal(k3, (n, *scfg.item_shape))
    logw = jax.random.normal(k4, (n,))
    cfgs = {uk: dataclasses.replace(scfg, use_kernels=uk) for uk in (True, False)}
    # Each case takes the kernel switch (static) and the store; arrays go
    # in as arguments so no program embeds the pool as a constant.
    cases = {
        "cow_gather": lambda uk, st: cow_gather(
            st.pool.data, st.tables.reshape(-1), use_kernel=uk),
        "pool_compact": lambda uk, st: pool_compact(
            st.pool.data,
            jnp.nonzero(st.pool.refcount > 0, size=nb, fill_value=-1)[0]
            .astype(jnp.int32),
            use_kernel=uk),
        "refcount_update": lambda uk, st: refcount_update(
            st.pool.refcount, st.pool.frozen, st.tables[perm], st.tables,
            do_freeze=True, use_kernel=uk),
        "clone_chain": lambda uk, st: clone_chain(
            k4, logw, st.tables, num_blocks=nb, use_kernel=uk),
        # write_at rewrites earlier items: COW copies of shared blocks
        # (delta copies under delta COW) through cow_write.
        "cow_write": lambda uk, st: store_lib.write_at(
            cfgs[uk], st, pos, vals).pool.data[:nb],
    }
    for name, fn in cases.items():
        kernel = jax.jit(functools.partial(fn, True))
        oracle = jax.jit(functools.partial(fn, False))
        calls = _kernel_calls(kernel, store)
        ph.check(f"{name}_kernels", calls, calls >= 1, ">= 1")
        got, want = jax.tree.leaves(kernel(store)), jax.tree.leaves(oracle(store))
        same = all(
            bool(np.array_equal(np.asarray(g), np.asarray(w)))
            for g, w in zip(got, want, strict=True)
        )
        ph.check(f"{name}_equals_oracle", same, same, "bit-exact")


def filter_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.core import store as store_lib
    from repro.smc.programs import rbpf

    ssm, params = rbpf.build()
    key = jax.random.PRNGKey(seed)
    ys = rbpf.gen_data(jax.random.PRNGKey(seed + 3), rbpf.PAPER_T)
    with Phase("filter") as ph:
        pf_j, res_j, wall_j = _run_filter(ssm, params, ys, key,
                                          use_kernels=False, delta_cow=False)
        pf_k, res_k, wall_k = _run_filter(ssm, params, ys, key,
                                          use_kernels=True, delta_cow=True)
        ph.note(f"rbpf N={rbpf.PAPER_N} T={rbpf.PAPER_T}: jnp store {wall_j:.3f} s, "
                f"kernel+delta store {wall_k:.3f} s (first call, compile included)")
        scfg = pf_k.store_cfg
        dense = scfg.n * scfg.max_blocks
        for tag, res in (("jnp", res_j), ("kernel", res_k)):
            ph.check(f"oom_{tag}", bool(res.oom), not bool(res.oom), False)
            peak = int(res.store.peak_blocks)
            ph.check(f"peak_blocks_{tag}", peak, peak < dense, f"< {dense} (dense)")
        lz_j, lz_k = float(res_j.log_evidence), float(res_k.log_evidence)
        tol = 1e-4 * abs(lz_j) + 1e-3
        ph.check("log_evidence_kernel_vs_jnp", [lz_k, lz_j],
                 abs(lz_k - lz_j) <= tol, f"|diff| <= {tol}")
        ids = np.arange(scfg.n)
        tj = store_lib.materialize_batch(pf_j.store_cfg, res_j.store, ids)
        tk = store_lib.materialize_batch(scfg, res_k.store, ids)
        same = bool(np.array_equal(np.asarray(tj), np.asarray(tk)))
        ph.check("trajectories_kernel_vs_jnp", same, same, "bit-exact")
        _store_kernel_parity(ph, scfg, res_k.store, jax.random.PRNGKey(seed + 4))


# -- four chips ----------------------------------------------------------------


def sharded_filter_phase(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.distributed import sharded_store as sharded_lib
    from repro.smc.programs import rbpf

    ssm, params = rbpf.build()
    key = jax.random.PRNGKey(seed)
    ys = rbpf.gen_data(jax.random.PRNGKey(seed + 3), rbpf.PAPER_T)
    with Phase("sharded_filter") as ph:
        mesh = Mesh(np.array(jax.devices()[:4]), ("shards",))
        _, res1, wall1 = _run_filter(ssm, params, ys, key,
                                     use_kernels=False, delta_cow=False)
        pf4, res4, wall4 = _run_filter(ssm, params, ys, key,
                                       use_kernels=False, delta_cow=False, mesh=mesh)
        ph.note(f"rbpf N={rbpf.PAPER_N} T={rbpf.PAPER_T}: one device {wall1:.3f} s, "
                f"4-chip mesh {wall4:.3f} s (first call, compile included)")
        shards = {d.id for d in res4.store.tables.devices()}
        ph.check("store_devices", sorted(shards), len(shards) == 4, "4 distinct")
        ph.check("oom", bool(res4.oom), not bool(res4.oom), False)
        peak = np.asarray(sharded_lib.peak_blocks_per_shard(pf4.sharded_cfg, res4.store))
        ph.note(f"peak blocks per shard: {peak.tolist()}")
        d = abs(float(res4.log_evidence) - float(res1.log_evidence))
        ph.check("log_evidence_4chip_vs_1", [float(res4.log_evidence),
                 float(res1.log_evidence)], d < SHARDED_LOGZ_TOL,
                 f"|diff| < {SHARDED_LOGZ_TOL}")


def router_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models.model import LanguageModel
    from repro.serving.engine import ServeEngine
    from repro.serving.kv_cache import KVCacheConfig
    from repro.serving.router import Router, make_replicas
    from repro.serving.scheduler import DecodeRequest, Scheduler

    cfg = get_config("musicgen_large").scaled(n_layers=ROUTER_LAYERS)
    lm = LanguageModel(cfg)
    n_req, steps = 4, 12  # one request per replica
    ccfg = KVCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        block_size=KV_BLOCK, max_seqs=2 * N_PARTICLES,
        max_blocks_per_seq=-(-(PROMPT + steps) // KV_BLOCK), dtype=cfg.dtype,
    )
    keys = jax.random.split(jax.random.PRNGKey(seed + 5), 2 * n_req)
    prompts = [np.asarray(jax.random.randint(keys[2 * i], (PROMPT,), 0, cfg.vocab_size))
               for i in range(n_req)]

    def requests():
        return [DecodeRequest(rid=f"req{i}", prompt=prompts[i],
                              n_particles=N_PARTICLES, steps=steps,
                              key=np.asarray(keys[2 * i + 1]))
                for i in range(n_req)]

    def build(i, dev):
        params, _ = lm.init(jax.random.PRNGKey(seed))
        return Scheduler(ServeEngine(lm, params, ccfg))

    print(f"router: {cfg.name} widths, depth cut to {cfg.n_layers} layers", flush=True)
    with Phase("router") as ph:
        scheds, devs = make_replicas(build, devices=jax.devices()[:4])
        homes = [{d.id for d in s.engine.cache.pool.data.devices()} for s in scheds]
        ph.check("replica_cache_devices", homes,
                 len({frozenset(h) for h in homes}) == 4 and all(len(h) == 1 for h in homes),
                 "one distinct device each")
        router = Router(scheds, devices=devs)
        for r in requests():
            router.submit(r)
        res = router.run()
        statuses = sorted({r.status for r in res.values()})
        ph.check("requests_done", len(res), len(res) == n_req, n_req)
        ph.check("statuses", statuses, statuses == ["ok"], ["ok"])
        ph.note(f"placements per replica: {[rep.placed for rep in router.replicas]}")
        one, one_devs = make_replicas(build, devices=jax.devices()[:1])
        solo = Router(one, devices=one_devs)
        for r in requests():
            solo.submit(r)
        res1 = solo.run()
        agree = [bool(np.array_equal(np.asarray(res[k].tokens), np.asarray(res1[k].tokens)))
                 for k in sorted(res)]
        first = next((k for k, a in zip(sorted(res), agree) if not a), None)
        ph.note(f"token agreement 4 replicas vs 1: {sum(agree)}/{len(agree)} "
                f"requests; first mismatch: {first}")


# -- main ----------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); nothing run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1

    from repro import compile_cache
    from repro.kernels.dispatch import resolve_kernel_mode

    print(f"compile cache: {compile_cache.enable()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    # The dispatch policy must send every kernel request to Mosaic here.
    if resolve_kernel_mode(None, False) != (True, False):
        print("chip_smoke: kernel dispatch is not compiled Pallas on this TPU",
              file=sys.stderr)
        return 1
    print(f"device: {devices[0].device_kind} x {len(devices)}; jax {jax.__version__}",
          flush=True)
    if args.chips == 1:
        serve_phase(args.seed)
        filter_phase(args.seed)
    else:
        sharded_filter_phase(args.seed)
        router_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
