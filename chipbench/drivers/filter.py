"""Driver: back-to-back particle-filter runs of the paper's RBPF through
the program's ``ParticleFilter.jitted()`` (a ``back_to_back`` mix).

Set-up: the observations are simulated from ``--seed`` by the reference
model (:mod:`chipbench.reference.rbpf`), the filter is built at the
configuration's N, T, copy mode and store, and one run compiles and
warms it.  The window runs the filter again and again, each run with a
fresh key drawn from the seed and waited for, until ``--seconds`` have
passed.  A sample of the window's runs, drawn from the seed, keeps its
result; after the window each is compared with the dense reference
filter run on the same keys and observations, at the configuration's
dtype and matmul precision.

With ``ctx.control`` the reference one precision lower (bfloat16 for the
configuration's float32) takes the program's place in that comparison,
and its outputs are judged by the same limits.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from chipbench import traffic as traffic_lib
from chipbench.reference import rbpf as ref_lib

OBS_STREAM, KEY_STREAM, SAMPLE_STREAM = 4, 5, 6
CONTROL_DTYPE = "bfloat16"


def _filter(conf: Dict[str, Any]):
    import dataclasses

    from repro.core.config import CopyMode
    from repro.smc.filters import FilterConfig, ParticleFilter
    from repro.smc.programs import rbpf

    ssm, params = rbpf.build()
    fc = FilterConfig(
        n_particles=int(conf["n_particles"]),
        n_steps=int(conf["n_steps"]),
        mode=CopyMode[conf["mode"]],
        block_size=int(conf["block_size"]),
        use_kernels=bool(conf["use_kernels"]),
    )
    pf = ParticleFilter(ssm, fc)
    if conf.get("delta_cow"):
        pf.store_cfg = dataclasses.replace(pf.store_cfg, delta_cow=True)
    return pf, params


def run(ctx, devices) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.core import store as store_lib

    cell, seed = ctx.cell, ctx.seed
    conf = cell.config["filter"]
    if cell.traffic["kind"] != "back_to_back":
        raise ValueError(f"the filter driver runs back_to_back mixes, not "
                         f"{cell.traffic['kind']!r}")
    check_spec = cell.spec["check"]
    n, steps = int(conf["n_particles"]), int(conf["n_steps"])
    precision = conf["matmul_precision"]

    # -- set-up --------------------------------------------------------------
    ys = ref_lib.simulate(traffic_lib.jax_key(seed, OBS_STREAM), steps)
    pf, params = _filter(conf)
    run_filter = pf.jitted()
    base = traffic_lib.jax_key(seed, KEY_STREAM)
    keys = jax.jit(lambda i: jax.random.fold_in(base, i))
    warm = run_filter(keys(jnp.uint32(1 << 31)), params, ys)
    jax.block_until_ready(warm)
    del warm

    # -- the window ----------------------------------------------------------
    # A uniform sample of the window's runs, drawn from the seed as they
    # finish (reservoir sampling): only as many results as the check
    # compares stay alive, the rest are freed as in a user's loop.
    want = int(check_spec["runs"])
    pick = traffic_lib.rng(seed, SAMPLE_STREAM)
    runs: List[tuple] = []  # (start, end, traced, log_evidence, oom, peak_blocks)
    sample: List[tuple] = []  # (run index, result)
    w0 = ctx.start_window()
    now = w0
    while now - w0 < ctx.seconds:
        traced = ctx.trace_due(now)
        i = len(runs)
        with jax.profiler.TraceAnnotation("chipbench.filter.run"):
            res = run_filter(keys(jnp.uint32(i)), params, ys)
            logz, oom, peak = jax.device_get(
                (res.log_evidence, res.oom, res.store.peak_blocks))
        t1 = time.perf_counter()
        runs.append((now, t1, traced, float(logz), bool(oom), int(peak)))
        if len(sample) < want:
            sample.append((i, res))
        else:
            slot = int(pick.integers(i + 1))
            if slot < want:
                sample[slot] = (i, res)
        del res
        now = t1
    ctx.end_window(now)
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devices)
    ctx.log("filter runs in the window (s): "
            + " ".join(f"{r[1] - r[0]:.4f}" for r in runs))

    # -- after the window: materialise the sampled runs, free the program ----
    ids = jnp.arange(n, dtype=jnp.int32)
    outputs = {
        j: (float(r.log_evidence),
            np.asarray(store_lib.materialize_batch(pf.store_cfg, r.store, ids))[:, :steps])
        for j, r in sorted(sample, key=lambda e: e[0])
    }
    del sample, pf, run_filter
    gc.collect()
    if ctx.control:
        # The reference one precision lower, in the program's place.
        low = {}
        for j in outputs:
            lz, tr = ref_lib.dense_filter(keys(jnp.uint32(j)), ys, n, dtype=CONTROL_DTYPE,
                                          precision=precision)
            low[j] = (float(lz), np.asarray(tr))
        outputs = low

    # -- correctness: each compared run against the dense reference ----------
    logz_rel, rows_differ, kalman = 0.0, 0.0, 0.0
    for j, (logz, traj) in sorted(outputs.items()):
        r_logz, r_traj = ref_lib.dense_filter(keys(jnp.uint32(j)), ys, n,
                                              dtype=conf["dtype"], precision=precision)
        r_traj = np.asarray(r_traj)
        logz_rel = max(logz_rel, abs(logz - float(r_logz)) / abs(float(r_logz)))
        rows_differ = max(rows_differ, float(np.mean(np.any(traj != r_traj, axis=(1, 2)))))
        kalman = max(kalman, ref_lib.kalman_residual(traj, ys))
    checks: List[Dict[str, Any]] = [
        dict(name="runs_compared", value=len(outputs), limit=f">= {want}",
             ok=len(outputs) >= want),
        dict(name="log_evidence_rel_diff", value=logz_rel,
             limit=float(check_spec["log_evidence_rel_diff"]),
             ok=logz_rel <= float(check_spec["log_evidence_rel_diff"])),
        dict(name="trajectories_differing_share", value=rows_differ,
             limit=float(check_spec["trajectories_differing_share"]),
             ok=rows_differ <= float(check_spec["trajectories_differing_share"])),
        dict(name="kalman_residual_max", value=kalman,
             limit=float(check_spec["kalman_residual_max"]),
             ok=kalman <= float(check_spec["kalman_residual_max"])),
    ]
    failed = sum(1 for r in runs if r[4] or not np.isfinite(r[3]))
    out = {
        "memory_peak_bytes": memory_peak,
        "attempted": len(runs),
        "failed": failed,
        "checks": checks,
        "window_t0": w0,
        "window_end": now,
        "runs": runs,
        "n_particles": n,
        "n_steps": steps,
        "store": {
            "block_size": int(conf["block_size"]),
            "record": 6,
            "max_blocks": -(-steps // int(conf["block_size"])),
        },
    }
    return out
