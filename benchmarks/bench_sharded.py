"""Sharded-population scaling benchmark (DESIGN.md §6).

Runs the bootstrap filter with the population split over a faked
multi-device host mesh (``--xla_force_host_platform_device_count``) and
reports, per (shard count, copy mode):

  * throughput in particle-steps/sec (N * T / median wall time),
  * per-shard blocks-in-use at the end and the per-shard running peak —
    the paper's memory metric, now resolved per device (imports land on
    the importing shard, so skew shows up here),
  * the log-evidence estimate, checked against the single-device run.

A 1-shard mesh is bit-exact with the single-device path; multi-shard
runs use independent per-shard propagation noise and must agree
statistically.  The final row reports that check: the 4-shard LAZY_SR
log-likelihood vs. the single-device estimate.

On a CPU host the mesh is faked: :func:`use_host_devices` gives the CPU
backend four devices, and must run before anything initializes JAX's
backends (``benchmarks/run.py`` calls it first thing).  On an
accelerator host the mesh spans the real chips, in the same process.

Run:  PYTHONPATH=src python benchmarks/bench_sharded.py
(or through ``python -m benchmarks.run --only sharded``).
"""

from __future__ import annotations

import math
import os
import time

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.config import ALL_MODES, CopyMode
from repro.distributed import sharded_store as sharded_lib
from repro.smc.filters import FilterConfig, ParticleFilter, SSMDef

if __package__ in (None, ""):  # invoked as a file path (the documented usage)
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

A, Q, R = 0.9, 0.5, 0.3


def use_host_devices(n: int = 4) -> None:
    """Fake ``n`` devices on the CPU backend, unless ``JAX_PLATFORMS``
    names only accelerator platforms (then the CPU client is never the
    backend and is left alone).  JAX refuses the setting once its
    backends have started, so callers run this before any computation."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        return
    jax.config.update("jax_num_cpu_devices", n)


def lgssm_def() -> SSMDef:
    def init(key, n, params):
        return jax.random.normal(key, (n,))

    def step(key, x, t, y_t, params):
        x = A * x + math.sqrt(Q) * jax.random.normal(key, x.shape)
        logw = -0.5 * ((y_t - x) ** 2 / R + math.log(2 * math.pi * R))
        return x, logw, x[:, None]

    return SSMDef(init=init, step=step, record_shape=(1,))


def _time(fn, key, obs, reps: int) -> tuple[float, object]:
    res = fn(key, None, obs)  # warmup / compile
    jax.block_until_ready(res.log_evidence)
    times = []
    for i in range(reps):
        t0 = time.time()
        res = fn(jax.random.PRNGKey(i), None, obs)
        jax.block_until_ready(res.log_evidence)
        times.append(time.time() - t0)
    return float(np.median(times)), res


def run(n: int = 256, t: int = 48, reps: int = 3, tol: float = 3.0):
    # Imported here: benchmarks.common computes at import, which would
    # start JAX's backends before use_host_devices could shape them.
    from benchmarks.common import emit

    key = jax.random.PRNGKey(0)
    devices = jax.devices()
    max_shards = len(devices)
    obs = jax.random.normal(key, (t,))
    rows = []

    # single-device reference (no mesh at all)
    pf0 = ParticleFilter(
        lgssm_def(),
        FilterConfig(n_particles=n, n_steps=t, mode=CopyMode.LAZY_SR, block_size=2),
    )
    secs0, res0 = _time(pf0.jitted(), key, obs, reps)
    ref_logz = float(res0.log_evidence)
    rows.append(
        emit(
            "sharded",
            "sharded_single_device_lazy_sr",
            secs0,
            f"pps={n * t / secs0:.0f};logZ={ref_logz:.3f};"
            f"peak={int(res0.store.peak_blocks)}",
            n=n, t=t,
        )
    )

    shard_counts = [s for s in (1, 2, 4) if s <= max_shards and n % s == 0]
    logz_by_cfg = {}
    for s in shard_counts:
        mesh = Mesh(np.array(devices[:s]), ("shards",))
        for mode in ALL_MODES:
            pf = ParticleFilter(
                lgssm_def(),
                FilterConfig(
                    n_particles=n, n_steps=t, mode=mode, block_size=2, mesh=mesh
                ),
            )
            secs, res = _time(pf.jitted(), key, obs, reps)
            shcfg = pf.sharded_cfg
            used = np.asarray(sharded_lib.used_blocks_per_shard(shcfg, res.store))
            peak = np.asarray(sharded_lib.peak_blocks_per_shard(shcfg, res.store))
            oom = bool(np.asarray(res.store.pool.oom).any())
            logz = float(res.log_evidence)
            logz_by_cfg[(s, mode)] = logz
            rows.append(
                emit(
                    "sharded",
                    f"sharded_s{s}_{mode.value}",
                    secs,
                    f"pps={n * t / secs:.0f};logZ={logz:.3f};"
                    f"used_per_shard={'/'.join(map(str, used))};"
                    f"peak_per_shard={'/'.join(map(str, peak))};oom={int(oom)}",
                    n=n, t=t, shards=s, mode=mode.value,
                )
            )

    # the acceptance check: multi-shard LAZY_SR vs single-device logZ
    s_chk = shard_counts[-1]
    delta = abs(logz_by_cfg[(s_chk, CopyMode.LAZY_SR)] - ref_logz)
    verdict = "ok" if delta < tol else "FAIL"
    rows.append(
        emit(
            "sharded",
            f"sharded_logz_check_s{s_chk}",
            0.0,
            f"delta={delta:.3f};tol={tol};verdict={verdict}",
        )
    )
    if verdict == "FAIL":
        raise SystemExit(
            f"{s_chk}-shard LAZY_SR logZ diverged from single-device: "
            f"{delta:.3f} > {tol}"
        )
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--t", type=int, default=48)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--json", default="")
    args = ap.parse_args()
    use_host_devices()
    if args.json:
        from benchmarks import common

        common.enable_json(args.json)
    print("name,us_per_call,derived")
    run(n=args.n, t=args.t, reps=args.reps)
    if args.json:
        common.flush_json()
