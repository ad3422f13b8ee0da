# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
#   fig5  — inference time/memory, 5 problems x 3 copy configurations
#   fig6  — simulation overhead (no copies)
#   fig7  — time/memory scaling in t
#   tree  — Jacob et al. reachable-set bound
#   serve — beyond-paper: COW-paged KV under SMC decoding
#   sharded — beyond-paper: multi-device population (DESIGN.md §6)
#   write — the kernelized COW write path vs the legacy jnp path
#           (DESIGN.md §3; includes the roofline byte/pass gate)
#   pool  — pool lifecycle: grow-from-tiny vs oversized-fixed and
#           compaction/shrink-to-fit (DESIGN.md §3.1; gates logZ
#           equality, bit-exact compaction, and the 1.25x fit bound)
#   pgibbs — particle Gibbs through the shared population executor
#           (DESIGN.md §4): iterations/sec + peak blocks per copy mode,
#           logZ sanity vs the plain filter, and the chunk-cache gate
#           (repeated runs must trigger zero recompiles; compile counts
#           land in the JSON artifacts)
#   sched — continuous-batching SMC serving scheduler (DESIGN.md §8):
#           tokens/sec + peak shared-pool blocks vs request arrival
#           rate; gates single-request parity (bit-exact tokens) and
#           peak < sum of per-request dense-equivalent caches
#   sim   — scheduler simulator validation (DESIGN.md §9): gates
#           decision-exact replay of recorded runs and +/-25% wall-time
#           prediction, plus a device-free Poisson capacity row whose
#           deterministic outputs the baseline remembers bit-for-bit
#   faults — fault-injection overhead (DESIGN.md §10): tokens/sec at
#           0/5/20% injected transient-fault rates; gates bit-exact
#           recovery (faulted runs == fault-free run in every output)
#
# ``--quick`` shrinks N/T for CI-speed runs; default sizes run in
# minutes on a CPU host.  The at-scale numbers live in the dry-run
# roofline tables (results/, EXPERIMENTS.md), not here.
#
# ``--json DIR`` additionally writes one machine-readable
# ``DIR/BENCH_<suite>.json`` per suite (name, us_per_call, derived,
# config per row) so the perf trajectory is trackable across PRs; CI
# uploads these as artifacts.

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--only", default="",
        help="comma list of {fig5,fig6,fig7,tree,serve,block,sharded,write,"
        "pool,pgibbs,sched,sim,faults}",
    )
    ap.add_argument(
        "--json", default="",
        help="directory to write BENCH_<suite>.json result files into",
    )
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    # Both must precede the first JAX computation (benchmarks.common
    # makes one at import): the device count only shapes a backend that
    # has not started, and the cache applies from the first compile.
    from benchmarks import bench_sharded
    from repro import compile_cache

    if only is None or "sharded" in only:
        bench_sharded.use_host_devices()
    compile_cache.enable()
    from benchmarks import common

    if args.json:
        common.enable_json(args.json)

    n, t = (48, 24) if args.quick else (128, 48)
    print("name,us_per_call,derived")
    try:
        _run_suites(args, only, n, t)
    finally:
        # Flush whatever completed even when a suite (e.g. the write-path
        # perf gate) fails — those are the runs whose evidence matters.
        if args.json:
            common.flush_json()


def _run_suites(args, only, n: int, t: int) -> None:
    from benchmarks import (
        bench_block_size,
        bench_inference,
        bench_pgibbs,
        bench_pool_lifecycle,
        bench_scaling,
        bench_scheduler,
        bench_serving,
        bench_simulation,
        bench_tree_bound,
        bench_write_path,
    )

    if only is None or "fig5" in only:
        bench_inference.run(n=n, t=t, reps=2 if args.quick else 3)
    if only is None or "fig6" in only:
        bench_simulation.run(n=n, t=t, reps=2 if args.quick else 3)
    if only is None or "fig7" in only:
        bench_scaling.run(n=n, t=2 * t)
    if only is None or "tree" in only:
        bench_tree_bound.run(t=40 if args.quick else 100)
    if only is None or "serve" in only:
        bench_serving.run(steps=16 if args.quick else 32)
    if only is None or "block" in only:
        bench_block_size.run(n=n, t=2 * t)
    if only is None or "write" in only:
        bench_write_path.run(quick=args.quick, reps=2 if args.quick else 3)
    if only is None or "pool" in only:
        bench_pool_lifecycle.run(
            n=n // 2 if args.quick else n, t=t, reps=2 if args.quick else 3
        )
    if only is None or "pgibbs" in only:
        bench_pgibbs.run(
            n=n // 2 if args.quick else n,
            t=t,
            iters=2 if args.quick else 3,
            reps=2 if args.quick else 3,
        )
    if only is None or "sched" in only:
        bench_scheduler.run(
            n_reqs=3 if args.quick else 4,
            n_particles=6 if args.quick else 8,
            steps=12 if args.quick else 24,
        )
    if only is None or "sim" in only:
        from benchmarks import bench_sim

        bench_sim.run(
            n_reqs=3,
            n_particles=6,
            steps=12,
            scale_reqs=120 if args.quick else 300,
        )
    if only is None or "faults" in only:
        from benchmarks import bench_faults

        bench_faults.run(
            n_reqs=2 if args.quick else 3,
            n_particles=6,
            steps=12 if args.quick else 16,
        )
    if only is None or "sharded" in only:
        from benchmarks import bench_sharded

        bench_sharded.run(n=n * 2, t=t, reps=2 if args.quick else 3)


if __name__ == "__main__":
    main()
