"""Least time for one generation (state, weights, appended record and
the resampling's table rows; :mod:`chipbench.counts.rbpf`) over the
measured device time per generation of the filter program."""

from chipbench.counts.rbpf import generation_work
from chipbench.measure import least_time

NAME = "generation_roofline.filter"
PROGRAM = r"jit_run"


def read(run, trace, *, cell, peaks):
    calls = trace.matching(PROGRAM, where="modules")
    if not calls or peaks is None:
        return None
    store = run["store"]
    flops, nbytes = generation_work(run["n_particles"], store["max_blocks"], store["record"])
    per_gen = sum(c.seconds for c in calls) / (len(calls) * run["n_steps"])
    return 100.0 * least_time(flops, nbytes, peaks) / per_gen
