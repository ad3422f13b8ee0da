"""Device time of the ``store.refcount`` scope and every scope inside it
(the refcount histogram over the pool, with the fused chain's comb and
table gather), per call of the filter program and generation, in ms
(:mod:`chipbench.scopes`)."""

from chipbench.scopes import ms_per_generation

NAME = "refcount_ms.filter"


def read(run, trace, *, cell, peaks):
    return ms_per_generation(run, trace, cell, "store.refcount")
