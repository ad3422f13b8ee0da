"""Device time of the ``filter.resample`` scope and every scope inside it
(the comb, the table gather, the clone bookkeeping and ``clone_state``), per
call of the filter program and generation, in ms (:mod:`chipbench.scopes`)."""

from chipbench.scopes import ms_per_generation

NAME = "resample_ms.filter"


def read(run, trace, *, cell, peaks):
    return ms_per_generation(run, trace, cell, "filter.resample")
