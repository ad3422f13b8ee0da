"""Device time of the ``filter.propagate`` scope (the model's step), per
call of the filter program and generation, in ms (:mod:`chipbench.scopes`)."""

from chipbench.scopes import ms_per_generation

NAME = "propagate_ms.filter"


def read(run, trace, *, cell, peaks):
    return ms_per_generation(run, trace, cell, "filter.propagate")
