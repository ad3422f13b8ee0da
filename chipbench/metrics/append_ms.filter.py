"""Device time of the ``store.append`` scope and every scope inside it
(allocation, the copy-on-write record write, the copy-away release), per
call of the filter program and generation, in ms (:mod:`chipbench.scopes`)."""

from chipbench.scopes import ms_per_generation

NAME = "append_ms.filter"


def read(run, trace, *, cell, peaks):
    return ms_per_generation(run, trace, cell, "store.append")
