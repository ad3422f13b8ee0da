"""Share of the filter program's device op time that no declared scope
holds (:mod:`chipbench.scopes`): what the per-scope times leave out."""

from chipbench.scopes import for_run

NAME = "unscoped_share.filter"


def read(run, trace, *, cell, peaks):
    s = for_run(trace, cell)
    return None if s is None else 100.0 * s.unscoped / s.op_s
