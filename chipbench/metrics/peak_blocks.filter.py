"""Highest ``FilterResult.store.peak_blocks`` over the window's runs."""

NAME = "peak_blocks.filter"


def read(run, trace, *, cell, peaks):
    return max(r[5] for r in run["runs"]) if run["runs"] else None
