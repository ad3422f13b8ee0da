"""Device programs executed per scheduler tick, over the traced ticks: the
one decode step and every eager operation the host loop dispatches."""

from chipbench import ticks as ticks_lib

NAME = "launches_per_tick.serve"


def read(run, trace, *, cell, peaks):
    spans = ticks_lib.spans(run, trace)
    if spans is None:
        return None
    return len(ticks_lib.programs(trace, spans)) / len(spans)
