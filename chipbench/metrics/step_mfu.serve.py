"""The whole tick's share of the chip's bf16 peak: the model FLOPs of the
traced ticks (every row's matrix work and its attention over its live
context, from the configuration's ``chipbench/counts/<config>.py``) over
the peak times the device's busy time inside those ticks."""

from chipbench import ticks as ticks_lib
from chipbench.drivers.serve import config_module

NAME = "step_mfu.serve"


def read(run, trace, *, cell, peaks):
    spans = ticks_lib.spans(run, trace)
    if spans is None or peaks is None:
        return None
    busy = ticks_lib.busy_s(trace, spans)
    if busy <= 0:
        return None
    counts = config_module("counts", cell)
    flops = sum(counts.decode_flops(cell.config["model"], t["contexts"])
                for t in run["ticks"] if t["traced"])
    return 100.0 * flops / (float(peaks["flops_per_s"]["bfloat16"]) * busy)
