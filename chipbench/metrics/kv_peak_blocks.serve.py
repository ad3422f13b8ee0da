"""Highest count of used KV pool blocks after any tick of the window (the
scheduler's own reading, taken once a tick)."""

NAME = "kv_peak_blocks.serve"


def read(run, trace, *, cell, peaks):
    used = [t["used_blocks"] for t in run["ticks"] if t["used_blocks"] is not None]
    return max(used) if used else None
