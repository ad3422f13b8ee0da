"""Share of the traced window in which no operation ran on the device."""

NAME = "device_idle_share.serve"


def read(run, trace, *, cell, peaks):
    return 100.0 * trace.idle_share
