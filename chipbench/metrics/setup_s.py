"""Seconds from process start to the start of the measured window:
weights, compilation or cache loads, and warm-up."""

NAME = "setup_s"


def read(run, trace, *, cell, peaks):
    return run["setup_s"]
