"""Request-tokens committed in the window over the window's time.

A tick commits one token for each request in flight; the window is
whole ticks, from its opening to the end of the last tick that started
inside it (:mod:`chipbench.drivers.serve`)."""

NAME = "tokens_per_s"


def read(run, trace, *, cell, peaks):
    return sum(t["committed"] for t in run["ticks"]) / run["window_s"]
