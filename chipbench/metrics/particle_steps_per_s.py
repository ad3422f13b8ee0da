"""N x T x the filter runs completed in the window, over the time those
runs took (the window runs whole filter runs back to back)."""

NAME = "particle_steps_per_s"


def read(run, trace, *, cell, peaks):
    return run["n_particles"] * run["n_steps"] * len(run["runs"]) / run["window_s"]
