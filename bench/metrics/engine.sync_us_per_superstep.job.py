"""Device time of the engine's ops under its `sync` scope (the lambda sync,
the superstep's counters and termination, the terminal histogram psums),
mean over chips, per superstep of the traced window, in us (see parts.py)."""

from bench.metrics.parts import scope_us_per_superstep


def read(r):
    return scope_us_per_superstep(r, "sync")
