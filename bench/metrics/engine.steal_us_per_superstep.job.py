"""Device time of the engine's ops under its `steal` scope (the hunger census
psum and the gated lifeline exchange), mean over chips, per superstep of
the traced window, in us (see parts.py)."""

from bench.metrics.parts import scope_us_per_superstep


def read(r):
    return scope_us_per_superstep(r, "steal")
