"""Device time of the engine's compiled programs (mean over chips) per
superstep of the traced window (`PhaseReport.supersteps`), in us."""

from bench.metrics.common import engine_us_per_superstep as read  # noqa: F401
