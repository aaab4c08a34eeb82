"""Time collectives were in flight with no compute on the chip, over the
traced window, mean of the chips, in %."""

from bench.metrics.common import collective_exposed_share as read  # noqa: F401
