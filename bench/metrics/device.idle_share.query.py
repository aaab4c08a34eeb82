"""1 - the union of device op intervals over the traced window, mean of the
chips, in %."""

from bench.metrics.common import idle_share as read  # noqa: F401
