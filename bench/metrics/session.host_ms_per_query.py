"""Time the program's host spans pack, postprocess and reconstruct run while
the chip is idle, per request of the traced window, in ms."""

from bench.metrics.common import host_ms_per_request as read  # noqa: F401
