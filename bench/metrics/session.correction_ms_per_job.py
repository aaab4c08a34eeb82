"""Time of the program's `correction` spans (the LAMP correction on the host
between passes: k, delta and the significant count from the histograms,
and the root record), clipped to the traced window, per job, in ms."""

from bench.metrics.parts import span_ms_per_request


def read(r):
    return span_ms_per_request(r, "correction")
