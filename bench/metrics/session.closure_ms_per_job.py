"""Time of the program's `closure` spans (host closure reconstruction and
dedup, inside `reconstruct`), clipped to the traced window, per request, in
ms."""

from bench.metrics.parts import span_ms_per_request


def read(r):
    return span_ms_per_request(r, "closure")
