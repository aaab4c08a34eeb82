"""p95 of the time requests of the traced window waited in the service's queue
(admission to start, `ServeResult.queued_s`), in ms."""

import numpy as np


def read(r):
    return float(np.percentile(r.queue_s, 95)) * 1e3 if r.queue_s else None
