"""repro.obs — observability for the mining engine (DESIGN.md §9).

Three layers, one per time base:

  trace.py    device superstep trace — a [trace_cap, N_FIELDS] i32 ring
              threaded through the BSP carry, sampled every trace_period
              supersteps, decoded host-side into per-miner timelines and
              load-balance metrics (Jain's fairness over donations, idle
              fractions, stack-depth imbalance).
  span.py     host span tracer — nested context-manager spans around
              pack/compile/dispatch/postprocess/reconstruct and their
              leaf steps, each with its own id, its parent's id and the
              request id of the query that caused it, kept in a ring of
              the newest SPAN_CAP events and exported as Chrome-trace
              (Perfetto) JSON, with an optional jax.profiler bridge so
              host and device timelines line up.
  metrics.py  metrics registry — counters/gauges/histograms with
              Prometheus text exposition, fed by MinerSession (cache
              hits/misses/evictions, latency histograms, telemetry-loss
              counters) and snapshot-exported by launch.mine_serve.

The device side of the same question needs no module here: the engine
runs each part of its superstep under a `jax.named_scope` (expand, steal,
sync, trace), which a device profile's ops carry in their op_name
(DESIGN.md §9).

Plus log.py (structured JSON-lines run records for the launchers) and
validate.py (artifact schema validators, used by CI and the tests).

Dependency direction: repro.core imports obs.trace for the record layout;
nothing in obs imports repro.core, so there is no cycle.
"""

from .log import JsonlLogger
from .metrics import DEFAULT_LATENCY_BUCKETS, MetricsRegistry
from .span import SPAN_CAP, SpanTracer
from .trace import (
    DEFAULT_TRACE_CAP,
    N_FIELDS,
    SuperstepTrace,
    TraceField,
    decode_trace,
    jain_fairness,
)

__all__ = [
    "DEFAULT_LATENCY_BUCKETS",
    "DEFAULT_TRACE_CAP",
    "JsonlLogger",
    "MetricsRegistry",
    "N_FIELDS",
    "SPAN_CAP",
    "SpanTracer",
    "SuperstepTrace",
    "TraceField",
    "decode_trace",
    "jain_fairness",
]
