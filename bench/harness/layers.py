"""What the per-layer metric readers read, and how the harness finds them.

Each per-layer metric `<name>` in BENCHMARK.json has a reader
`bench/metrics/<name>.py` with one function, `read(r: Readings)`, that
returns the metric's value or None when the traced window holds nothing it
can read (the metric is then left out of the result line).
"""

from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass, field

from .spec import BENCH_DIR
from .trace import DeviceTrace


@dataclass
class Readings:
    """Everything from the traced window of one run."""

    trace: DeviceTrace
    n_requests: int          # requests (open loop) or jobs (closed) completed
    phases: list             # the program's PhaseReports of those requests
    n_items: int             # logical (unpadded) database shape
    n_transactions: int
    chips: int
    peaks: dict              # bench/peaks.json entry of this device kind
    engine_modules: frozenset = frozenset()  # module names of the engine's programs
    kernel_ops: frozenset = frozenset()      # instruction names of the Pallas kernel
    queue_s: list[float] = field(default_factory=list)  # serve: admission -> start


def reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader {path} for per-layer metric {name!r}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(metrics: tuple[dict, ...], r: Readings) -> dict:
    out = {}
    for m in metrics:
        value = reader(m["name"])(r)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
