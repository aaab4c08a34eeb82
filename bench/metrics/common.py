"""Reductions the per-layer readers share: from the traced window's device
trace, the program's spans and its counters to one number each."""

from __future__ import annotations

import numpy as np

from bench.harness.layers import Readings
from bench.harness.trace import (busy_ns, exposed_collective_ns, idle_gaps, overlap_ns,
                                  stable_module)

from .support_count_bytes import logical_bytes

#: the program's host-path spans (repro.api.session)
HOST_SPANS = ("pack", "postprocess", "reconstruct")


def host_ms_per_request(r: Readings) -> float | None:
    """Time per request in which the host path runs and the chip is idle.

    `postprocess` also waits for the phase program (it reads its outputs
    back), so the spans' own time would count device time twice; the time
    they hold the device back is their overlap with its idle gaps (mean
    over chips), taken on the profiler's clock.
    """
    spans = [(iv.start, iv.end) for iv in r.trace.spans if iv.name in HOST_SPANS]
    chips = r.trace.chips()
    if not r.n_requests or not spans or not chips:
        return None
    ns = np.mean([overlap_ns(spans, idle_gaps(r.trace, c)) for c in chips])
    return float(ns) / 1e6 / r.n_requests


def _engine_ns(r: Readings, chip: int) -> int:
    return sum(iv.end - iv.start for iv in r.trace.modules.get(chip, ())
               if stable_module(iv.name) in r.engine_modules)


def engine_us_per_superstep(r: Readings) -> float | None:
    """Device time of the engine's programs, mean over chips, per superstep."""
    steps = sum(p.supersteps for p in r.phases)
    chips = r.trace.chips()
    if not steps or not chips or not r.engine_modules:
        return None
    ns = np.mean([_engine_ns(r, c) for c in chips])
    return float(ns) / 1e3 / steps if ns else None


def kernel_seconds(r: Readings) -> float:
    """Device time of the Pallas kernel inside the engine's programs, all chips."""
    return sum(op.end - op.start for c in r.trace.chips() for op in r.trace.ops[c]
               if op.name in r.kernel_ops and stable_module(op.module) in r.engine_modules) / 1e9


def support_count_roofline(r: Readings) -> float | None:
    """Least time the bytes the algorithm needs could take at the chip's HBM
    bandwidth, over the kernel's device time, in %.  The bound is bytes alone:
    no published peak covers the VPU's integer AND and popcount."""
    secs = kernel_seconds(r)
    if secs <= 0 or not r.peaks:
        return None
    steps = sum(p.supersteps for p in r.phases)
    nodes = sum(p.n_nodes for p in r.phases)
    need = logical_bytes(steps, nodes, n_items=r.n_items,
                         n_transactions=r.n_transactions, chips=r.chips)
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / secs


def idle_share(r: Readings) -> float | None:
    chips = r.trace.chips()
    if not chips or r.trace.window_s <= 0:
        return None
    busy = np.mean([busy_ns(r.trace, c) for c in chips]) / 1e9
    return 100.0 * (1.0 - busy / r.trace.window_s)


def collective_exposed_share(r: Readings) -> float | None:
    chips = r.trace.chips()
    if len(chips) < 2 or r.trace.window_s <= 0:
        return None
    ns = np.mean([exposed_collective_ns(r.trace, c) for c in chips])
    return 100.0 * ns / 1e9 / r.trace.window_s
