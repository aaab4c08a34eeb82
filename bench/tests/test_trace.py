"""The reduction from a device trace to metrics, checked on pieces of traces
recorded on the chip (`bench/tools/record_trace.py`) against a brute-force
count on a time grid, and on hand-made intervals."""

import glob
import os
import re

import numpy as np
import pytest

from bench.harness import tracefile
from bench.harness.layers import Readings
from bench.harness.trace import (
    DeviceTrace, Interval, breakdown, busy_ns, exposed_collective_ns, idle_gaps, op_kind,
    stable_module)
from bench.metrics import common

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = sorted(glob.glob(os.path.join(DATA, "*.trace.json.gz")))
STEP_NS = 50  # grid resolution of the brute-force count


def _grid(tr, ivs):
    lo, hi = tr.window
    g = np.zeros((hi - lo) // STEP_NS + 1, dtype=bool)
    for iv in ivs:
        s, e = tr.clip(iv)
        if e > s:
            g[(s - lo) // STEP_NS:(e - lo) // STEP_NS] = True
    return g


@pytest.fixture(params=RECORDED, ids=os.path.basename)
def recorded(request):
    return tracefile.load(request.param)


def test_both_cells_have_a_recorded_trace():
    names = {os.path.basename(p).split(".")[0] for p in RECORDED}
    assert {"hapmap_dom_20-serve", "mcf7-lamp23-4chip"} <= names


def test_recorded_on_the_chip(recorded):
    tr, meta = recorded
    assert meta["device"]["platform"] == "tpu"
    assert meta["device"]["kind"] == "TPU v5 lite"
    assert len(tr.chips()) == meta["chips"] == meta["device"]["count"]


def test_idle_share_matches_a_grid_count(recorded):
    tr, _ = recorded
    for chip in tr.chips():
        grid = _grid(tr, tr.ops[chip])
        busy = grid.sum() * STEP_NS
        assert abs(busy_ns(tr, chip) - busy) <= 2 * STEP_NS * len(tr.ops[chip])
        gaps = sum(e - s for s, e in idle_gaps(tr, chip))
        assert gaps + busy_ns(tr, chip) == tr.window[1] - tr.window[0]
    share = common.idle_share(_readings(recorded))
    assert 0.0 <= share < 100.0


def test_kernel_time_is_the_kernel_ops_in_engine_programs(recorded):
    tr, meta = recorded
    r = _readings(recorded)
    kernel = common.kernel_seconds(r)
    assert kernel > 0
    by_hand = sum(op.end - op.start for c in tr.chips() for op in tr.ops[c]
                  if op.name in meta["kernel_ops"]
                  and stable_module(op.module) in meta["engine_modules"]) / 1e9
    assert kernel == pytest.approx(by_hand)
    engine = sum(iv.end - iv.start for c in tr.chips() for iv in tr.modules[c]
                 if stable_module(iv.name) in meta["engine_modules"]) / 1e9
    assert kernel < engine


def test_collective_exposure_matches_a_grid_count(recorded):
    tr, _ = recorded
    for chip in tr.chips():
        ops = tr.ops[chip] + tr.async_ops.get(chip, [])
        coll = _grid(tr, [op for op in ops if op_kind(op) == "collective"])
        comp = _grid(tr, [op for op in ops if op_kind(op) == "compute"])
        exposed = (coll & ~comp).sum() * STEP_NS
        assert abs(exposed_collective_ns(tr, chip) - exposed) <= 2 * STEP_NS * len(ops)
    if len(tr.chips()) > 1:
        assert any(op_kind(op) == "collective"
                   for c in tr.chips() for op in tr.ops[c] + tr.async_ops.get(c, []))


def test_breakdown_names_survive_renumbering(recorded):
    tr, meta = recorded
    kernels = set(meta["kernel_ops"])
    first = breakdown(tr, kernel_ops=kernels)
    assert first["device_ops"]
    for name, secs in first["device_ops"]:
        assert not re.search(r"\.\d+", name) and not re.search(r"\(\d+\)", name)
        assert secs > 0

    def renumber(ivs):  # what a rebuild does to XLA's names
        return [Interval(re.sub(r"\.(\d+)", lambda m: f".{int(m.group(1)) + 7}", iv.name),
                         iv.start, iv.end,
                         re.sub(r"\((\d+)\)", "(99)", iv.module)) for iv in ivs]

    moved = DeviceTrace(tr.window, {c: renumber(v) for c, v in tr.ops.items()},
                        {c: renumber(v) for c, v in tr.async_ops.items()},
                        {c: renumber(v) for c, v in tr.modules.items()}, tr.spans)
    kernels_moved = {re.sub(r"\.(\d+)", lambda m: f".{int(m.group(1)) + 7}", k)
                     for k in kernels}
    assert breakdown(moved, kernel_ops=kernels_moved) == first
    assert any(name.endswith("/pallas_call") for name, _ in first["device_ops"])


def _readings(recorded):
    tr, meta = recorded
    return Readings(trace=tr, n_requests=1, phases=[], n_items=1,
                    n_transactions=1, chips=meta["chips"], peaks={},
                    engine_modules=frozenset(meta["engine_modules"]),
                    kernel_ops=frozenset(meta["kernel_ops"]))


def test_reduction_on_hand_made_intervals():
    ops = [Interval("fusion.1", 0, 100, "jit_program(1)", "fusion"),
           Interval("psum.3", 80, 150, "jit_program(1)", "all-reduce"),
           Interval("while.2", 0, 300, "jit_program(1)", "while"),  # holds other ops
           Interval("ppermute.4", 200, 260, "jit_program(1)", "collective-permute-done"),
           Interval("fusion.5", 250, 270, "jit_program(1)", "fusion")]
    tr = DeviceTrace((0, 400), ops={0: ops}, async_ops={0: []}, modules={0: []}, spans=[])
    assert busy_ns(tr, 0) == 300
    assert idle_gaps(tr, 0) == [(300, 400)]
    # all-reduce 100..150 uncovered, permute 200..250 and 260 uncovered
    assert exposed_collective_ns(tr, 0) == 50 + 50
    names = dict(breakdown(tr)["device_ops"])
    assert names == {"jit_program/fusion": 120e-9, "jit_program/psum": 70e-9,
                     "jit_program/ppermute": 60e-9}
