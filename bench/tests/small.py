"""Cells of the real BENCHMARK.json cut to sizes a CPU test can run."""

from __future__ import annotations

import copy
import dataclasses
import time

from bench.harness import runner
from bench.harness.spec import load_cell


def serve_cell():
    """hapmap_dom_20-serve on 700 of its 11,914 items, thresholds 600..620."""
    cell = load_cell("hapmap_dom_20-serve")
    cfg = copy.deepcopy(cell.config)
    cfg["generator"]["n_items"] = 700
    cfg["min_sup_lists"]["valley"] = [600, 610, 620]
    traffic = dict(cell.traffic, rate_qps=12.0, trace_seconds=0.5)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def lamp_cell(chips: int = 4):
    """mcf7-lamp23-4chip on 60 items x 1,200 transactions, 100 positives."""
    cell = load_cell("mcf7-lamp23-4chip")
    cfg = copy.deepcopy(cell.config)
    cfg["generator"].update(n_items=60, n_transactions=1200, n_pos=100)
    return dataclasses.replace(cell, config=cfg, chips=chips)


def run(cell, seed: int = 2**33 + 7, seconds: float = 1.0, trace: bool = False) -> dict:
    return runner.run(cell, seed=seed, seconds=seconds, trace=trace,
                      t_start=time.perf_counter(), require_tpu=False)
