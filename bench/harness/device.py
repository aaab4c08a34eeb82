"""The chips a run measures, their published peaks, and the compile cache."""

from __future__ import annotations

import json
import os
import time

from .spec import BENCH_DIR, ROOT

#: JAX's persistent compilation cache: a fixed path inside the checkout, so
#: only a cell's first run in a checkout compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def chips(n: int, *, require_tpu: bool = True):
    """The first `n` devices; a TPU with at least `n` chips unless told not to."""
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < n):
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX has {len(devices)} "
                     f"{devices[0].platform} device(s)")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} devices; JAX has {len(devices)}")
    return devices[:n]


def use_compile_cache() -> str:
    """Keep every program of the run, however quick its compile, in CACHE_DIR."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no LRU eviction: it reads an `-atime` file per entry, and an entry
    # written without one made every later write fail on the chip
    jax.config.update("jax_compilation_cache_max_size", -1)
    return CACHE_DIR


def peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def describe(devices) -> dict:
    """The device block of the result line; memory is the fullest chip's peak."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class Watch:
    """What happens inside the measured window, once `arm()` is called: XLA
    compiles and persistent-cache loads (the benchmark warms every shape up
    first, so it should count none), and the garbage collector's pauses,
    which stall the load generator's thread like any other host work."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import gc

        import jax

        self.armed = False
        self.compiles = 0
        self.gc_pauses: list[float] = []
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        gc.callbacks.append(self._on_gc)

    def _on_event(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event in self._EVENTS:
            self.compiles += 1

    def _on_gc(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.armed:
            self.gc_pauses.append(time.perf_counter() - self._gc_t0)

    def arm(self) -> None:
        self.armed, self.compiles, self.gc_pauses = True, 0, []

    def disarm(self) -> None:
        self.armed = False
