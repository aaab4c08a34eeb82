"""One run of one cell: set-up, the measured window, the check, the metrics.

    set-up   runtime start, the seed's cohorts, the program's service or
             session, and warm-up passes over the cell's whole query mix, so
             every program and shape the window uses is compiled or loaded
             from the persistent cache first;
    window   open loop: requests due on the traffic file's schedule go to
             `MiningService.submit`, each timed from when it was due to its
             result; closed loop: `MinerSession.run` one job at a time until
             `--seconds` have passed, the last job run whole;
    check    every answer of the window against the reference (check.py);
    metrics  end-to-end from the host clock (--trace 0), per layer from a
             profiler trace of a shorter window (--trace 1).
"""

from __future__ import annotations

import asyncio
import contextlib
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from . import device
from .check import Reference, compare, describe_difference, exact, program_answer
from .data import cohorts, instance, rng_for
from .layers import Readings, per_layer
from .programs import engine_names
from .spec import Cell, SpecError
from .trace import Profile, breakdown, busy_ns
from .traffic import open_schedule, query_mix

#: how long past the window's close an answer is still waited for
GRACE_S = 60.0


@dataclass
class Request:
    qi: int                 # index into the cell's query mix
    due: float              # perf_counter when it was due
    cohort: int = 0         # index of the cohort (dataset) it asked about
    done: float | None = None
    outcome: str = "pending"
    report: object = None   # the program's MineReport
    queued_s: float | None = None


@dataclass
class Window:
    t0: float               # the first request was due: set-up ends here
    requests: list[Request]
    sessions: list
    watch: device.Watch
    profile: Profile | None
    lateness_s: list[float] = field(default_factory=list)

    @property
    def answered(self) -> list[Request]:
        return [r for r in self.requests if r.outcome == "ok"]


def _finish(r: Request, fut) -> None:
    r.done = time.perf_counter()
    res = fut.result()
    r.outcome, r.report, r.queued_s = res.outcome, res.report, res.queued_s


async def start_service(cell: Cell, mix, ds, devices, runtime, trace):
    """A started `MiningService` for the cell, warmed on its whole query mix."""
    from repro.obs import SpanTracer
    from repro.serve import MiningService, ServeConfig

    layout = cell.config.get("service", {})
    service = MiningService(size=int(layout.get("fleet_size", 1)), devices=devices,
                            runtime=runtime, config=ServeConfig(**layout.get("serve", {})))
    if trace:  # the program's spans, on the profiler's clock too
        for w in service.fleet.workers:
            w.session.tracer = SpanTracer(jax_profiler=True)
    await service.start()
    queries = [q.build() for q in mix]
    for _ in range(int(cell.traffic.get("warmup_rounds", 1))):
        for q in queries:
            res = await service.mine(ds, q)
            if not res.ok:
                raise RuntimeError(f"warm-up request failed: {res.outcome} {res.reason}")
    return service, queries


async def open_window(service, ds, queries, schedule, seconds, trace, watch) -> Window:
    """Submit each request when it is due, whatever the service is doing, and
    wait for every answer (up to GRACE_S past the close)."""
    from repro.serve.request import AdmissionError

    sessions = [w.session for w in service.fleet.workers]
    for s in sessions:
        s.tracer.clear()
    requests, futures, late = [], [], []
    profile = Profile() if trace else None
    with profile or contextlib.nullcontext():
        watch.arm()
        t0 = time.perf_counter()
        for offset, qi in schedule:
            r = Request(qi, t0 + offset)
            delay = r.due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            late.append(time.perf_counter() - r.due)
            requests.append(r)
            try:
                sreq = service.submit(ds, queries[qi], client=f"c{len(requests)}")
            except AdmissionError as e:
                r.outcome, r.done = f"rejected:{e.reason}", time.perf_counter()
                continue
            sreq.future.add_done_callback(lambda fut, r=r: _finish(r, fut))
            futures.append(sreq.future)
        if futures:
            await asyncio.wait(futures, timeout=seconds + GRACE_S - (time.perf_counter() - t0))
        await asyncio.sleep(0)  # let the last done-callbacks run
        watch.disarm()
    return Window(t0, requests, sessions, watch, profile, late)


async def _open_loop(cell: Cell, mix, datasets, devices, runtime, seed, seconds,
                     trace, watch, parts) -> Window:
    t = time.perf_counter()
    service, queries = await start_service(cell, mix, datasets[0], devices, runtime, trace)
    parts["service_and_warmup_s"] = time.perf_counter() - t
    schedule = open_schedule(cell.traffic, len(mix), seed, seconds)
    try:
        return await open_window(service, datasets[0], queries, schedule, seconds, trace,
                                 watch)
    finally:
        await service.stop()


def _closed_loop(cell: Cell, mix, datasets, devices, runtime, seed, seconds, trace,
                 watch, parts) -> Window:
    from repro.api import MinerSession
    from repro.obs import SpanTracer

    t = time.perf_counter()
    session = MinerSession(devices, runtime=runtime, tracer=SpanTracer(jax_profiler=trace))
    queries = [q.build() for q in mix]
    order = rng_for(seed, 4).permutation(len(mix))
    # every cohort once: its answer's size sets the shapes of the host's
    # closure reconstruction, which the window must find compiled
    for i in range(int(cell.traffic.get("warmup_rounds", 1)) * max(len(mix), len(datasets))):
        session.run(datasets[i % len(datasets)], queries[order[i % len(mix)]])
    parts["session_and_warmup_s"] = time.perf_counter() - t

    jobs = int(cell.traffic["trace_jobs"]) if trace else None
    session.tracer.clear()
    requests = []
    profile = Profile() if trace else None
    with profile or contextlib.nullcontext():
        watch.arm()
        t0 = time.perf_counter()
        i = 0
        while (i < jobs) if trace else (i == 0 or time.perf_counter() - t0 < seconds):
            qi = int(order[i % len(mix)])
            r = Request(qi, time.perf_counter(), cohort=i % len(datasets))
            r.report = session.run(datasets[r.cohort], queries[qi])
            r.done, r.outcome = time.perf_counter(), "ok"
            requests.append(r)
            i += 1
        watch.disarm()
    return Window(t0, requests, [session], watch, profile)


def end_to_end(name: str, w: Window, t_start: float) -> float:
    if name == "setup_s":
        return w.t0 - t_start
    if name == "job_s":
        done = w.answered
        return (max(r.done for r in done) - w.t0) / len(done)
    if name in ("latency_p50_ms", "latency_p95_ms"):
        close = w.t0 + max(r.due - w.t0 for r in w.requests) + GRACE_S
        lat = [((r.done if r.outcome == "ok" else close) - r.due) * 1e3 for r in w.requests]
        return float(np.percentile(lat, 50 if name == "latency_p50_ms" else 95))
    raise SpecError(f"no definition of end-to-end metric {name!r}")


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True, on_readings=None) -> dict:
    """Run the cell once and return its result line (a dict).

    `on_readings(readings)`, when given, receives the traced window's
    `Readings` (bench/tools/record_trace.py keeps a piece of it)."""
    from repro.api import Dataset, RuntimeConfig

    devices = device.chips(cell.chips, require_tpu=require_tpu)
    if require_tpu:
        device.use_compile_cache()
    watch = device.Watch()
    parts = {"runtime_s": time.perf_counter() - t_start}

    t = time.perf_counter()
    config, traffic = cell.config, cell.traffic
    mix = query_mix(traffic, config)
    base = instance(config)
    cos = cohorts(base, seed, int(traffic.get("cohorts", 1)),
                  int(traffic.get("label_swaps", 0)))
    datasets = [Dataset.from_dense(c.db, c.labels, name=f"{config['name']}-{i}")
                for i, c in enumerate(cos)]
    runtime = RuntimeConfig(**config.get("runtime", {}))
    parts["data_s"] = time.perf_counter() - t

    if traffic["loop"] == "open":
        secs = float(traffic["trace_seconds"]) if trace else seconds
        w = asyncio.run(_open_loop(cell, mix, datasets, devices, runtime, seed, secs,
                                   trace, watch, parts))
    elif traffic["loop"] == "closed":
        w = _closed_loop(cell, mix, datasets, devices, runtime, seed, seconds, trace,
                         watch, parts)
    else:
        raise SpecError(f"unknown loop {traffic['loop']!r}")
    dev = device.describe(devices)

    out: dict = {}
    if trace:
        tr = w.profile.trace
        modules, kernels = engine_names(w.sessions)
        done = w.answered
        readings = Readings(
            trace=tr, n_requests=len(done),
            phases=[p for r in done for p in r.report.phases],
            n_items=base.db.shape[1], n_transactions=base.db.shape[0],
            chips=len(devices), peaks=device.peaks(dev["kind"]) if require_tpu else {},
            engine_modules=modules, kernel_ops=kernels,
            queue_s=[r.queued_s for r in done if r.queued_s is not None],
        )
        out["metrics"] = per_layer(cell.per_layer, readings)
        if on_readings is not None:
            on_readings(readings)
        busy = [busy_ns(tr, c) / 1e9 for c in tr.chips()]
        dev["busy_s"] = float(np.mean(busy)) if busy else 0.0
        dev["window_s"] = tr.window_s
        out["trace_file"] = {"bytes": w.profile.file_bytes, "read_s": w.profile.read_s}
        out["breakdown"] = breakdown(
            tr, kernel_ops=kernels,
            span_names={ev["name"] for s in w.sessions for ev in s.tracer.events()})
    else:
        out["metrics"] = {m["name"]: {"value": end_to_end(m["name"], w, t_start),
                                      "unit": m["unit"]} for m in cell.end_to_end}

    t = time.perf_counter()
    reference = Reference(base)
    wants = {}
    for r in w.requests:
        if (r.cohort, r.qi) not in wants:
            wants[r.cohort, r.qi] = reference.answer(cos[r.cohort], mix[r.qi])
    answers = [program_answer(r.report, mix[r.qi], base.db.shape[1]) if r.outcome == "ok"
               else None for r in w.requests]
    checks = compare(answers, [wants[r.cohort, r.qi] for r in w.requests],
                     config.get("limits", {}))
    for r, a in zip(w.requests, answers):  # say how the first wrong answer differs
        want = wants[r.cohort, r.qi]
        if a is not None and exact(a) != exact(want):
            dropped = [p.emit_dropped for p in r.report.phases]
            print(f"bench: first wrong answer (query {mix[r.qi]}, cohort {r.cohort}, "
                  f"emit_dropped {dropped}): {describe_difference(a, want)}", file=sys.stderr)
            break
    reference_s = time.perf_counter() - t

    result = {
        "correct": bool(w.requests) and all(c.ok for c in checks),
        "attempted": len(w.requests),
        "failed": sum(r.outcome != "ok" for r in w.requests),
        **out,
        "device": dev,
        "setup_parts": parts,
        "compiles_in_window": w.watch.compiles,
        "gc_pause_max_ms": max(w.watch.gc_pauses, default=0.0) * 1e3,
        "reference_s": reference_s,
    }
    if w.lateness_s:
        result["generator_late_ms_p95"] = float(np.percentile(w.lateness_s, 95) * 1e3)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return result
