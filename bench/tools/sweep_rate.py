#!/usr/bin/env python3
"""Find the highest rate an open-loop serve cell sustains: one sweep, one process.

    python3 bench/tools/sweep_rate.py --workload hapmap_dom_20-serve \\
        --rates 20,24,28,32,36,40 --seconds 10 --seed 7 [--out sweep.jsonl]

Starts the cell's service once, warms it as a run does, then offers each
rate for `--seconds` with the traffic file's gaps and mix.  Per rate it
prints the latency quantiles, the completion rate, and how far the backlog
grew: the median latency of the last quarter of requests over that of the
first.  A rate is sustained while that ratio stays near 1.  The rate the
traffic file fixes comes from such a sweep on the chip.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true", help="allow a CPU run (rehearsal only)")
    args = ap.parse_args(argv)

    import numpy as np

    from bench.harness import device, runner
    from bench.harness.data import cohorts, instance
    from bench.harness.spec import load_cell
    from bench.harness.traffic import open_schedule, query_mix
    from repro.api import Dataset, RuntimeConfig

    cell = load_cell(args.workload)
    devices = device.chips(cell.chips, require_tpu=not args.cpu)
    if not args.cpu:
        device.use_compile_cache()
    watch = device.Watch()
    mix = query_mix(cell.traffic, cell.config)
    co = cohorts(instance(cell.config), args.seed, 1)[0]
    ds = Dataset.from_dense(co.db, co.labels, name=cell.config["name"])
    runtime = RuntimeConfig(**cell.config.get("runtime", {}))

    async def sweep():
        service, queries = await runner.start_service(cell, mix, ds, devices, runtime, False)
        rows = []
        try:
            for rate in (float(x) for x in args.rates.split(",")):
                traffic = dict(cell.traffic, rate_qps=rate)
                schedule = open_schedule(traffic, len(mix), args.seed, args.seconds)
                w = await runner.open_window(service, ds, queries, schedule, args.seconds,
                                             False, watch)
                lat = np.array([(r.done - r.due) * 1e3 for r in w.requests if r.done])
                q = max(1, len(lat) // 4)
                done = [r.done for r in w.requests if r.done]
                row = {
                    "rate_qps": rate, "requests": len(w.requests),
                    "failed": sum(r.outcome != "ok" for r in w.requests),
                    "completed_per_s": len(done) / (max(done) - w.t0),
                    "p50_ms": float(np.percentile(lat, 50)),
                    "p95_ms": float(np.percentile(lat, 95)),
                    "max_ms": float(lat.max()),
                    "backlog_growth": float(np.median(lat[-q:]) / np.median(lat[:q])),
                    "compiles_in_window": w.watch.compiles,
                    "gc_pause_max_ms": max(w.watch.gc_pauses, default=0.0) * 1e3,
                    "generator_late_ms_max": max(w.lateness_s) * 1e3,
                }
                print(json.dumps(row), flush=True)
                rows.append(row)
        finally:
            await service.stop()
        return rows

    rows = asyncio.run(sweep())
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    print(f"sweep took {time.perf_counter() - T_START:.1f}s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
