#!/usr/bin/env python3
"""Record a small piece of a cell's traced window for the reduction's tests.

    python3 bench/tools/record_trace.py --workload <cell> --seed <n> \\
        --ms 3 --out bench/tests/data/<cell>.trace.json.gz

Makes one `--trace 1` run of the cell, crops its device trace to `--ms`
milliseconds from the start of the first engine program in the window, and
writes it with the engine's module and kernel names and the run's counters.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--ms", type=float, default=3.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench.harness import runner, tracefile
    from bench.harness.spec import load_cell
    from bench.harness.trace import stable_module

    kept = []
    result = runner.run(load_cell(args.workload), seed=args.seed, seconds=args.seconds,
                        trace=True, t_start=T_START, on_readings=kept.append)
    r = kept[0]
    first = min(iv.start for c in r.trace.chips() for iv in r.trace.modules[c]
                if stable_module(iv.name) in r.engine_modules)
    piece = tracefile.crop(r.trace, first, first + int(args.ms * 1e6))
    tracefile.save(args.out, piece, engine_modules=sorted(r.engine_modules),
                   kernel_ops=sorted(r.kernel_ops), chips=r.chips, workload=args.workload,
                   device=result["device"])
    print(json.dumps({"out": args.out, "bytes": os.path.getsize(args.out),
                      "ops": sum(len(v) for v in piece.ops.values()), **result}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
