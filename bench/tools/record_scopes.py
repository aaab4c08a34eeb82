#!/usr/bin/env python3
"""Record a piece of a cell's traced window with the engine's scope maps, and
print where the engine's device time goes by superstep scope.

    python3 bench/tools/record_scopes.py --workload <cell> --seed <n> \\
        --ms 3 --out bench/tests/data/<cell>.scopes.json.gz

Makes one `--trace 1` run of the cell (its result line is printed too).
Over the whole traced window it prints, per superstep and as a mean over
chips: the engine's op time under each scope (`bench/metrics/parts.py`),
the unscoped remainder, the total op time of the engine's modules
(containers left out) counted directly, and the modules' own time
(`engine.device_us_per_superstep`, gaps between ops included).  The piece
is `record_trace.py`'s crop plus, per executable, the (opcode, scope) of
each instruction the piece holds.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def whole_window(r) -> dict:
    """Where the engine's op time of the traced window goes, per superstep."""
    import numpy as np

    from bench.harness.trace import op_kind, stable_module
    from bench.metrics.common import engine_us_per_superstep
    from bench.metrics.parts import scope_us

    tr = r.trace
    steps = sum(p.supersteps for p in r.phases)
    per_chip = []
    for c in tr.chips():
        ns = 0
        for op in tr.ops[c]:
            if op_kind(op) != "container" and stable_module(op.module) in r.engine_modules:
                s, e = tr.clip(op)
                ns += max(0, e - s)
        per_chip.append(ns)
    by_scope = scope_us(r) or {}
    return {"supersteps": steps,
            "scopes_us": {str(k): v for k, v in by_scope.items()},
            "scoped_plus_unscoped_us": sum(by_scope.values()),
            "engine_op_us": float(np.mean(per_chip)) / 1e3 / steps,
            "engine_module_us": engine_us_per_superstep(r)}


def piece(r, ms: float):
    """`record_trace.py`'s crop, and each executable's (opcode, scope) of the
    instructions it holds."""
    from bench.harness import tracefile
    from bench.harness.trace import stable_module
    from bench.metrics.parts import phase_programs

    tr = r.trace
    first = min(iv.start for c in tr.chips() for iv in tr.modules[c]
                if stable_module(iv.name) in r.engine_modules)
    cropped = tracefile.crop(tr, first, first + int(ms * 1e6))
    names = {op.name for ops in cropped.ops.values() for op in ops}
    programs = [{n: list(v) for n, v in prog.items() if n in names}
                for prog in phase_programs(r.phases)]
    return cropped, programs


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

    kept = []
    result = runner.run(load_cell(args.workload), seed=args.seed, seconds=args.seconds,
                        trace=True, t_start=T_START, on_readings=kept.append)
    r = kept[0]
    parts = whole_window(r)
    cropped, programs = piece(r, args.ms)
    tracefile.save(args.out, cropped, engine_modules=sorted(r.engine_modules),
                   kernel_ops=sorted(r.kernel_ops), chips=r.chips, workload=args.workload,
                   device=result["device"], programs=programs, parts=parts)
    print(json.dumps({"out": args.out, "bytes": os.path.getsize(args.out),
                      "ops": sum(len(v) for v in cropped.ops.values()), "parts": parts,
                      **result}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
