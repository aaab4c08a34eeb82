#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
`BENCHMARK.json` at the root of the checkout.  The last line of standard
output is one JSON object: `correct`, `attempted`, `failed`, `metrics` (the
cell's end-to-end metrics with --trace 0, its per-layer metrics with
--trace 1), `device`, with --trace 1 `breakdown`, and last `checks`, each
number the comparison with the reference read beside its limit.  The same
numbers close standard error.  Without a TPU, or with fewer chips than the
cell asks for, it prints no result and exits 1.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import runner
    from bench.harness.device import NoChip
    from bench.harness.spec import load_cell

    cell = load_cell(args.workload)
    try:
        result = runner.run(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(f"bench: {args.workload} seed={args.seed} setup={result['setup_parts']} "
          f"compiles_in_window={result['compiles_in_window']} "
          f"reference_s={result['reference_s']:.3f}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
