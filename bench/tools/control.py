#!/usr/bin/env python3
"""Read the control's numbers: the reference in the program's place, with one
stated guarantee broken, through the comparison that decides `correct`.

    python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 20] [--jobs 12]

For each seed it builds the cell's cohorts and the requests a run of
`--seconds` would send (open loop), or `--jobs` jobs (closed loop), answers
each with the control (`check.Reference(control=True)`: supports carried in
bfloat16, or P-values in float32) and prints the checks as a run would.
A sound limit lies below what the control reads.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--jobs", type=int, default=12)
    args = ap.parse_args(argv)

    from bench.harness.check import Reference, compare
    from bench.harness.data import cohorts, instance
    from bench.harness.spec import load_cell
    from bench.harness.traffic import open_schedule, query_mix

    cell = load_cell(args.workload)
    cfg, traffic = cell.config, cell.traffic
    mix = query_mix(traffic, cfg)
    base = instance(cfg)
    for seed in (int(s) for s in args.seeds.split(",")):
        cos = cohorts(base, seed, int(traffic.get("cohorts", 1)),
                      int(traffic.get("label_swaps", 0)))
        if traffic["loop"] == "open":
            reqs = [(0, qi) for _, qi in open_schedule(traffic, len(mix), seed, args.seconds)]
        else:
            reqs = [(i % len(cos), i % len(mix)) for i in range(args.jobs)]
        ref, ctl = Reference(base), Reference(base, control=True)
        wants, gots = {}, {}
        for key in sorted(set(reqs)):
            c, qi = key
            wants[key] = ref.answer(cos[c], mix[qi])
            gots[key] = ctl.answer(cos[c], mix[qi])
        checks = compare([gots[k] for k in reqs], [wants[k] for k in reqs],
                         cfg.get("limits", {}))
        print(json.dumps({"workload": args.workload, "seed": seed, "requests": len(reqs),
                          "correct": all(c.ok for c in checks),
                          "checks": {c.name: c.value for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
