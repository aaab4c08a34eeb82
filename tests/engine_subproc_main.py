"""Subprocess entry for multi-device engine tests.

Must set XLA_FLAGS before importing jax — pytest's process already initialized
jax with 1 device, so multi-device engine tests run this script instead.

Usage: python engine_subproc_main.py '<json spec>'   -> prints a json result.
"""

import json
import os
import sys


def main():
    spec = json.loads(sys.argv[1])
    # replace (not just prepend to) any inherited device-count flag — CI runs
    # the whole suite under --xla_force_host_platform_device_count=8
    inherited = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    os.environ["XLA_FLAGS"] = " ".join(
        [f"--xla_force_host_platform_device_count={spec['n_devices']}"] + inherited
    )
    import numpy as np

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

    from repro.core.engine import EngineConfig, lamp_distributed, mine
    from repro.data.synthetic import SyntheticSpec, generate

    gspec = SyntheticSpec(
        name="sub",
        n_items=spec["n_items"],
        n_transactions=spec["n_transactions"],
        density=spec["density"],
        n_pos=spec["n_pos"],
        n_planted=spec.get("n_planted", 2),
        seed=spec.get("seed", 0),
    )
    db, labels, _ = generate(gspec)
    cfg = EngineConfig(
        expand_batch=spec.get("expand_batch", 8),
        stack_cap=spec.get("stack_cap", 4096),
        steal_max=spec.get("steal_max", 64),
        push_cap=spec.get("push_cap", 256),
        out_cap=spec.get("out_cap", 1024),
        steal_enabled=spec.get("steal_enabled", True),
        seed=spec.get("engine_seed", 0),
        kernel_impl=spec.get("kernel_impl", "ref"),
    )
    out = {}
    if spec["mode"] == "run_vs_legacy":
        # the query-object path vs the legacy one-shot shim, same devices:
        # session.run(SignificantPatternQuery) must reproduce the
        # lamp_distributed dict bit-identically (incl. exact P-values)
        import warnings

        from repro.api import Dataset, MinerSession, RuntimeConfig, SignificantPatternQuery

        def patterns_of(rs):
            return [
                [list(p.items), p.support, p.pos_support, p.pvalue, p.qvalue]
                for p in rs
            ]

        pipeline = spec.get("pipeline", "three_phase")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            legacy = lamp_distributed(
                db, labels, alpha=spec.get("alpha", 0.05), cfg=cfg,
                pipeline=pipeline,
            )
        session = MinerSession(
            runtime=RuntimeConfig.from_engine_config(cfg))
        rep = session.run(
            Dataset.from_dense(db, labels),
            SignificantPatternQuery(alpha=spec.get("alpha", 0.05),
                                    statistic="fisher", pipeline=pipeline),
        )
        out = {
            "legacy": {
                "min_sup": legacy["min_sup"],
                "correction_factor": legacy["correction_factor"],
                "delta": legacy["delta"],
                "n_significant": legacy["n_significant"],
                "patterns": patterns_of(legacy["results"]),
            },
            "run": {
                "min_sup": rep.min_sup,
                "correction_factor": rep.correction_factor,
                "delta": rep.delta,
                "n_significant": rep.n_significant,
                "patterns": patterns_of(rep.results),
            },
        }
    elif spec["mode"] == "session":
        # two queries (reseeded same-shape datasets) on one MinerSession:
        # returns both pattern sets plus the program-cache counters so the
        # parent can assert the second query compiled nothing
        from repro.api import AlgorithmConfig, Dataset, MinerSession, RuntimeConfig

        session = MinerSession(
            algorithm=AlgorithmConfig(alpha=spec.get("alpha", 0.05),
                                      pipeline=spec.get("pipeline", "three_phase")),
            runtime=RuntimeConfig.from_engine_config(cfg).with_options(
                stack_cap=None),
        )
        queries = []
        misses = []
        for seed in (spec.get("seed", 0), spec.get("seed2", 1)):
            db_q, labels_q, _ = generate(
                SyntheticSpec(
                    name="sub", n_items=spec["n_items"],
                    n_transactions=spec["n_transactions"],
                    density=spec["density"], n_pos=spec["n_pos"],
                    n_planted=spec.get("n_planted", 2), seed=seed,
                )
            )
            rep = session.mine(Dataset.from_dense(db_q, labels_q, name=f"q{seed}"))
            queries.append({
                "min_sup": rep.min_sup,
                "correction_factor": rep.correction_factor,
                "delta": rep.delta,
                "n_significant": rep.n_significant,
                "cold": rep.cold,
                "patterns": [
                    [list(p.items), p.support, p.pos_support, p.pvalue, p.qvalue]
                    for p in rep.results
                ],
            })
            ci = session.cache_info()
            misses.append(ci.misses)
        out = {
            "queries": queries,
            "misses_per_query": misses,
            "hits": ci.hits,
            "n_programs": ci.n_programs,
        }
    elif spec["mode"] == "lamp_full":
        res = lamp_distributed(db, labels, alpha=spec.get("alpha", 0.05), cfg=cfg,
                               pipeline=spec.get("pipeline", "three_phase"))
        p1, p2 = res["phase_outputs"][:2]
        rs = res["results"]
        out = {
            "lambda_final": res["lambda_final"],
            "min_sup": res["min_sup"],
            "correction_factor": res["correction_factor"],
            "delta": res["delta"],
            "n_significant": res["n_significant"],
            "p1_supersteps": p1.supersteps,
            "steals_got": p1.stats["steals_got"].tolist(),
            "closed_per_dev": p2.stats["closed"].tolist(),
            "popped_per_dev": p2.stats["popped"].tolist(),
            "patterns": [
                [list(p.items), p.support, p.pos_support, p.pvalue, p.qvalue]
                for p in rs
            ],
            "patterns_complete": rs.complete,
        }
    elif spec["mode"] == "count":
        res = mine(db, labels, mode="count", min_sup=spec["min_sup"], cfg=cfg)
        out = {
            "hist": res.hist.tolist(),
            "supersteps": res.supersteps,
            "closed_per_dev": res.stats["closed"].tolist(),
            "steals_got": res.stats["steals_got"].tolist(),
            "gives": res.stats["gives"].tolist(),
        }
    elif spec["mode"] == "scopes":
        # the superstep scopes of a fused23 query's compiled programs, and
        # its answer, on this device count (tests/hlo_scopes.py)
        from hlo_scopes import fused23_report

        from repro.api import RuntimeConfig

        out = fused23_report(db, labels, RuntimeConfig.from_engine_config(cfg))
    elif spec["mode"] == "trace_parity":
        # the same pass traced vs untraced on this device count: results
        # must be bit-identical, and the decoded trace must reconcile with
        # the engine's cumulative per-miner counters
        import dataclasses

        res_off = mine(db, labels, mode="lamp1", cfg=cfg)
        cfg_on = dataclasses.replace(
            cfg, trace_period=spec.get("trace_period", 1),
            trace_cap=spec.get("trace_cap", 4096),
        )
        res_on = mine(db, labels, mode="lamp1", cfg=cfg_on)
        tr = res_on.trace
        out = {
            "hist_equal": res_off.hist.tolist() == res_on.hist.tolist(),
            "lam_equal": res_off.lam_final == res_on.lam_final,
            "supersteps_equal": res_off.supersteps == res_on.supersteps,
            "supersteps": res_on.supersteps,
            "sampled_steps": tr.n_steps,
            "dropped": tr.dropped,
            "steps_monotone": bool(np.all(np.diff(tr.steps) > 0)),
            "depth_nonneg": bool(np.all(tr.depth >= 0)),
            "popped_matches_stats": (
                tr.popped.sum(axis=1).tolist()
                == res_on.stats["popped"].tolist()
            ),
            "fired_matches_stats": (
                int(tr.fired.sum()) == int(res_on.stats["steal_rounds"][0])
            ),
            "donation_fairness": tr.donation_fairness(),
            "summary": tr.summary(),
        }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
