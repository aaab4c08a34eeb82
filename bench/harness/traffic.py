"""The one traffic generator: turns a traffic file into queries and arrivals.

A traffic file (`bench/traffic/<name>.json`) is data:

    "loop": "open" | "closed"
        open: requests arrive on a schedule, whatever the service is doing,
        and go to `MiningService.submit`; closed: one job at a time through
        `MinerSession.run`, the next submitted when the last has returned.
    "query": {"kind": "closed_frequent", "min_sup": [m, ...] | "<name>"}
           | {"kind": "significant", "alpha": a, "statistic": s, "pipeline": p}
        A string `min_sup` names a list under the configuration's
        "min_sup_lists".  A list makes a mix in equal shares.
    "rate_qps", "arrival_seed"   (open loop) offered rate, and the seed of the
        fixed set of Poisson gaps
    "cohorts"                    (closed loop) transaction permutations cycled
    "warmup_rounds"              passes over the mix (or jobs) before the window
    "trace_seconds" | "trace_jobs"   length of the traced window

Every seed gets the same set of gaps and the same number of each query; the
seed only shuffles their order, so the offered work does not depend on it.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .data import rng_for
from .spec import SpecError


@dataclass(frozen=True)
class QuerySpec:
    kind: str
    min_sup: int = 0
    alpha: float = 0.0
    statistic: str = "fisher"
    pipeline: str = "three_phase"

    def build(self):
        """The program's query object."""
        from repro.api import ClosedFrequentQuery, SignificantPatternQuery

        if self.kind == "closed_frequent":
            return ClosedFrequentQuery(min_sup=self.min_sup)
        return SignificantPatternQuery(alpha=self.alpha, statistic=self.statistic,
                                       pipeline=self.pipeline)


def query_mix(traffic: dict, config: dict) -> list[QuerySpec]:
    q = traffic["query"]
    if q["kind"] == "closed_frequent":
        sups = q["min_sup"]
        if isinstance(sups, str):
            sups = config["min_sup_lists"][sups]
        if isinstance(sups, int):
            sups = [sups]
        return [QuerySpec("closed_frequent", min_sup=int(m)) for m in sups]
    if q["kind"] == "significant":
        if q.get("statistic", "fisher") != "fisher":
            raise SpecError("the reference tests with Fisher's exact test only")
        return [QuerySpec("significant", alpha=float(q["alpha"]),
                          statistic=q.get("statistic", "fisher"),
                          pipeline=q.get("pipeline", "three_phase"))]
    raise SpecError(f"unknown query kind {q['kind']!r}")


def open_schedule(traffic: dict, n_mix: int, seed: int, seconds: float):
    """[(offset_s, mix index)] for an open loop of `seconds` at the file's rate.

    The request count is rate x seconds rounded to whole passes over the
    mix; the gaps are Poisson gaps drawn once from `arrival_seed` and scaled
    to sum to `seconds`; the seed permutes the gaps and the order of the mix.
    """
    rate = float(traffic["rate_qps"])
    n = max(1, round(rate * seconds / n_mix)) * n_mix
    gaps = np.random.default_rng(int(traffic["arrival_seed"])).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    rng = rng_for(seed, 2)
    gaps = gaps[rng.permutation(n)]
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    order = rng.permutation(n_mix)
    return [(float(offsets[i]), int(order[i % n_mix])) for i in range(n)]
