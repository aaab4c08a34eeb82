"""Synthetic transaction databases matched to Table 1 of arXiv:1510.07787.

A copy of the dense generator `generate` of `src/repro/data/synthetic.py`:
skewed (clipped Pareto) per-item marginals at the published density, plus
`n_planted` positive-enriched itemsets.  The configuration files name every
parameter, so the instance a cell mines is fixed by the file alone.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

__all__ = ["SyntheticSpec", "generate"]


@dataclass(frozen=True)
class SyntheticSpec:
    name: str
    n_items: int
    n_transactions: int
    density: float
    n_pos: int
    n_planted: int = 3
    planted_pos_rate: float = 0.6
    planted_neg_rate: float = 0.05
    skew: float = 1.2
    seed: int = 0


def generate(spec: SyntheticSpec) -> tuple[np.ndarray, np.ndarray, list[list[int]]]:
    """Returns (db_bool [N, M], labels [N] bool, planted itemsets)."""
    rng = np.random.default_rng(spec.seed)
    n, m = spec.n_transactions, spec.n_items
    labels = np.zeros(n, dtype=bool)
    labels[rng.choice(n, size=spec.n_pos, replace=False)] = True

    w = rng.pareto(spec.skew, size=m) + 1.0
    p_item = w / w.mean() * spec.density
    p_item = np.clip(p_item, 0.0, 0.95)
    db = rng.random((n, m)) < p_item[None, :]

    planted: list[list[int]] = []
    for _ in range(spec.n_planted):
        size = int(rng.integers(2, 5))
        items = rng.choice(m, size=size, replace=False).tolist()
        carrier = np.where(
            labels, rng.random(n) < spec.planted_pos_rate,
            rng.random(n) < spec.planted_neg_rate,
        )
        for j in items:
            db[carrier, j] = True
        planted.append(sorted(items))
    return db, labels, planted
