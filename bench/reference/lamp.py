"""LAMP's three phases on the plain reference (a copy of `src/repro/core/lamp.py`).

Phase 1 runs LCM with the support threshold lambda rising while
CS(lambda) > alpha / f(lambda - 1); phase 2 counts the closed itemsets at
min_sup = lambda - 1 exactly (k), and phase 3 keeps those whose Fisher
P-value is at most delta = alpha / k.

The final lambda does not depend on the order in which LCM visits nodes:
pruning at a lambda only drops itemsets of smaller support, so every closed
itemset of support >= the final lambda is counted, and lambda passed each
smaller value only when the count above it already exceeded its threshold.
It is therefore the least lambda with CS(lambda) <= thr[lambda], which the
batched traversal of `lcm.py` finds as the sequential one does.

Phases 1 and 2 read the labels only through N_pos, so `lattice` computes
them once and `test` runs phase 3 for any labelling with that N_pos.
`pvalue_dtype` is float64 for the reference; the control passes float32.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .fisher import fisher_pvalue, lamp_count_thresholds
from .lcm import ClosedSets, lcm_closed, pack_rows, popcount_rows

__all__ = ["LampAnswer", "Lattice", "lamp", "lattice", "test"]


@dataclass
class Lattice:
    n_transactions: int
    n_pos: int
    alpha: float
    lambda_final: int
    min_sup: int
    closed: ClosedSets  # every closed itemset with support >= min_sup, with occ


@dataclass
class LampAnswer:
    lambda_final: int
    min_sup: int
    k: int
    delta: float
    significant: ClosedSets  # the closed itemsets with P-value <= delta
    pvalue: np.ndarray


class _SupportIncrease:
    def __init__(self, n: int, n_pos: int, alpha: float):
        self.n = n
        self.thr = lamp_count_thresholds(n, n_pos, alpha)
        self.cnt = np.zeros(n + 2, dtype=np.int64)
        self.cs = 0  # closed itemsets counted with support >= lam
        self.lam = 1

    def observe(self, support: int) -> int:
        if support >= self.lam:
            self.cnt[support] += 1
            self.cs += 1
            while self.lam <= self.n and self.cs > self.thr[self.lam]:
                self.cs -= self.cnt[self.lam]
                self.lam += 1
        return self.lam


def lattice(db_bool: np.ndarray, n_pos: int, alpha: float = 0.05) -> Lattice:
    """Phases 1 and 2."""
    db_bool = np.asarray(db_bool, dtype=bool)
    n = db_bool.shape[0]
    phase1 = _SupportIncrease(n, n_pos, alpha)
    lcm_closed(db_bool, 1, dynamic_min_sup=phase1.observe)
    min_sup = max(phase1.lam - 1, 1)
    closed = lcm_closed(db_bool, min_sup, keep_occ=True)
    return Lattice(n, n_pos, alpha, phase1.lam, min_sup, closed)


def test(lat: Lattice, labels: np.ndarray, pvalue_dtype=np.float64) -> LampAnswer:
    """Phase 3 for `labels` (in the lattice's transaction order)."""
    labels = np.asarray(labels, dtype=bool)
    if int(labels.sum()) != lat.n_pos:
        raise ValueError(f"labels have {int(labels.sum())} positives, the lattice {lat.n_pos}")
    c = lat.closed
    pos = popcount_rows(c.occ & pack_rows(labels))
    k = len(c)
    delta = lat.alpha / max(k, 1)
    pv = fisher_pvalue(c.support, pos, lat.n_transactions, lat.n_pos, dtype=pvalue_dtype)
    sig = pv <= pvalue_dtype(delta)
    return LampAnswer(lat.lambda_final, lat.min_sup, k, delta,
                      ClosedSets(c.closure[sig], c.support[sig], pos[sig]),
                      pv[sig].astype(np.float64))


def lamp(db_bool: np.ndarray, labels: np.ndarray, alpha: float = 0.05, *,
         pvalue_dtype=np.float64) -> LampAnswer:
    labels = np.asarray(labels, dtype=bool)
    return test(lattice(db_bool, int(labels.sum()), alpha), labels, pvalue_dtype)
