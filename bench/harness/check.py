"""The comparison that decides `correct`: served answers against the reference.

Answers of the program and of the reference are brought to one form, an
`Answer`: a header of scalars (lambda, min_sup, k, delta for LAMP; min_sup for
a closed-itemset query) and a map from each itemset, as packed item bits, to
its numbers (support; support, positive support and P-value for LAMP).
Exact quantities must be equal.  P-values, which the program and the
reference both compute in float64 on the host, are held to the limit the
configuration states, as a relative gap.

The control (`Reference(control=True)`) is the reference with one guarantee
the configurations state broken: supports carried in bfloat16 for closed-itemset
queries, P-values in float32 for LAMP.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from bench.reference import lamp
from bench.reference.lcm import lcm_closed

from .data import Cohort
from .traffic import QuerySpec


@dataclass(frozen=True)
class Answer:
    header: tuple
    patterns: dict  # packed item bits -> tuple of numbers


@dataclass(frozen=True)
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def item_key(items, n_items: int) -> bytes:
    row = np.zeros(n_items, dtype=bool)
    row[list(items)] = True
    return np.packbits(row, bitorder="little").tobytes()


def program_answer(report, q: QuerySpec, n_items: int) -> Answer:
    """A `MineReport` in the common form."""
    if q.kind == "closed_frequent":
        pats = {item_key(p.items, n_items): (int(p.support),) for p in report.results}
        return Answer((int(report.min_sup),), pats)
    pats = {item_key(p.items, n_items): (int(p.support), int(p.pos_support), float(p.pvalue))
            for p in report.results}
    return Answer((int(report.lambda_final), int(report.min_sup),
                   int(report.correction_factor), float(report.delta)), pats)


class Reference:
    """The reference's answers for one run, computed once per distinct query.

    A closed-itemset query is mined on the cohort's own rows.  For LAMP,
    phases 1 and 2 read the labels only through N_pos, so they run once, on
    the instance's rows, and phase 3 runs for each cohort's labels taken back
    to the instance's row order: a permutation of the transactions changes
    no support, so this is the cohort's own answer.
    `control=True` breaks one stated guarantee (see the module docstring).
    """

    def __init__(self, base: Cohort, *, control: bool = False):
        self.base = base
        self.control = control
        self._lattices: dict = {}

    def answer(self, cohort: Cohort, q: QuerySpec) -> Answer:
        if q.kind == "closed_frequent":
            count_dtype = None
            if self.control:
                import ml_dtypes

                count_dtype = ml_dtypes.bfloat16
            cs = lcm_closed(cohort.db, q.min_sup, count_dtype=count_dtype)
            return Answer((q.min_sup,), {row.tobytes(): (int(s),)
                                         for row, s in zip(cs.closure, cs.support)})
        if q.alpha not in self._lattices:
            self._lattices[q.alpha] = lamp.lattice(self.base.db, int(self.base.labels.sum()),
                                                   q.alpha)
        a = lamp.test(self._lattices[q.alpha], cohort.labels_in_instance_order(),
                      np.float32 if self.control else np.float64)
        sig = a.significant
        pats = {row.tobytes(): (int(s), int(ps), float(p))
                for row, s, ps, p in zip(sig.closure, sig.support, sig.pos_support, a.pvalue)}
        return Answer((a.lambda_final, a.min_sup, a.k, float(a.delta)), pats)


def pvalue_gap(got: Answer, want: Answer) -> float:
    """Largest |p - p_ref| / p_ref over itemsets both answers hold."""
    gap = 0.0
    for key, w in want.patterns.items():
        g = got.patterns.get(key)
        if g is None or len(w) < 3:
            continue
        p, p_ref = g[2], w[2]
        gap = max(gap, abs(p - p_ref) / p_ref if p_ref > 0 else abs(p - p_ref))
    return gap


def exact(a: Answer) -> tuple:
    """What must equal the reference's exactly: all but the P-values."""
    return a.header, {k: v[:2] for k, v in a.patterns.items()}


def describe_difference(got: Answer, want: Answer, n_show: int = 3) -> str:
    """One line on how an answer differs from the reference's."""
    missing = [k for k in want.patterns if k not in got.patterns]
    extra = [k for k in got.patterns if k not in want.patterns]
    changed = [k for k in want.patterns if k in got.patterns
               and got.patterns[k][:2] != want.patterns[k][:2]]

    def show(keys, src):
        return [(np.flatnonzero(np.unpackbits(np.frombuffer(k, np.uint8),
                                              bitorder="little")).tolist(), src.patterns[k])
                for k in keys[:n_show]]

    return (f"header {got.header} vs reference {want.header}; {len(got.patterns)} vs "
            f"{len(want.patterns)} itemsets: {len(missing)} missing {show(missing, want)}, "
            f"{len(extra)} extra {show(extra, got)}, {len(changed)} with other supports "
            f"{show(changed, got)}")


def compare(answers: list[Answer | None], wants: list[Answer], limits: dict) -> list[Check]:
    """Checks over every request: `answers[i]` is None when request i was
    never answered, `wants[i]` the reference's answer to its query."""
    unanswered = sum(a is None for a in answers)
    wrong = sum(1 for a, w in zip(answers, wants) if a is not None and exact(a) != exact(w))
    checks = [Check("unanswered", unanswered, 0), Check("wrong_answers", wrong, 0)]
    if "pvalue_rel_gap" in limits:
        gap = max((pvalue_gap(a, w) for a, w in zip(answers, wants) if a is not None),
                  default=0.0)
        checks.append(Check("pvalue_rel_gap", gap if math.isfinite(gap) else 1.0,
                            float(limits["pvalue_rel_gap"])))
    return checks
