"""A cell's data: the configuration's fixed instance, permuted by the seed.

The configuration names every parameter of the generator, so the instance
(items, transactions, supports, the closed-itemset lattice) is the same in
every run.  `--seed` draws only permutations of the transactions: they
change every bit the program is handed, and change neither the lattice, nor
any support, nor the order of items that LCM's enumeration tree follows.
So the amount of work of a run does not depend on the seed.  Labels move
with their transactions.

A traffic file may also give each cohort a phenotype of its own: with
`label_swaps` = s, s positive and s negative labels trade places (drawn from
the seed) before the permutation.  N_pos stays as published, so LAMP's
Tarone bound, lambda, min_sup and k stay too, and its phases 1 and 2 do the
same work on every cohort; only which itemsets test significant, and their
P-values, change a little from cohort to cohort.
"""

from __future__ import annotations

from dataclasses import dataclass, fields

import numpy as np

from bench.reference.synthetic import SyntheticSpec, generate

from .spec import SpecError


@dataclass(frozen=True)
class Cohort:
    db: np.ndarray      # [N, M] bool, transactions permuted
    labels: np.ndarray  # [N] bool, in the same order
    perm: np.ndarray    # row i of this cohort is row perm[i] of the instance

    def labels_in_instance_order(self) -> np.ndarray:
        out = np.empty_like(self.labels)
        out[self.perm] = self.labels
        return out


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """One independent stream per use of the seed (any integer seed)."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def instance(config: dict) -> Cohort:
    gen = dict(config["generator"])
    if gen.pop("kind", None) != "dense_synthetic":
        raise SpecError(f"{config['name']}: unknown generator {config['generator']!r}")
    names = {f.name for f in fields(SyntheticSpec)} - {"name"}
    unknown = set(gen) - names
    if unknown:
        raise SpecError(f"{config['name']}: unknown generator keys {sorted(unknown)}")
    db, labels, _ = generate(SyntheticSpec(name=config["name"], **gen))
    return Cohort(db, labels, np.arange(db.shape[0]))


def cohorts(base: Cohort, seed: int, count: int, label_swaps: int = 0) -> list[Cohort]:
    """`count` transaction permutations of the instance, drawn from `seed`,
    each with `label_swaps` positive/negative label pairs traded."""
    rng = rng_for(seed, 1)
    pos, neg = np.flatnonzero(base.labels), np.flatnonzero(~base.labels)
    out = []
    for _ in range(count):
        labels = base.labels.copy()
        if label_swaps:
            labels[rng.choice(pos, label_swaps, replace=False)] = False
            labels[rng.choice(neg, label_swaps, replace=False)] = True
        perm = rng.permutation(base.db.shape[0])
        out.append(Cohort(base.db[perm], labels[perm], perm))
    return out
