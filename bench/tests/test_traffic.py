"""The traffic files and the seed's cohorts: deterministic, and the same work
for every seed."""

import numpy as np
import pytest

from bench.harness.data import cohorts, instance
from bench.harness.spec import load_cell
from bench.harness.traffic import open_schedule, query_mix

SEEDS = (0, 12345, 2**31 + 17, 2**40 + 3, -5)


def test_serve_mix_is_the_valley_in_equal_shares():
    cell = load_cell("hapmap_dom_20-serve")
    mix = query_mix(cell.traffic, cell.config)
    assert [q.min_sup for q in mix] == [605, 615, 625, 635, 645]
    sched = open_schedule(cell.traffic, len(mix), 7, 20.0)
    counts = np.bincount([qi for _, qi in sched], minlength=len(mix))
    assert len(set(counts.tolist())) == 1
    assert len(sched) == len(mix) * round(cell.traffic["rate_qps"] * 20.0 / len(mix))


@pytest.mark.parametrize("seed", SEEDS)
def test_open_schedule_is_deterministic_and_seed_only_reorders(seed):
    cell = load_cell("hapmap_dom_20-serve")
    a = open_schedule(cell.traffic, 5, seed, 20.0)
    assert a == open_schedule(cell.traffic, 5, seed, 20.0)
    ref = open_schedule(cell.traffic, 5, 1, 20.0)
    gaps = np.diff([t for t, _ in a])
    ref_gaps = np.diff([t for t, _ in ref])
    assert a[0][0] == 0.0 and a[-1][0] < 20.0
    # the same gaps, less the one after the last arrival, and the same mix
    assert np.isclose(sorted(np.append(gaps, 20.0 - a[-1][0])),
                      sorted(np.append(ref_gaps, 20.0 - ref[-1][0]))).all()
    assert sorted(qi for _, qi in a) == sorted(qi for _, qi in ref)


@pytest.mark.parametrize("seed", SEEDS)
def test_cohorts_are_deterministic_permutations(seed):
    cell = load_cell("mcf7-lamp23-4chip")
    cfg = dict(cell.config, generator=dict(cell.config["generator"], n_items=40,
                                           n_transactions=300, n_pos=30))
    base = instance(cfg)
    a = cohorts(base, seed, 2, label_swaps=3)
    b = cohorts(base, seed, 2, label_swaps=3)
    for x, y in zip(a, b):
        assert (x.db == y.db).all() and (x.labels == y.labels).all()
        assert (x.db == base.db[x.perm]).all()
        assert x.labels.sum() == base.labels.sum()
        assert (x.labels_in_instance_order() != base.labels).sum() == 6
    assert not (a[0].perm == a[1].perm).all()
