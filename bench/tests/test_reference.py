"""The copied reference agrees with the program's own oracles, and the
control (one stated guarantee broken) does not pass the comparison."""

import numpy as np
import pytest

from bench.harness.check import Answer, Reference, compare
from bench.harness.data import Cohort
from bench.harness.traffic import QuerySpec
from bench.reference import fisher, lamp
from bench.reference.lcm import lcm_closed
from bench.reference.synthetic import SyntheticSpec, generate


def _small(seed, n=300, m=30, density=0.2, n_pos=60):
    return generate(SyntheticSpec("t", m, n, density, n_pos, seed=seed))


def _as_set(cs):
    out = set()
    for row, s in zip(cs.closure, cs.support):
        items = np.flatnonzero(np.unpackbits(row, bitorder="little"))
        out.add((frozenset(items.tolist()), int(s)))
    return out


def test_generator_is_the_programs():
    from repro.data.synthetic import SyntheticSpec as PSpec
    from repro.data.synthetic import generate as pgen

    for seed in (0, 3):
        a = generate(SyntheticSpec("t", 500, 200, 0.02, 40, seed=seed))
        b = pgen(PSpec("t", 500, 200, 0.02, 40, seed=seed))
        assert (a[0] == b[0]).all() and (a[1] == b[1]).all() and a[2] == b[2]


@pytest.mark.parametrize("seed,min_sup", [(1, 20), (2, 35), (3, 50)])
def test_lcm_matches_the_programs_oracle(seed, min_sup):
    from repro.core.lcm import lcm_closed as prog_lcm

    db, _, _ = _small(seed)
    want, _ = prog_lcm(db, min_sup)
    for batch in (1, 7, 256):
        assert _as_set(lcm_closed(db, min_sup, batch=batch)) == set(want)


@pytest.mark.parametrize("seed", [4, 5])
def test_lamp_matches_the_programs_oracle(seed):
    from repro.core.lamp import lamp as prog_lamp

    db, labels, _ = _small(seed, n=400, m=40, density=0.12, n_pos=80)
    want = prog_lamp(db, labels, alpha=0.05)
    got = lamp.lamp(db, labels, 0.05)
    assert (got.lambda_final, got.min_sup, got.k, got.delta) == (
        want.lambda_final, want.min_sup, want.correction_factor, want.delta)
    assert len(want.significant) > 0
    got_map = {}
    for row, s, ps, p in zip(got.significant.closure, got.significant.support,
                             got.significant.pos_support, got.pvalue):
        items = frozenset(np.flatnonzero(np.unpackbits(row, bitorder="little")).tolist())
        got_map[items] = (int(s), int(ps), p)
    assert set(got_map) == {s.items for s in want.significant}
    for s in want.significant:
        sup, pos, p = got_map[s.items]
        assert (sup, pos) == (s.support, s.pos_support)
        assert abs(p - s.pvalue) <= 1e-12 * s.pvalue


def test_fisher_matches_the_programs():
    from repro.stats.fisher import fisher_pvalue, lamp_count_thresholds

    rng = np.random.default_rng(0)
    x = rng.integers(1, 3000, 5000)
    n = np.minimum(x, (x * rng.random(5000)).astype(int))
    want = fisher_pvalue(x, n, 12773, 1129)
    got = fisher.fisher_pvalue(x, n, 12773, 1129)
    live = want > 0
    assert np.max(np.abs(got - want)[live] / want[live]) < 1e-12
    assert np.array_equal(fisher.lamp_count_thresholds(697, 105, 0.05),
                          lamp_count_thresholds(697, 105, 0.05))


def _cohort(db, labels):
    return Cohort(db, labels, np.arange(len(labels)))


def test_control_fails_closed_itemsets():
    """Supports carried in bfloat16 round above 256: answers differ."""
    db, labels, _ = _small(7, n=700, m=40, density=0.5, n_pos=100)
    co = _cohort(db, labels)
    q = QuerySpec("closed_frequent", min_sup=330)
    want = Reference(co).answer(co, q)
    got = Reference(co, control=True).answer(co, q)
    checks = {c.name: c for c in compare([got], [want], {})}
    assert len(want.patterns) > 1
    assert not checks["wrong_answers"].ok


def test_control_fails_lamp():
    """P-values in float32 pass the P-value gap's limit."""
    db, labels, _ = _small(8, n=1500, m=40, density=0.1, n_pos=150)
    co = _cohort(db, labels)
    q = QuerySpec("significant", alpha=0.05)
    want = Reference(co).answer(co, q)
    got = Reference(co, control=True).answer(co, q)
    assert len(want.patterns) > 0
    checks = {c.name: c for c in compare([got], [want], {"pvalue_rel_gap": 1e-7})}
    assert not all(c.ok for c in checks.values())
    assert checks["pvalue_rel_gap"].value > 1e-7


def test_compare_counts_unanswered_and_wrong():
    a = Answer((5,), {b"\x01": (7,)})
    b = Answer((5,), {b"\x01": (8,)})
    checks = {c.name: c.value for c in compare([a, None, b], [a, a, a], {})}
    assert checks == {"unanswered": 1, "wrong_answers": 1}
