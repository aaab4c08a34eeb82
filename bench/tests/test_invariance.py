"""`--seed` changes the bits the program is handed, not the work it does:
three seeds' cohorts of each cell take the same nodes and supersteps, and
give the same supports, as the unpermuted instance."""

import pytest
from small import lamp_cell, serve_cell

from bench.harness.check import Reference, program_answer
from bench.harness.data import cohorts, instance
from bench.harness.traffic import query_mix

SEEDS = (11, 2**31 + 5, 2**45 + 1)


def _session():
    import jax

    from repro.api import MinerSession

    return MinerSession(jax.devices()[:1])


def _dataset(co):
    from repro.api import Dataset

    return Dataset.from_dense(co.db, co.labels)


def _work(report):
    return [(p.mode, p.n_nodes, p.supersteps) for p in report.phases]


def test_closed_itemset_work_and_answers_do_not_depend_on_the_seed():
    cell = serve_cell()
    base = instance(cell.config)
    session = _session()
    for q in query_mix(cell.traffic, cell.config):
        query = q.build()
        want = session.run(_dataset(base), query)
        ref = Reference(base).answer(base, q)
        assert program_answer(want, q, base.db.shape[1]) == ref
        for seed in SEEDS:
            co = cohorts(base, seed, 1)[0]
            got = session.run(_dataset(co), query)
            assert _work(got) == _work(want)
            assert program_answer(got, q, base.db.shape[1]) == ref
            assert Reference(base).answer(co, q) == ref


@pytest.mark.parametrize("seed", SEEDS)
def test_lamp_work_does_not_depend_on_the_seed(seed):
    """Phases read the labels only through N_pos or in their emission test, so
    every phase pops the same nodes in the same supersteps for any cohort."""
    cell = lamp_cell(chips=1)
    base = instance(cell.config)
    (q,) = query_mix(cell.traffic, cell.config)
    session = _session()
    want = session.run(_dataset(base), q.build())
    for co in cohorts(base, seed, 2, int(cell.traffic["label_swaps"])):
        got = session.run(_dataset(co), q.build())
        assert _work(got) == _work(want)
        assert (got.lambda_final, got.min_sup, got.correction_factor) == (
            want.lambda_final, want.min_sup, want.correction_factor)
        assert program_answer(got, q, base.db.shape[1]) == Reference(base).answer(co, q)
