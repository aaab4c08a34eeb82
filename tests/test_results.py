"""Results subsystem: emission -> closure reconstruction -> dedup -> exact test.

The acceptance bar (ISSUE 2): on a synthetic case-control problem with
planted significant patterns, `lamp_distributed` returns a ResultSet whose
exported top-k contains every planted pattern's closure with its exact Fisher
P-value (recall 1.0 when out_cap suffices), identically for 1-device and
8-simulated-device runs and for both three_phase and fused23 pipelines.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core.engine import EngineConfig, lamp_distributed, mine
from repro.core.fisher import fisher_pvalue
from repro.core.lamp import lamp
from repro.data.synthetic import SyntheticSpec, generate
from repro.core.bitmap import full_occ, pack_db, supports_np
from repro.obs import SpanTracer
from repro.results import (
    Pattern,
    ResultSet,
    ResultStream,
    build_result_set,
    dedup_by_closure,
    reconstruct_closures,
    score_planted,
)
from repro.stats import get_statistic

HERE = os.path.dirname(os.path.abspath(__file__))

CFG = EngineConfig(expand_batch=8, stack_cap=2048, steal_max=32, push_cap=128)


def small_problem(seed=0, n=60, m=24, density=0.15, n_pos=20, planted=2):
    spec = SyntheticSpec(
        name="t", n_items=m, n_transactions=n, density=density, n_pos=n_pos,
        n_planted=planted, seed=seed,
    )
    return generate(spec)


def planted_problem():
    """Strong planted signal: the engine must recover every planted closure."""
    spec = SyntheticSpec(
        name="planted", n_items=48, n_transactions=120, density=0.06, n_pos=40,
        n_planted=2, planted_pos_rate=0.75, planted_neg_rate=0.02, seed=7,
    )
    return generate(spec)


def _pattern_key(p):
    return (p.items, p.support, p.pos_support)


def _oracle_patterns(db, labels, alpha=0.05):
    ref = lamp(db, labels, alpha=alpha)
    return ref, sorted(
        (tuple(sorted(s.items)), s.support, s.pos_support, s.pvalue)
        for s in ref.significant if s.items
    )


# ------------------------------------------------------- oracle equivalence
@pytest.mark.parametrize("seed", [0, 4])
def test_three_phase_resultset_matches_oracle(seed):
    db, labels, _ = small_problem(seed=seed)
    res = lamp_distributed(db, labels, alpha=0.05, cfg=CFG)
    rs = res["results"]
    assert isinstance(rs, ResultSet)
    assert rs.complete and rs.n_dropped == 0
    assert len(rs) == res["n_significant"]
    ref, want = _oracle_patterns(db, labels)
    got = sorted((p.items, p.support, p.pos_support, p.pvalue) for p in rs)
    assert got == want  # identities AND exact float64 P-values
    # Bonferroni q-values and P-value ordering
    k = res["correction_factor"]
    for p in rs:
        assert p.qvalue == min(1.0, p.pvalue * k)
        assert p.pvalue <= res["delta"]
    pv = [p.pvalue for p in rs]
    assert pv == sorted(pv)


def test_fused23_resultset_identical_to_three_phase():
    db, labels, _ = small_problem(seed=4)
    a = lamp_distributed(db, labels, alpha=0.05, cfg=CFG)
    b = lamp_distributed(db, labels, alpha=0.05, cfg=CFG, pipeline="fused23")
    assert b["results"].delta == a["results"].delta
    pa = [(p.items, p.support, p.pos_support, p.pvalue, p.qvalue)
          for p in a["results"]]
    pb = [(p.items, p.support, p.pos_support, p.pvalue, p.qvalue)
          for p in b["results"]]
    assert pa == pb
    assert len(b["results"]) == b["n_significant"]


def test_single_device_matches_all_devices():
    """devices=[d0] vs the full local device set: identical ResultSet."""
    db, labels, _ = small_problem(seed=2)
    one = lamp_distributed(db, labels, alpha=0.05, cfg=CFG,
                           devices=jax.devices()[:1])
    full = lamp_distributed(db, labels, alpha=0.05, cfg=CFG)
    assert ([_pattern_key(p) + (p.pvalue,) for p in one["results"]]
            == [_pattern_key(p) + (p.pvalue,) for p in full["results"]])


# ------------------------------------------------ planted recovery + export
@pytest.mark.parametrize("pipeline", ["three_phase", "fused23"])
def test_planted_recovery_and_topk_export(tmp_path, pipeline):
    db, labels, planted = planted_problem()
    res = lamp_distributed(db, labels, alpha=0.05, cfg=CFG, pipeline=pipeline)
    rs = res["results"]
    assert rs.complete, "out_cap must suffice for the acceptance criterion"

    score = score_planted(rs, planted)
    assert score["recall"] == 1.0, f"missed planted itemsets: {score['missed']}"

    n, n_pos = db.shape[0], int(labels.sum())
    top = rs.top(len(rs))

    # TSV export round-trip: every planted closure appears with its exact P
    tsv_path = tmp_path / "patterns.tsv"
    rs.save(str(tsv_path))
    lines = tsv_path.read_text().strip().splitlines()
    header = lines[0].split("\t")
    rows = [dict(zip(header, ln.split("\t"))) for ln in lines[1:]]
    assert len(rows) == len(top)
    by_items = {tuple(map(int, r["items"].split(","))): r for r in rows}
    for pl in planted:
        match = [items for items in by_items if set(pl) <= set(items)]
        assert match, f"planted {pl} not in TSV export"
        for items in match:
            r = by_items[items]
            exact = fisher_pvalue(int(r["support"]), int(r["pos_support"]),
                                  n, n_pos)[0]
            assert float(r["pvalue"]) == pytest.approx(exact, rel=1e-5)

    # JSON export round-trip carries the full testing context
    json_path = tmp_path / "patterns.json"
    rs.save(str(json_path))
    payload = json.loads(json_path.read_text())
    assert payload["n_patterns"] == len(rs)
    assert payload["complete"] is True
    assert payload["delta"] == res["delta"]
    assert payload["correction_factor"] == res["correction_factor"]
    got = {tuple(p["items"]) for p in payload["patterns"]}
    for pl in planted:
        assert any(set(pl) <= set(items) for items in got)


def test_top_k_selection_is_prefix_of_pvalue_order():
    db, labels, _ = small_problem(seed=0)
    rs = lamp_distributed(db, labels, alpha=0.05, cfg=CFG)["results"]
    assert rs.top(3) == rs.patterns[:3]
    assert rs.top(None) == rs.patterns
    assert len(rs.to_tsv(top_k=3).strip().splitlines()) == 1 + min(3, len(rs))


# ------------------------------------------------------------ overflow path
def test_emission_overflow_warns_counts_and_flags_incomplete():
    db, labels, _ = small_problem(seed=0)
    base = lamp_distributed(db, labels, alpha=0.05, cfg=CFG)
    assert base["n_significant"] > 2
    tiny = EngineConfig(expand_batch=8, stack_cap=2048, steal_max=32,
                        push_cap=128, out_cap=2)
    with pytest.warns(RuntimeWarning, match="emission overflow"):
        res = mine(db, labels, mode="test", min_sup=base["min_sup"],
                   delta=base["delta"], cfg=tiny)
    # counts stay exact; only the materialized pattern list is clipped
    assert res.sig_count == base["n_significant"]
    n_devices = len(jax.devices())
    assert res.emit_dropped >= base["n_significant"] - 2 * n_devices
    assert res.emit_dropped == int(res.stats["emit_dropped"].sum())
    with pytest.warns(RuntimeWarning, match="emission overflow"):
        rs = lamp_distributed(db, labels, alpha=0.05, cfg=tiny)["results"]
    assert not rs.complete and rs.n_dropped > 0
    assert len(rs) < base["n_significant"]
    base_keys = {_pattern_key(p) for p in base["results"]}
    assert {_pattern_key(p) for p in rs} <= base_keys


# ------------------------------------------------- P-values from tested cells
def _host_records(seed=0):
    """Records of every item and item pair of a small problem, one per
    closure, with the problem's packed bits: (db_bits, occ, sup, pos_sup,
    n, n_pos)."""
    db, labels, _ = small_problem(seed=seed)
    n, m = db.shape
    db_bits = pack_db(db)
    pos_bits = pack_db(labels.astype(bool)[:, None])
    pairs = [(i, i) for i in range(m)] + [
        (i, j) for i in range(m) for j in range(i + 1, m)]
    occ = np.stack([db_bits[i] & db_bits[j] for i, j in pairs])
    sup = supports_np(occ, full_occ(n)[None])[:, 0].astype(np.int64)
    occ, sup = occ[sup > 0], sup[sup > 0]
    pos_sup = supports_np(occ, pos_bits)[:, 0].astype(np.int64)
    closures = reconstruct_closures(occ, sup, db_bits)
    first = {}
    for i, c in enumerate(closures):
        first.setdefault(c, i)
    keep = np.array(sorted(first.values()))
    return db_bits, occ[keep], sup[keep], pos_sup[keep], n, int(labels.sum())


def _cells(statistic, sup, pos_sup, n, n_pos):
    """The distinct (sup, pos_sup) cells, tested in one batch, in row-major
    order as the fused pipeline's correction tests them."""
    key = np.unique(sup * (n_pos + 1) + pos_sup)
    xs, ns = key // (n_pos + 1), key % (n_pos + 1)
    return xs, ns, get_statistic(statistic).pvalue(xs, ns, n, n_pos)


def _build(records, statistic, delta, streaming, cells):
    """One filtered build -> (ResultSet, the `pvalues` span's args)."""
    db_bits, occ, sup, pos_sup, n, n_pos = records
    tracer = SpanTracer()
    stream = (ResultStream(head_k=3, on_head=lambda head: None, chunk=16)
              if streaming else None)
    rs = build_result_set(
        occ, sup, pos_sup, db_bits, n=n, n_pos=n_pos, alpha=0.05, min_sup=1,
        correction_factor=len(sup), delta=delta, filter_host=True,
        statistic=statistic, stream=stream, tracer=tracer, cell_pvalues=cells,
    )
    (span,) = [e for e in tracer.events() if e["name"] == "pvalues"]
    return rs, span["args"]


def _assert_same_results(a, b):
    assert len(a) > 0
    assert [_pattern_key(p) for p in a] == [_pattern_key(p) for p in b]
    pa = np.array([p.pvalue for p in a])
    pb = np.array([p.pvalue for p in b])
    np.testing.assert_allclose(pa, pb, rtol=1e-12, atol=0)


@pytest.mark.parametrize("streaming", [False, True], ids=["batch", "stream"])
@pytest.mark.parametrize("statistic", ["fisher", "chi2"])
def test_cell_pvalues_give_the_direct_test_results(statistic, streaming):
    """Records read their P-values from the tested cells: the same patterns
    in the same order as testing every record, and nothing tested again."""
    records = _host_records()
    _, _, sup, pos_sup, n, n_pos = records
    cells = _cells(statistic, sup, pos_sup, n, n_pos)
    delta = float(np.quantile(cells[2], 0.5))
    direct, direct_args = _build(records, statistic, delta, streaming, None)
    looked_up, args = _build(records, statistic, delta, streaming, cells)
    _assert_same_results(looked_up, direct)
    assert len(looked_up) < len(sup)  # the delta filter dropped some
    assert args == {"n_records": len(sup), "n_tested": 0}
    assert direct_args == {"n_records": len(sup), "n_tested": len(sup)}


@pytest.mark.parametrize("missing", ["dropped_cell", "root"])
@pytest.mark.parametrize("streaming", [False, True], ids=["batch", "stream"])
def test_records_without_a_tested_cell_are_tested_directly(missing, streaming):
    statistic = "chi2"  # the root's P (0.5) passes a delta of 0.5
    db_bits, occ, sup, pos_sup, n, n_pos = _host_records()
    xs, ns, pv = _cells(statistic, sup, pos_sup, n, n_pos)
    if missing == "dropped_cell":
        gone = np.flatnonzero((xs == sup[0]) & (ns == pos_sup[0]))
        xs, ns, pv = (np.delete(a, gone) for a in (xs, ns, pv))
        n_missing = int(np.sum((sup == sup[0]) & (pos_sup == pos_sup[0])))
    else:
        assert not np.any((xs == n) & (ns == n_pos))
        occ = np.concatenate([occ, full_occ(n)[None]])
        sup, pos_sup = np.append(sup, n), np.append(pos_sup, n_pos)
        n_missing = 1
    records = (db_bits, occ, sup, pos_sup, n, n_pos)
    direct, _ = _build(records, statistic, 0.5, streaming, None)
    looked_up, args = _build(records, statistic, 0.5, streaming, (xs, ns, pv))
    _assert_same_results(looked_up, direct)
    assert args == {"n_records": len(sup), "n_tested": n_missing}
    if missing == "root":
        assert any(p.support == n for p in looked_up)


# ------------------------------------------------------------------ scoring
def test_score_planted_precision_recall():
    mined = [
        Pattern(items=(1, 2, 3), support=10, pos_support=9, pvalue=1e-6, qvalue=1e-4),
        Pattern(items=(7,), support=8, pos_support=7, pvalue=1e-4, qvalue=1e-2),
    ]
    score = score_planted(mined, planted=[[1, 2], [4, 5]])
    assert score["recall"] == 0.5
    assert score["precision"] == 0.5
    assert score["recovered"] == [[1, 2]]
    assert score["missed"] == [[4, 5]]
    empty = score_planted([], planted=[[1, 2]])
    assert empty["recall"] == 0.0 and empty["precision"] == 0.0


# ----------------------------------------------------- multi-device oracles
def run_subproc(spec: dict) -> dict:
    from repro.core.collectives import host_device_count_env

    env = host_device_count_env(spec["n_devices"])
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "engine_subproc_main.py"),
         json.dumps(spec)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("pipeline", ["three_phase", "fused23"])
def test_multidevice_resultset_matches_single_device(pipeline):
    """8 simulated miners return byte-identical patterns to the P=1 run."""
    prob = dict(n_items=24, n_transactions=60, density=0.15, n_pos=20, seed=1)
    got = run_subproc(dict(prob, mode="lamp_full", n_devices=8,
                           pipeline=pipeline))
    db, labels, _ = small_problem(seed=1)
    one = lamp_distributed(db, labels, alpha=0.05, cfg=CFG,
                           devices=jax.devices()[:1], pipeline=pipeline)
    want = [[list(p.items), p.support, p.pos_support] for p in one["results"]]
    assert [p[:3] for p in got["patterns"]] == want
    for (_, _, _, pv, qv), p in zip(got["patterns"], one["results"]):
        assert pv == pytest.approx(p.pvalue, rel=1e-12)
        assert qv == pytest.approx(p.qvalue, rel=1e-12)
    assert got["patterns_complete"]
    assert got["n_significant"] == one["n_significant"]
