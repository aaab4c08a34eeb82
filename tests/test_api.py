"""Session API: Dataset packing/bucketing, compile-once MinerSession,
first-class Query objects, typed reports, and the legacy shim.

Acceptance bars: a repeated query on a warm session (same shape bucket)
triggers **zero** recompiles — asserted via cache_info() — and returns
bit-identical ResultSets (incl. exact P-values) to a fresh
`lamp_distributed` run, on 1 in-process device and on 8 simulated devices
(subprocess); `session.run(SignificantPatternQuery(statistic="fisher"))`
reproduces the legacy `mine()` path bit-identically on both device counts;
chi2 / closed-frequent / top-k queries match sequential host oracles;
fisher and chi2 occupy distinct test-program cache entries while sharing
lamp1/count; the program cache is LRU-bounded.
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.api import (
    EXACT_BUCKETS,
    BucketPolicy,
    ClosedFrequentQuery,
    Dataset,
    MinerSession,
    RuntimeConfig,
    ShapeBucket,
    SignificantPatternQuery,
    TopKSignificantQuery,
)
from repro.core.engine import EngineConfig, MineOutput, lamp_distributed
from repro.data.synthetic import SyntheticSpec, generate
from repro.results import ResultSet

HERE = os.path.dirname(os.path.abspath(__file__))

CFG = EngineConfig(expand_batch=8, stack_cap=2048, steal_max=32, push_cap=128)
RUNTIME = RuntimeConfig.from_engine_config(CFG)


def small_problem(seed=0, n=60, m=24, density=0.15, n_pos=20, planted=2):
    spec = SyntheticSpec(
        name="t", n_items=m, n_transactions=n, density=density, n_pos=n_pos,
        n_planted=planted, seed=seed,
    )
    return generate(spec)


def _keys(rs):
    return [(p.items, p.support, p.pos_support, p.pvalue, p.qvalue) for p in rs]


def _legacy(db, labels, **kw):
    with pytest.warns(DeprecationWarning):
        return lamp_distributed(db, labels, alpha=0.05, cfg=CFG, **kw)


# ------------------------------------------------------------------ Dataset
def test_bucket_policy_rounding():
    pol = BucketPolicy()  # x2 growth from (64, 16, 64)
    assert pol.bucket_for(60, 20, 24) == ShapeBucket(64, 32, 64)
    assert pol.bucket_for(64, 16, 64) == ShapeBucket(64, 16, 64)
    assert pol.bucket_for(65, 17, 65) == ShapeBucket(128, 32, 128)
    assert pol.bucket_for(697, 105, 225) == ShapeBucket(1024, 128, 256)
    assert pol.bucket_for(1, 1, 1) == ShapeBucket(64, 16, 64)
    exact = EXACT_BUCKETS.bucket_for(697, 105, 225)
    assert exact == ShapeBucket(697, 105, 225)


def test_dataset_packs_once_padded_and_immutable():
    db, labels, _ = small_problem()
    ds = Dataset.from_dense(db, labels, name="d0")
    b = ds.bucket
    assert (ds.n_transactions, ds.n_pos, ds.n_items) == (60, 20, 24)
    assert ds.db_bits.shape == (b.items, b.words)
    assert ds.packed.occ0.shape == (b.words,)
    assert not ds.db_bits.flags.writeable
    assert not ds.labels.flags.writeable
    # padded item columns are all-zero bits — they can never gain support
    assert not ds.db_bits[ds.n_items:].any()
    # exact policy pads nothing
    ds_exact = Dataset.from_dense(db, labels, bucket_policy=EXACT_BUCKETS)
    assert ds_exact.db_bits.shape == (24, 2)


def test_dataset_from_transactions_and_tsv(tmp_path):
    txns = [["rs17", "rs3"], ["rs3"], ["rs17", "rs3", "rs99"]]
    labels = np.array([True, False, True])
    ds = Dataset.from_transactions(txns, labels, name="toy")
    assert ds.item_names == ("rs17", "rs3", "rs99")  # sorted vocabulary
    assert ds.n_items == 3 and ds.n_transactions == 3 and ds.n_pos == 2
    dense = np.array([[1, 1, 0], [0, 1, 0], [1, 1, 1]], dtype=bool)
    np.testing.assert_array_equal(
        ds.db_bits[:3], Dataset.from_dense(dense, labels).db_bits[:3]
    )

    path = tmp_path / "toy.tsv"
    path.write_text("1\trs17\trs3\n0\trs3\n1\trs17\trs3\trs99\n")
    ds2 = Dataset.from_tsv(str(path))
    assert ds2.item_names == ds.item_names
    np.testing.assert_array_equal(ds2.db_bits, ds.db_bits)
    np.testing.assert_array_equal(ds2.labels, labels)


# ------------------------------------------------------- RuntimeConfig.resolve
def test_runtime_resolve_moves_launcher_heuristic_into_library():
    rt = RuntimeConfig()
    cfg = rt.resolve(ShapeBucket(1024, 128, 256), n_devices=8)
    # small problems keep the old items-based floor
    assert cfg.stack_cap == 8192
    # the heuristic grows with items per miner exactly as the CLI rule did
    cfg_big = rt.resolve(ShapeBucket(1024, 128, 262144), n_devices=8)
    assert cfg_big.stack_cap == 2 * 262144 // 8 + 64


def test_runtime_resolve_accounts_for_word_width():
    rt = RuntimeConfig(stack_mem_mb=4)
    wide = rt.resolve(ShapeBucket(1 << 20, 128, 65536), n_devices=1)   # W=32768
    thin = rt.resolve(ShapeBucket(64, 16, 65536), n_devices=1)         # W=2
    # same items: the transaction-heavy bucket must get a smaller stack
    assert wide.stack_cap < thin.stack_cap
    node_bytes = 4 * ((1 << 20) // 32 + 4)
    assert wide.stack_cap * node_bytes <= 4 * 2**20 or \
        wide.stack_cap == 2 * (rt.push_cap + rt.steal_max + rt.expand_batch)
    # explicit stack_cap is never overridden
    assert RuntimeConfig(stack_cap=777).resolve(
        ShapeBucket(1 << 20, 128, 65536), 1).stack_cap == 777


def test_runtime_resolve_is_bucket_deterministic():
    """Same-bucket datasets resolve to the same EngineConfig (cache key)."""
    db1, l1, _ = small_problem(seed=0)
    db2, l2, _ = small_problem(seed=9)
    ds1, ds2 = Dataset.from_dense(db1, l1), Dataset.from_dense(db2, l2)
    assert ds1.bucket == ds2.bucket
    rt = RuntimeConfig()
    assert rt.resolve(ds1.bucket, 4) == rt.resolve(ds2.bucket, 4)


def test_kernel_impl_auto_resolves_per_backend(monkeypatch):
    """"auto" picks the Pallas kernel on TPU, its Triton lowering on GPU,
    and the jnp ref elsewhere."""
    import jax

    from repro.core.expand import resolve_kernel_impl

    assert resolve_kernel_impl("auto", backend="tpu") == "pallas"
    assert resolve_kernel_impl("auto", backend="cpu") == "ref"
    assert resolve_kernel_impl("auto", backend="gpu") == "pallas_gpu"
    # explicit choices always pass through untouched
    assert resolve_kernel_impl("pallas_interpret", backend="tpu") == "pallas_interpret"
    assert resolve_kernel_impl("ref", backend="tpu") == "ref"

    bucket = ShapeBucket(64, 16, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert RuntimeConfig().resolve(bucket, 1).kernel_impl == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert RuntimeConfig().resolve(bucket, 1).kernel_impl == "ref"
    # the resolved config is the cache key: "auto" never leaks into it
    assert "auto" not in (
        RuntimeConfig().resolve(bucket, 1).kernel_impl,
        RuntimeConfig(kernel_impl="pallas").resolve(bucket, 1).kernel_impl,
    )


def test_sync_period_lands_in_resolved_config_and_cache_key():
    bucket = ShapeBucket(64, 16, 64)
    a = RuntimeConfig(sync_period=1).resolve(bucket, 1)
    b = RuntimeConfig(sync_period=8).resolve(bucket, 1)
    assert a.sync_period == 1 and b.sync_period == 8
    assert a != b  # different cadences must never share a compiled program


# ------------------------------------------------- warm-vs-cold equivalence
def test_warm_query_zero_compiles_and_bit_identical_results():
    db1, l1, _ = small_problem(seed=0)
    db2, l2, _ = small_problem(seed=4)
    session = MinerSession(runtime=RUNTIME)

    rep1 = session.mine(Dataset.from_dense(db1, l1, name="q1"))
    ci1 = session.cache_info()
    assert rep1.cold
    assert ci1.misses == len(rep1.phases) == 3
    assert all(p.compile_s > 0 for p in rep1.phases)

    # second query, same bucket: ZERO new compiles, all phases warm
    rep2 = session.mine(Dataset.from_dense(db2, l2, name="q2"))
    ci2 = session.cache_info()
    assert ci2.misses == ci1.misses
    assert ci2.hits == ci1.hits + len(rep2.phases)
    assert not rep2.cold
    assert all(p.cache_hit and p.compile_s == 0.0 for p in rep2.phases)

    # both queries bit-identical to fresh legacy runs (incl. exact P-values)
    for rep, (db, labels) in ((rep1, (db1, l1)), (rep2, (db2, l2))):
        ref = _legacy(db, labels)
        assert rep.min_sup == ref["min_sup"]
        assert rep.correction_factor == ref["correction_factor"]
        assert rep.delta == ref["delta"]
        assert rep.n_significant == ref["n_significant"]
        assert _keys(rep.results) == _keys(ref["results"])


def test_warm_alpha_change_reuses_programs():
    """alpha enters as runtime data (thresholds/delta), never the cache key."""
    db, labels, _ = small_problem(seed=2)
    session = MinerSession(runtime=RUNTIME)
    ds = Dataset.from_dense(db, labels)
    session.mine(ds)
    before = session.cache_info()
    rep = session.mine(ds, alpha=0.01)
    after = session.cache_info()
    assert after.misses == before.misses
    assert rep.alpha == 0.01
    ref = _legacy(db, labels)  # alpha=0.05 sanity: stricter level, fewer hits
    assert rep.n_significant <= ref["n_significant"]


def test_fused23_session_matches_three_phase():
    db, labels, _ = small_problem(seed=4)
    session = MinerSession(runtime=RUNTIME)
    ds = Dataset.from_dense(db, labels)
    a = session.mine(ds, pipeline="three_phase")
    b = session.mine(ds, pipeline="fused23")
    assert len(b.phases) == 2
    assert (b.min_sup, b.correction_factor, b.delta, b.n_significant) == \
        (a.min_sup, a.correction_factor, a.delta, a.n_significant)
    assert _keys(b.results) == _keys(a.results)
    # fused23 reuses the already-warm lamp1 program: only count2d compiles
    assert session.cache_info().misses == 4


@pytest.mark.parametrize("statistic", ["fisher", "chi2"])
def test_fused23_filter_reads_the_correction_cells(statistic):
    """fused23's host filter reads the P-values the correction computed for
    the 2-D histogram's cells: the list and the count agree, and at most the
    root record is tested again."""
    db, labels, _ = small_problem(seed=4)
    session = MinerSession(runtime=RUNTIME)
    rep = session.run(Dataset.from_dense(db, labels),
                      SignificantPatternQuery(statistic=statistic,
                                              pipeline="fused23"))
    assert rep.results.complete and len(rep.results) > 0
    assert rep.n_significant == len(rep.results)
    (span,) = [e for e in session.tracer.events() if e["name"] == "pvalues"]
    assert span["args"]["n_records"] >= len(rep.results)
    assert span["args"]["n_tested"] <= 1


def test_unknown_pipeline_raises():
    db, labels, _ = small_problem()
    session = MinerSession(runtime=RUNTIME)
    with pytest.raises(ValueError, match="unknown pipeline"):
        session.mine(Dataset.from_dense(db, labels), pipeline="nope")


# ----------------------------------------------------------- legacy shim
def test_lamp_distributed_shim_dict_and_deprecation():
    db, labels, _ = small_problem(seed=0)
    res = _legacy(db, labels)
    assert set(res) == {
        "lambda_final", "min_sup", "correction_factor", "delta",
        "n_significant", "results", "phase_outputs",
    }
    assert isinstance(res["results"], ResultSet)
    assert len(res["phase_outputs"]) == 3
    assert all(isinstance(p, MineOutput) for p in res["phase_outputs"])
    fused = _legacy(db, labels, pipeline="fused23")
    assert len(fused["phase_outputs"]) == 2
    assert fused["n_significant"] == res["n_significant"]
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="unknown pipeline"):
            lamp_distributed(db, labels, pipeline="nope")
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="conflicts"):
            lamp_distributed(db, labels, fuse_phase23=True, pipeline="three_phase")


def test_engine_pipelines_reexport():
    from repro.core.engine import PIPELINES

    assert set(PIPELINES) == {"three_phase", "fused23"}


# ------------------------------------------------------------- item names
def test_item_names_flow_to_describe_and_exports(tmp_path):
    db, labels, _ = small_problem(seed=0)
    names = tuple(f"rs{j:04d}" for j in range(db.shape[1]))
    session = MinerSession(runtime=RUNTIME)
    rep = session.mine(Dataset.from_dense(db, labels, item_names=names))
    rs = rep.results
    assert len(rs) > 0
    p0 = rs.patterns[0]

    # human-readable output shows names
    text = rs.describe(3)
    assert names[p0.items[0]] in text

    # TSV keeps the machine-readable index column AND adds a names column
    tsv = rs.to_tsv(str(tmp_path / "p.tsv"))
    header = tsv.splitlines()[0].split("\t")
    assert header[:7] == ["rank", "items", "size", "support", "pos_support",
                          "pvalue", "qvalue"]
    assert header[7] == "names"
    row = dict(zip(header, tsv.splitlines()[1].split("\t")))
    assert tuple(map(int, row["items"].split(","))) == p0.items
    assert row["names"] == ",".join(names[j] for j in p0.items)

    # JSON: indices stay, names added per pattern
    payload = json.loads(rs.to_json())
    assert payload["patterns"][0]["items"] == list(p0.items)
    assert payload["patterns"][0]["names"] == [names[j] for j in p0.items]

    # unnamed datasets keep the legacy formats exactly
    rep2 = MinerSession(runtime=RUNTIME).mine(Dataset.from_dense(db, labels))
    assert "names" not in rep2.results.to_tsv().splitlines()[0].split("\t")
    assert "names" not in json.loads(rep2.results.to_json())["patterns"][0]


# ----------------------------------------------- multi-device warm session
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


# ------------------------------------------------- first-class Query objects
def _closed_oracle(db, labels, min_sup):
    """Sequential closed-frequent oracle: [(frozenset, sup, pos_sup)]."""
    from repro.core.bitmap import unpack_occ
    from repro.core.lcm import lcm_closed

    n = db.shape[0]
    out = []

    def on_closed(occ, sup, clo):
        pos = int(np.count_nonzero(unpack_occ(occ, n) & labels)) \
            if labels is not None else 0
        out.append((frozenset(clo.tolist()), sup, pos))

    lcm_closed(db, min_sup=min_sup, on_closed=on_closed)
    return out


def test_run_fisher_query_bit_identical_to_legacy_mine():
    """session.run(SignificantPatternQuery(statistic="fisher")) reproduces
    the legacy mine()/lamp_distributed path bit-for-bit, both pipelines."""
    db, labels, _ = small_problem(seed=3)
    for pipeline in ("three_phase", "fused23"):
        session = MinerSession(runtime=RUNTIME)
        rep = session.run(
            Dataset.from_dense(db, labels),
            SignificantPatternQuery(alpha=0.05, statistic="fisher",
                                    pipeline=pipeline),
        )
        ref = _legacy(db, labels, pipeline=pipeline)
        assert rep.min_sup == ref["min_sup"]
        assert rep.correction_factor == ref["correction_factor"]
        assert rep.delta == ref["delta"]
        assert rep.n_significant == ref["n_significant"]
        assert _keys(rep.results) == _keys(ref["results"])
        assert rep.statistic == "fisher" and rep.query == "significant"


def test_chi2_query_matches_sequential_oracle():
    from repro.core.lamp import lamp

    db, labels, _ = small_problem(seed=1)
    session = MinerSession(runtime=RUNTIME)
    ds = Dataset.from_dense(db, labels)
    for pipeline in ("three_phase", "fused23"):
        rep = session.run(ds, SignificantPatternQuery(
            alpha=0.05, statistic="chi2", pipeline=pipeline))
        ref = lamp(db, labels, alpha=0.05, statistic="chi2")
        assert rep.min_sup == ref.min_sup
        assert rep.correction_factor == ref.correction_factor
        assert rep.delta == ref.delta
        assert rep.n_significant == len(ref.significant)
        got = {(p.items, p.support, p.pos_support) for p in rep.results}
        want = {(tuple(sorted(s.items)), s.support, s.pos_support)
                for s in ref.significant}
        assert got == want
        # exact host P-values match the oracle's
        oracle_p = {tuple(sorted(s.items)): s.pvalue for s in ref.significant}
        for p in rep.results:
            assert p.pvalue == pytest.approx(oracle_p[p.items], rel=1e-12)


def test_fisher_chi2_distinct_programs_lamp1_count_shared():
    """The statistic joins the cache key for the traced modes only: fisher
    and chi2 test programs are distinct entries; lamp1/count are shared, so
    the second statistic compiles exactly one new program — and warm repeat
    queries of either statistic re-trace zero times."""
    db, labels, _ = small_problem(seed=2)
    session = MinerSession(runtime=RUNTIME)
    ds = Dataset.from_dense(db, labels)

    session.mine(ds)                                   # fisher: 3 compiles
    ci1 = session.cache_info()
    assert ci1.misses == 3
    session.run(ds, SignificantPatternQuery(statistic="chi2"))
    ci2 = session.cache_info()
    assert ci2.misses == 4                             # only the chi2 test
    test_entries = {p.statistic for p in ci2.programs if p.mode == "test"}
    assert test_entries == {"fisher", "chi2"}
    shared = {p.statistic for p in ci2.programs if p.mode in ("lamp1", "count")}
    assert shared == {None}

    # warm repeats of BOTH statistics: zero new compiles
    for stat in ("fisher", "chi2"):
        before = session.cache_info().misses
        rep = session.run(ds, SignificantPatternQuery(statistic=stat))
        assert session.cache_info().misses == before
        assert not rep.cold


def test_closed_frequent_query_matches_lcm_oracle():
    db, labels, _ = small_problem(seed=0)
    session = MinerSession(runtime=RUNTIME)
    rep = session.run(Dataset.from_dense(db, labels),
                      ClosedFrequentQuery(min_sup=10))
    oracle = _closed_oracle(db, labels, 10)
    assert rep.n_significant == len(oracle)
    from repro.api import QUERIES

    assert rep.query == "closed-frequent" and rep.statistic is None
    assert rep.query in QUERIES  # the tag round-trips into the registry
    got = {(frozenset(p.items), p.support, p.pos_support) for p in rep.results}
    want = set(oracle)
    assert got == want
    # untested patterns carry NaN P/q, sort by support, export null
    assert all(math.isnan(p.pvalue) and math.isnan(p.qvalue)
               for p in rep.results)
    sups = [p.support for p in rep.results]
    assert sups == sorted(sups, reverse=True)
    payload = json.loads(rep.results.to_json())
    assert payload["statistic"] is None
    assert payload["patterns"][0]["pvalue"] is None
    # TSV exports untested P/q as empty cells, never the string "nan"
    tsv_row = rep.results.to_tsv().splitlines()[1].split("\t")
    assert tsv_row[5] == "" and tsv_row[6] == ""

    # top_k truncates the ResultSet; the count stays exact
    rep_k = session.run(Dataset.from_dense(db, labels),
                        ClosedFrequentQuery(min_sup=10, top_k=3))
    assert len(rep_k.results) == 3
    assert rep_k.n_significant == len(oracle)
    assert [p.support for p in rep_k.results] == sups[:3]


def test_closed_frequent_works_without_labels():
    db, _, _ = small_problem(seed=5)
    session = MinerSession(runtime=RUNTIME)
    rep = session.run(Dataset.from_dense(db, None), ClosedFrequentQuery(min_sup=12))
    oracle = _closed_oracle(db, None, 12)
    assert rep.n_significant == len(oracle)
    assert {frozenset(p.items) for p in rep.results} == \
        {c[0] for c in oracle}


def test_topk_query_matches_oracle_and_probes_stay_warm():
    from repro.stats import get_statistic

    db, labels, _ = small_problem(seed=4)
    n, n_pos = db.shape[0], int(labels.sum())
    session = MinerSession(runtime=RUNTIME)
    ds = Dataset.from_dense(db, labels)
    rep = session.run(ds, TopKSignificantQuery(k=6))
    # every probe reuses ONE compiled test program
    assert session.cache_info().misses == 1
    assert len(rep.phases) >= 1
    assert sum(not p.cache_hit for p in rep.phases) == 1

    oracle = _closed_oracle(db, labels, 1)
    pv = get_statistic("fisher").pvalue(
        np.array([c[1] for c in oracle]), np.array([c[2] for c in oracle]),
        n, n_pos)
    want = np.sort(pv)[:6]
    got = np.array([p.pvalue for p in rep.results])
    assert len(got) == 6
    assert np.all(np.diff(got) >= 0)
    assert np.allclose(got, want, rtol=1e-12)
    assert rep.n_significant == 6 and rep.query == "topk"

    # warm second top-k (different k): still zero new compiles
    rep2 = session.run(ds, TopKSignificantQuery(k=2))
    assert session.cache_info().misses == 1
    assert [p.pvalue for p in rep2.results] == [p.pvalue for p in rep.results][:2]


def test_query_constructors_validate_parameters():
    with pytest.raises(ValueError, match="alpha.*\\(0, 1\\)"):
        SignificantPatternQuery(alpha=1.5)
    with pytest.raises(ValueError, match="alpha"):
        SignificantPatternQuery(alpha=0.0)
    with pytest.raises(ValueError, match="unknown test statistic"):
        SignificantPatternQuery(statistic="nope")
    with pytest.raises(ValueError, match="min_sup must be an int >= 1"):
        ClosedFrequentQuery(min_sup=0)
    with pytest.raises(ValueError, match="top_k"):
        ClosedFrequentQuery(min_sup=5, top_k=0)
    with pytest.raises(ValueError, match="k must be an int >= 1"):
        TopKSignificantQuery(k=0)
    with pytest.raises(ValueError, match="unknown test statistic"):
        TopKSignificantQuery(k=3, statistic="nope")


def test_run_phase_and_run_validate_inputs():
    db, labels, _ = small_problem()
    session = MinerSession(runtime=RUNTIME)
    ds = Dataset.from_dense(db, labels)
    # a bare assert would vanish under python -O; this must stay a ValueError
    with pytest.raises(ValueError, match="unknown engine mode.*lamp1"):
        session.run_phase(ds, "count3d")
    with pytest.raises(ValueError, match="unknown test statistic"):
        session.run_phase(ds, "test", statistic="nope")
    with pytest.raises(TypeError, match="repro.api.Query"):
        session.run(ds, "significant")
    with pytest.raises(ValueError, match="unknown pipeline"):
        session.run(ds, SignificantPatternQuery(pipeline="nope"))
    # testing objectives refuse unlabelled datasets with an actionable error
    ds_unlabelled = Dataset.from_dense(db, None)
    with pytest.raises(ValueError, match="labels"):
        session.run(ds_unlabelled, SignificantPatternQuery())
    with pytest.raises(ValueError, match="labels"):
        session.run(ds_unlabelled, TopKSignificantQuery(k=3))
    # statistic=None means "no test" elsewhere; mine() must not read it as
    # "session default" silently
    with pytest.raises(ValueError, match="ClosedFrequentQuery"):
        session.mine(ds, statistic=None)


def test_engine_mine_rejects_unknown_mode():
    from repro.core.engine import mine

    db, labels, _ = small_problem()
    with pytest.raises(ValueError, match="unknown engine mode"):
        mine(db, labels, mode="bogus")


# ------------------------------------------------------- bounded program cache
def test_program_cache_lru_eviction_and_clear():
    db, labels, _ = small_problem(seed=0)
    session = MinerSession(runtime=RUNTIME.with_options(max_programs=2))
    ds = Dataset.from_dense(db, labels)

    session.run_phase(ds, "lamp1")
    session.run_phase(ds, "count", min_sup=5)
    ci = session.cache_info()
    assert (ci.n_programs, ci.evictions) == (2, 0)

    # third program evicts the least recently used (lamp1)
    session.run_phase(ds, "test", min_sup=5, delta=1e-4)
    ci = session.cache_info()
    assert (ci.n_programs, ci.evictions) == (2, 1)
    assert {p.mode for p in ci.programs} == {"count", "test"}
    assert "evicted" in str(ci)

    # a hit refreshes recency: count survives the next insertion
    session.run_phase(ds, "count", min_sup=5)
    session.run_phase(ds, "lamp1")
    ci = session.cache_info()
    assert {p.mode for p in ci.programs} == {"count", "lamp1"}
    assert ci.evictions == 2

    # evicted programs recompile on return (a new miss)
    misses = ci.misses
    session.run_phase(ds, "test", min_sup=5, delta=1e-4)
    assert session.cache_info().misses == misses + 1

    # clear_cache drops everything but keeps the counters
    n = session.clear_cache()
    ci2 = session.cache_info()
    assert n == 2 and ci2.n_programs == 0
    assert ci2.misses == misses + 1 and ci2.evictions == 3

    with pytest.raises(ValueError, match="max_programs"):
        MinerSession(runtime=RUNTIME.with_options(max_programs=0))


@pytest.mark.slow
def test_run_vs_legacy_8dev_bit_identical():
    """8 simulated miners: session.run(SignificantPatternQuery) reproduces
    the legacy lamp_distributed dict bit-identically (incl. P-values)."""
    prob = dict(n_items=24, n_transactions=60, density=0.15, n_pos=20, seed=1)
    for pipeline in ("three_phase", "fused23"):
        got = run_subproc(dict(prob, mode="run_vs_legacy", n_devices=8,
                               pipeline=pipeline))
        assert got["run"] == got["legacy"], pipeline


@pytest.mark.slow
def test_session_8dev_warm_query_zero_compiles_and_matches_1dev():
    """8 simulated miners: the warm query compiles nothing and both queries
    return byte-identical patterns to a 1-device in-process session."""
    prob = dict(n_items=24, n_transactions=60, density=0.15, n_pos=20,
                seed=1, seed2=5)
    got = run_subproc(dict(prob, mode="session", n_devices=8))
    assert got["misses_per_query"][0] == 3          # cold: one per phase
    assert got["misses_per_query"][1] == 3          # warm: zero new compiles
    assert got["n_programs"] == 3
    assert got["queries"][0]["cold"] and not got["queries"][1]["cold"]

    session = MinerSession(devices=jax.devices()[:1], runtime=RUNTIME)
    for q, seed in zip(got["queries"], (1, 5)):
        db, labels, _ = small_problem(seed=seed)
        rep = session.mine(Dataset.from_dense(db, labels))
        assert q["min_sup"] == rep.min_sup
        assert q["correction_factor"] == rep.correction_factor
        assert q["n_significant"] == rep.n_significant
        want = [[list(p.items), p.support, p.pos_support] for p in rep.results]
        assert [p[:3] for p in q["patterns"]] == want
        for (_, _, _, pv, qv), p in zip(q["patterns"], rep.results):
            assert pv == pytest.approx(p.pvalue, rel=1e-12)
            assert qv == pytest.approx(p.qvalue, rel=1e-12)
