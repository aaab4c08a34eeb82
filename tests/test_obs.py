"""Observability subsystem (repro.obs, DESIGN.md §9).

Three layers, three contracts:

* device superstep trace — tracing must be *free of observable effect*
  (traced and untraced runs bit-identical), the decoded timeline must
  reconcile with the engine's cumulative counters, and ring wrap must be
  loud (trace_dropped + RuntimeWarning, mirroring emit_dropped);
* host span tracer — Chrome-trace JSON any viewer loads;
* metrics registry — Prometheus text exposition any scraper parses.

The exporter formats are pinned by the same validators CI runs against the
artifacts of a real traced mine (repro.obs.validate).  Multi-device trace
parity runs in a subprocess (pytest's jax is already initialized with one
device); decode invariants are property-tested under hypothesis with a
seeded sweep fallback.
"""

import io
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.obs import (
    DEFAULT_TRACE_CAP,
    JsonlLogger,
    MetricsRegistry,
    N_FIELDS,
    SpanTracer,
    TraceField,
    decode_trace,
    jain_fairness,
)
from repro.obs.trace import expected_samples
from repro.obs.validate import validate_chrome_trace, validate_prometheus_text

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

HERE = os.path.dirname(os.path.abspath(__file__))


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


# ------------------------------------------------------------------ trace unit
def make_ring(n_miners, cap, supersteps, period, seed=0):
    """Simulate the engine's ring writes exactly (slot = idx % cap)."""
    rng = np.random.default_rng(seed)
    raw = np.zeros((n_miners, cap, N_FIELDS), np.int32)
    for t in range(supersteps):
        if t % period:
            continue
        idx = t // period
        rec = rng.integers(0, 100, size=(n_miners, N_FIELDS)).astype(np.int32)
        rec[:, TraceField.STEP] = t
        raw[:, idx % cap, :] = rec
    return raw


def check_invariants(tr, n_miners, cap, supersteps, period):
    n_sampled = expected_samples(supersteps, period)
    assert tr.n_steps == min(n_sampled, cap)
    assert tr.dropped == n_sampled - tr.n_steps
    assert tr.n_miners == n_miners
    # superstep ids strictly increasing, all multiples of the period,
    # and — after a wrap — exactly the most recent window
    assert np.all(np.diff(tr.steps) > 0)
    assert np.all(tr.steps % period == 0)
    if tr.dropped:
        assert tr.steps[0] == tr.dropped * period
    per_miner = (tr.depth, tr.popped, tr.pushed, tr.closed, tr.emitted,
                 tr.donated, tr.received)
    for arr in per_miner:
        assert arr.shape == (n_miners, tr.n_steps)
    for f in (tr.donation_fairness(), tr.work_fairness()):
        assert 0.0 <= f <= 1.0 + 1e-12
    idle = tr.idle_fraction()
    assert idle.shape == (n_miners,)
    assert np.all((idle >= 0) & (idle <= 1))
    json.dumps(tr.summary())  # metrics blob must be JSON-able


def test_decode_no_wrap():
    raw = make_ring(4, cap=64, supersteps=40, period=1)
    tr = decode_trace(raw, supersteps=40, period=1)
    check_invariants(tr, 4, 64, 40, 1)
    assert tr.steps.tolist() == list(range(40))


def test_decode_wrap_keeps_most_recent_window():
    raw = make_ring(2, cap=8, supersteps=30, period=1)
    tr = decode_trace(raw, supersteps=30, period=1)
    check_invariants(tr, 2, 8, 30, 1)
    assert tr.dropped == 22
    assert tr.steps.tolist() == list(range(22, 30))


def test_decode_sampled_period():
    raw = make_ring(3, cap=16, supersteps=50, period=4)
    tr = decode_trace(raw, supersteps=50, period=4)
    check_invariants(tr, 3, 16, 50, 4)
    assert tr.steps.tolist() == list(range(0, 50, 4))


def test_decode_rejects_wrong_shape():
    with pytest.raises(ValueError, match="expected raw trace"):
        decode_trace(np.zeros((2, 8, N_FIELDS + 1)), supersteps=8, period=1)


if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(
        n_miners=st.integers(1, 6),
        cap=st.integers(1, 32),
        supersteps=st.integers(0, 120),
        period=st.integers(1, 7),
    )
    def test_decode_invariants_property(n_miners, cap, supersteps, period):
        raw = make_ring(n_miners, cap, supersteps, period, seed=cap)
        tr = decode_trace(raw, supersteps=supersteps, period=period)
        check_invariants(tr, n_miners, cap, supersteps, period)


def test_decode_invariants_seeded_sweep():
    """Seeded sweep of the same invariants — always runs, even without
    hypothesis."""
    rng = np.random.default_rng(7)
    for _ in range(40):
        n_miners = int(rng.integers(1, 7))
        cap = int(rng.integers(1, 33))
        supersteps = int(rng.integers(0, 121))
        period = int(rng.integers(1, 8))
        raw = make_ring(n_miners, cap, supersteps, period, seed=cap)
        tr = decode_trace(raw, supersteps=supersteps, period=period)
        check_invariants(tr, n_miners, cap, supersteps, period)


def test_jain_fairness():
    assert jain_fairness([1, 1, 1, 1]) == pytest.approx(1.0)
    assert jain_fairness([4, 0, 0, 0]) == pytest.approx(0.25)
    assert jain_fairness([0, 0, 0]) == 1.0  # nothing to share = fair
    assert jain_fairness([]) == 1.0
    x = np.random.default_rng(0).integers(0, 50, 16)
    assert 1 / 16 <= jain_fairness(x) <= 1.0


# ------------------------------------------------------------- engine tracing
def _problem(seed=0):
    from repro.data.synthetic import SyntheticSpec, generate

    return generate(SyntheticSpec(
        name="obs", n_items=24, n_transactions=60, density=0.15, n_pos=20,
        n_planted=2, seed=seed,
    ))


def _cfg(**kw):
    from repro.core.engine import EngineConfig

    return EngineConfig(expand_batch=8, stack_cap=2048, steal_max=32,
                        push_cap=128, **kw)


@pytest.mark.parametrize("mode", ["lamp1", "count", "test"])
def test_tracing_is_bit_identical(mode):
    """The tentpole's contract: trace_period changes the carry, never the
    answer — histogram, lambda, and emitted records all match exactly."""
    from repro.core.engine import mine

    db, labels, _ = _problem(seed=0)
    kw = dict(min_sup=3) if mode != "lamp1" else {}
    off = mine(db, labels, mode=mode, cfg=_cfg(), **kw)
    on = mine(db, labels, mode=mode,
              cfg=_cfg(trace_period=1, trace_cap=1024), **kw)
    np.testing.assert_array_equal(off.hist, on.hist)
    assert off.lam_final == on.lam_final
    assert off.supersteps == on.supersteps
    assert off.sig_count == on.sig_count
    if mode == "test":
        np.testing.assert_array_equal(off.sig_occ, on.sig_occ)
        np.testing.assert_array_equal(off.sig_sup, on.sig_sup)
    assert off.trace is None
    assert on.trace is not None


def test_trace_reconciles_with_stats():
    """Per-step trace volumes summed over time == the cumulative counters."""
    from repro.core.engine import mine

    db, labels, _ = _problem(seed=1)
    res = mine(db, labels, mode="lamp1",
               cfg=_cfg(trace_period=1, trace_cap=1024))
    tr = res.trace
    assert tr.n_steps == res.supersteps and tr.dropped == 0
    np.testing.assert_array_equal(tr.popped.sum(axis=1), res.stats["popped"])
    np.testing.assert_array_equal(tr.pushed.sum(axis=1), res.stats["pushed"])
    np.testing.assert_array_equal(tr.closed.sum(axis=1), res.stats["closed"])
    assert int(tr.fired.sum()) == int(res.stats["steal_rounds"][0])
    assert np.all(tr.depth >= 0)
    assert np.all(np.diff(tr.lam) >= 0)  # LAMP lambda only ratchets up
    assert tr.lam[-1] <= res.lam_final  # recorded pre-sync


def test_ring_wrap_warns_and_counts():
    from repro.core.engine import mine

    db, labels, _ = _problem(seed=0)
    full = mine(db, labels, mode="count", min_sup=3,
                cfg=_cfg(trace_period=1, trace_cap=1024))
    assert full.trace_dropped == 0
    cap = 4
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        res = mine(db, labels, mode="count", min_sup=3,
                   cfg=_cfg(trace_period=1, trace_cap=cap))
    assert any("trace ring wrapped" in str(x.message) for x in w)
    assert res.trace_dropped == res.supersteps - cap
    # the device-side counter agrees with the host-side decode; every miner
    # samples on the same global step cadence, so the [P] counter is uniform
    np.testing.assert_array_equal(
        res.stats["trace_dropped"],
        np.full_like(res.stats["trace_dropped"], res.trace_dropped),
    )
    # the surviving window is the most recent one, results still exact
    assert res.trace.steps.tolist() == list(
        range(res.supersteps - cap, res.supersteps)
    )
    np.testing.assert_array_equal(res.hist, full.hist)


def test_trace_period_validation():
    from repro.core.engine import mine

    db, labels, _ = _problem(seed=0)
    with pytest.raises(ValueError, match="requires trace_cap"):
        mine(db, labels, mode="count", min_sup=3, cfg=_cfg(trace_period=1))
    with pytest.raises(ValueError, match="trace_period"):
        mine(db, labels, mode="count", min_sup=3,
             cfg=_cfg(trace_period=-1, trace_cap=8))


@pytest.mark.slow
@pytest.mark.parametrize("n_devices", [8])
def test_multidevice_trace_parity(n_devices):
    """8 simulated miners: tracing stays bit-identical with real steal
    traffic in flight, and the decoded timeline reconciles per miner."""
    got = run_subproc(dict(
        n_items=24, n_transactions=60, density=0.15, n_pos=20, seed=0,
        mode="trace_parity", n_devices=n_devices, trace_period=1,
        trace_cap=4096,
    ))
    assert got["hist_equal"] and got["lam_equal"] and got["supersteps_equal"]
    assert got["dropped"] == 0
    assert got["sampled_steps"] == got["supersteps"]
    assert got["steps_monotone"] and got["depth_nonneg"]
    assert got["popped_matches_stats"] and got["fired_matches_stats"]
    assert 1 / n_devices <= got["donation_fairness"] <= 1.0 + 1e-12


# -------------------------------------------------------------- session layer
def test_session_trace_and_metrics_wiring():
    from repro.api import Dataset, MinerSession, RuntimeConfig

    db, labels, _ = _problem(seed=2)
    ds = Dataset.from_dense(db, labels, name="obs")
    session = MinerSession(
        runtime=RuntimeConfig(trace_period=1, trace_cap=512))
    rep = session.mine(ds)
    rep2 = session.mine(ds)  # warm
    for p in rep.phases + rep2.phases:
        assert p.trace is not None
        assert p.trace.n_steps == p.supersteps
    # metrics mirror cache_info
    ci = session.cache_info()
    text = session.metrics.expose_text()
    assert validate_prometheus_text(text) > 0
    assert f"miner_cache_hits_total {ci.hits}" in text
    assert f"miner_cache_misses_total {ci.misses}" in text
    assert f"miner_cached_programs {ci.n_programs}" in text
    # per-phase and per-query latency histograms observed every pass
    n_phases = len(rep.phases) + len(rep2.phases)
    first_mode = rep.phases[0].mode
    assert f'miner_phase_seconds_count{{mode="{first_mode}"}}' in text
    counts = sum(
        int(float(line.rsplit(" ", 1)[1]))
        for line in text.splitlines()
        if line.startswith("miner_phase_seconds_count")
    )
    assert counts == n_phases
    assert 'miner_query_seconds_count{query="significant"} 2' in text
    # span timeline: one phase span per pass, nested sub-spans, valid JSON
    ct = session.tracer.to_chrome_trace()
    assert validate_chrome_trace(ct) > 0
    names = [e["name"] for e in ct["traceEvents"]]
    for p in rep.phases:
        assert f"phase:{p.mode}" in names
    assert sum(n.startswith("phase:") for n in names) == n_phases
    assert "dispatch" in names and "postprocess" in names
    assert "compile" in names and "reconstruct" in names
    assert names.count("query:SignificantPatternQuery") == 2


def test_session_untraced_has_no_trace():
    from repro.api import Dataset, MinerSession

    db, labels, _ = _problem(seed=2)
    ds = Dataset.from_dense(db, labels, name="obs")
    rep = MinerSession().mine(ds)
    assert all(p.trace is None for p in rep.phases)


def test_resolve_defaults_trace_cap():
    from repro.api import Dataset, RuntimeConfig

    db, labels, _ = _problem(seed=2)
    bucket = Dataset.from_dense(db, labels, name="obs").bucket
    cfg = RuntimeConfig(trace_period=4).resolve(bucket, 1)
    assert cfg.trace_period == 4
    assert cfg.trace_cap == DEFAULT_TRACE_CAP
    cfg = RuntimeConfig(trace_period=4, trace_cap=128).resolve(bucket, 1)
    assert cfg.trace_cap == 128
    cfg = RuntimeConfig().resolve(bucket, 1)
    assert cfg.trace_period == 0 and cfg.trace_cap == 0


def test_trace_period_joins_cache_key():
    """Traced and untraced sessions must not share compiled programs."""
    from repro.api import Dataset, MinerSession, RuntimeConfig

    db, labels, _ = _problem(seed=2)
    ds = Dataset.from_dense(db, labels, name="obs")
    session = MinerSession()
    session.run_phase(ds, "count", min_sup=3)
    misses0 = session.cache_info().misses
    traced = MinerSession(runtime=RuntimeConfig(trace_period=1, trace_cap=64))
    r1 = traced.runtime.resolve(ds.bucket, 1)
    r0 = session.runtime.resolve(ds.bucket, 1)
    assert r1 != r0  # distinct EngineConfigs -> distinct cache keys
    assert misses0 == 1


# ----------------------------------------------------------------- span layer
def test_span_tracer_nesting_and_export(tmp_path):
    tracer = SpanTracer()
    with tracer.span("outer", query="q1"):
        with tracer.span("inner"):
            pass
        with tracer.span("inner"):
            pass
    events = tracer.events()
    assert [e["name"] for e in events] == ["inner", "inner", "outer"]
    outer = events[-1]
    for inner in events[:2]:  # nested spans lie inside the outer interval
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"query": "q1"}
    path = tracer.save(str(tmp_path / "trace.json"))
    assert validate_chrome_trace(path) == 3
    tracer.clear()
    assert tracer.events() == []


def test_span_sets_args_before_it_closes():
    tracer = SpanTracer()
    with tracer.span("pvalues", n_records=3) as args:
        args["n_tested"] = 1
    (event,) = tracer.events()
    assert event["args"] == {"n_records": 3, "n_tested": 1}


def test_span_tracer_records_on_exception():
    tracer = SpanTracer()
    with pytest.raises(RuntimeError):
        with tracer.span("boom"):
            raise RuntimeError("x")
    assert [e["name"] for e in tracer.events()] == ["boom"]


def test_span_tracer_jax_profiler_bridge():
    """jax_profiler=True must degrade to plain recording, never raise."""
    tracer = SpanTracer(jax_profiler=True)
    with tracer.span("bridged"):
        pass
    assert len(tracer.events()) == 1


def test_span_ids_parents_and_one_request_per_run():
    """Every span of one `MinerSession.run` shares a request id; ids are
    unique and each parent is the enclosing span of the same query."""
    from repro.api import (ClosedFrequentQuery, Dataset, MinerSession,
                           SignificantPatternQuery)

    db, labels, _ = _problem(seed=2)
    ds = Dataset.from_dense(db, labels, name="obs")
    session = MinerSession()
    session.run(ds, SignificantPatternQuery(alpha=0.05, pipeline="fused23"))
    session.run(ds, SignificantPatternQuery(alpha=0.05, pipeline="three_phase"))
    session.run(ds, ClosedFrequentQuery(min_sup=5))
    events = session.tracer.events()
    assert validate_chrome_trace(session.tracer.to_chrome_trace()) == len(events)
    by_id = {e["id"]: e for e in events}
    assert len(by_id) == len(events)
    requests = sorted({e["request"] for e in events})
    assert requests == [1, 2, 3]
    for rid in requests:
        mine = [e for e in events if e["request"] == rid]
        roots = [e for e in mine if e["parent"] is None]
        assert [r["name"] for r in roots] == [mine[-1]["name"]]
        assert roots[0]["name"].startswith("query:")
        for e in mine:
            if e["parent"] is None:
                continue
            up = by_id[e["parent"]]
            assert up["request"] == rid
            assert up["ts"] <= e["ts"] <= e["ts"] + e["dur"] <= up["ts"] + up["dur"] + 1e-3
    parent = {e["id"]: by_id[e["parent"]]["name"] for e in events
              if e["parent"] is not None}
    leaves = {(e["name"], parent[e["id"]]) for e in events if e["id"] in parent}
    assert ("readback", "postprocess") in leaves
    for step in ("closure", "patterns"):
        assert (step, "reconstruct") in leaves
    assert ("pvalues", "reconstruct") in leaves  # significance queries only
    corrections = [e for e in events if e["name"] == "correction"]
    assert [e["request"] for e in corrections] == [1, 2, 2]  # three_phase: k, root
    assert {parent[e["id"]] for e in corrections} == {"query:SignificantPatternQuery"}


def test_span_retention_stays_at_the_cap():
    from repro.obs.span import SPAN_CAP

    tracer = SpanTracer()
    n = 100_000
    assert n > SPAN_CAP
    with tracer.request():
        for i in range(n):
            with tracer.span("s", i=i):
                pass
    events = tracer.events()
    assert len(events) == SPAN_CAP  # the newest, oldest first
    assert [e["args"]["i"] for e in events] == list(range(n - SPAN_CAP, n))
    assert events[-1]["id"] == n and {e["request"] for e in events} == {1}


def test_span_exception_keeps_structure():
    tracer = SpanTracer()
    with tracer.request() as rid:
        with tracer.span("outer"):
            with pytest.raises(ValueError):
                with tracer.span("inner", step=1):
                    raise ValueError("x")
            with tracer.span("after"):
                pass
    inner, after, outer = tracer.events()
    assert inner["args"] == {"step": 1, "error": "ValueError"}
    assert inner["parent"] == after["parent"] == outer["id"]
    assert outer["parent"] is None and "args" not in outer
    assert inner["request"] == after["request"] == outer["request"] == rid
    with tracer.span("outside"):
        pass
    last = tracer.events()[-1]
    assert last["parent"] is None and last["request"] is None


def test_bridged_span_names_stay_bare():
    """The profiler sees the span's name alone: readers match spans by exact
    name, and an encoded name (`closure#n=3#`) would not match."""
    tracer = SpanTracer(jax_profiler=True)
    seen = []

    class Recorder:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    tracer._annotation = Recorder
    with tracer.span("closure", n_records=3):
        with tracer.span("patterns"):
            pass
    assert seen == [("closure", {}), ("patterns", {})]
    assert tracer.events()[-1]["args"] == {"n_records": 3}


# -------------------------------------------------------------- device scopes
_SCOPE_SPEC = dict(n_items=24, n_transactions=60, density=0.15, n_pos=20,
                   n_planted=2, seed=0)


def _scope_runtime():
    from repro.api import RuntimeConfig

    return RuntimeConfig.from_engine_config(_cfg())


@pytest.fixture(scope="module")
def scope_reports():
    """A fused23 query on one device here and on four simulated devices in
    a subprocess (pytest's jax already holds one device)."""
    from hlo_scopes import fused23_report

    from repro.data.synthetic import SyntheticSpec, generate

    db, labels, _ = generate(SyntheticSpec(name="sub", **_SCOPE_SPEC))
    one = fused23_report(db, labels, _scope_runtime())
    four = run_subproc(dict(_SCOPE_SPEC, mode="scopes", n_devices=4,
                            expand_batch=8, stack_cap=2048, steal_max=32,
                            push_cap=128))
    return {1: one, 4: four}


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("mode", ["lamp1", "count2d"])
def test_superstep_loop_names_its_scopes(scope_reports, mode, n_devices):
    """Every working instruction of the superstep loop names expand, steal or
    sync in its op_name (bar jnp.cumsum's out-of-line reduce-windows, which
    JAX lowers without the caller's name stack), and the answer on four
    devices is bit-identical to one device's."""
    got = scope_reports[n_devices]["programs"][mode]
    assert got["unscoped"] == []
    assert got["scopes"] == ["expand", "steal", "sync"]  # no ring: no trace
    one, many = scope_reports[1], scope_reports[n_devices]
    for key in ("lambda", "k", "n_significant", "hist2d", "patterns"):
        assert many[key] == one[key], key


def test_trace_scope_only_with_the_ring():
    from hlo_scopes import loop_instructions, scope_of

    from repro.api import Dataset, MinerSession, RuntimeConfig

    db, labels, _ = _problem(seed=0)
    session = MinerSession(runtime=RuntimeConfig(trace_period=1, trace_cap=64))
    rep = session.run_phase(Dataset.from_dense(db, labels), "count", min_sup=3)
    text = rep.compiled.as_text()
    assert {scope_of(op) for _, _, op in loop_instructions(text)} >= {
        "expand", "steal", "sync", "trace"}


# -------------------------------------------------------------- metrics layer
def test_metrics_exposition_format():
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", "jobs processed")
    g = reg.gauge("queue_depth", "live queue depth")
    h = reg.histogram("latency_seconds", "op latency", buckets=(0.1, 1.0))
    lab = reg.counter("errors_total", "errors by kind", labels=("kind",))
    c.inc()
    c.inc(2)
    g.set(5)
    g.inc(-2)
    h.observe(0.05)
    h.observe(0.5)
    h.observe(30.0)
    lab.labels(kind="io").inc()
    lab.labels(kind='we"ird\\').inc(3)
    text = reg.expose_text()
    # jobs_total + queue_depth + 2 errors_total children + histogram's
    # (2 bounds + Inf + sum + count) = 9 samples
    assert validate_prometheus_text(text) == 9
    assert "jobs_total 3" in text
    assert "queue_depth 3" in text
    assert 'latency_seconds_bucket{le="0.1"} 1' in text
    assert 'latency_seconds_bucket{le="1"} 2' in text
    assert 'latency_seconds_bucket{le="+Inf"} 3' in text
    assert "latency_seconds_count 3" in text
    assert 'errors_total{kind="io"} 1' in text
    assert 'errors_total{kind="we\\"ird\\\\"} 3' in text


def test_metrics_semantics():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    # idempotent re-registration returns the same instrument
    assert reg.counter("c_total") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("c_total")
    lab = reg.histogram("h_seconds", labels=("op",))
    with pytest.raises(ValueError, match="expected labels"):
        lab.labels(wrong="x")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("0bad")
    h = reg.histogram("h2_seconds", buckets=(1.0, 0.1))  # sorted for you
    h.observe(0.5)  # > 0.1, <= 1.0
    assert h.cumulative_counts() == [0, 1, 1]


# ------------------------------------------------------------------ log layer
def test_jsonl_logger():
    buf = io.StringIO()
    log = JsonlLogger(buf, clock=lambda: 123.456)
    rec = log.event("phase", mode="count", wall_s=0.5, arr=np.arange(2))
    lines = buf.getvalue().splitlines()
    assert len(lines) == 1
    parsed = json.loads(lines[0])
    assert parsed["ts"] == 123.456
    assert parsed["event"] == "phase"
    assert parsed["mode"] == "count"
    assert parsed["arr"] == "[0 1]"  # non-JSON values stringified, not raised
    assert rec["mode"] == "count"


# ------------------------------------------------------------------ validators
def test_chrome_validator_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"events": []})
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0}]}
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(bad)
    bad = {"traceEvents": [{"name": "", "ph": "X", "ts": 0, "dur": 1}]}
    with pytest.raises(ValueError, match="name"):
        validate_chrome_trace(bad)


def test_prometheus_validator_rejects_malformed():
    with pytest.raises(ValueError, match="no preceding TYPE"):
        validate_prometheus_text("mystery_metric 1\n")
    with pytest.raises(ValueError, match="malformed sample"):
        validate_prometheus_text("# TYPE a counter\na 1 2 3\n")
    bad_hist = (
        "# TYPE h histogram\n"
        'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\nh_count 3\n'
    )
    with pytest.raises(ValueError, match="not cumulative"):
        validate_prometheus_text(bad_hist)
    no_inf = "# TYPE h histogram\n" 'h_bucket{le="1"} 1\n'
    with pytest.raises(ValueError, match=r"\+Inf"):
        validate_prometheus_text(no_inf)
