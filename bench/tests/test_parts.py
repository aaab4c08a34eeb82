"""The readers of the program's parts (bench/metrics/parts.py): device time
per superstep scope and host time per leaf span, on hand-made intervals and
on pieces of traces recorded on the chip with the engine's scope maps
(`bench/tools/record_scopes.py`), checked against a brute-force grid."""

import glob
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench.harness import tracefile
from bench.harness.layers import Readings, reader
from bench.harness.trace import DeviceTrace, Interval, op_kind, stable_module
from bench.metrics import parts

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = sorted(glob.glob(os.path.join(DATA, "*.scopes.json.gz")))
STEP_NS = 50  # grid resolution of the brute-force count

SCOPE_READERS = {  # reader -> scope
    "engine.expand_us_per_superstep.query": "expand",
    "engine.expand_us_per_superstep.job": "expand",
    "engine.steal_us_per_superstep.job": "steal",
    "engine.sync_us_per_superstep.job": "sync",
}
SPAN_READERS = {  # reader -> span
    "session.closure_ms_per_query": "closure",
    "session.closure_ms_per_job": "closure",
    "session.correction_ms_per_job": "correction",
}

PATH = 'op_name="jit(program)/shard_map/while/body/{}"'
PROGRAM_A = f"""HloModule jit_program, entry_computation_layout={{(s32[])->s32[]}}

%body (p: s32[]) -> s32[] {{
  %p = s32[] parameter(0)
  %fusion.1 = s32[] fusion(%p), kind=kLoop, calls=%f1, metadata={{{PATH.format("expand/and")}}}
  %psum.2 = s32[4]{{0}} all-reduce(%p), channel_id=1, metadata={{{PATH.format("steal/psum")}}}
  %reduce-window.3 = s32[16]{{0}} reduce-window(%p, %p), metadata={{op_name="reduce_window_sum"}}
  ROOT %add.4 = s32[] add(%p, %p), metadata={{{PATH.format("sync/add")}}}
}}

ENTRY %main (a: s32[]) -> s32[] {{
  ROOT %while.5 = s32[] while(%a), condition=%cond, body=%body, \
metadata={{op_name="jit(program)/while"}}
}}
"""
# a second program of the same module name: fusion.1 is sync's here,
# copy.9 is its alone and reduce-window.3 is not its
PROGRAM_B = "\n".join(
    line for line in PROGRAM_A.replace("expand/and", "sync/and").replace(
        "  ROOT %add.4", "  %copy.9 = s32[] copy(%p)\n  ROOT %add.4").splitlines()
    if "reduce-window.3" not in line)


def _program(text):
    return SimpleNamespace(as_text=lambda: text)


def _ops(module, scale=1):
    return [Interval("while.5", 0, 1000 * scale, module, "while"),
            Interval("fusion.1", 0, 100 * scale, module, "fusion"),
            Interval("psum.2", 100 * scale, 150 * scale, module, "all-reduce"),
            Interval("reduce-window.3", 150 * scale, 200 * scale, module, "reduce-window"),
            Interval("add.4", 200 * scale, 210 * scale, module, "add")]


def _readings(ops, phases, spans=(), n_requests=2, window=(0, 10_000)):
    tr = DeviceTrace(window, ops=ops, async_ops={c: [] for c in ops},
                     modules={c: [] for c in ops}, spans=list(spans))
    return Readings(trace=tr, n_requests=n_requests, phases=phases, n_items=1,
                    n_transactions=1, chips=len(ops), peaks={},
                    engine_modules=frozenset({"jit_program"}))


def _phase(text, supersteps):
    return SimpleNamespace(supersteps=supersteps, compiled=_program(text))


def test_program_scopes_reads_op_names():
    got = parts.program_scopes(PROGRAM_A)
    assert got["fusion.1"] == ("fusion", "expand")
    assert got["psum.2"] == ("all-reduce", "steal")
    assert got["reduce-window.3"] == ("reduce-window", None)
    assert got["add.4"] == ("add", "sync")
    assert got["while.5"] == ("while", None)
    assert got["p"] == ("parameter", None)


def test_scope_readers_on_hand_made_intervals():
    # two chips, the second twice as slow; one program; 4 supersteps
    r = _readings({0: _ops("jit_program(11)"), 1: _ops("jit_program(11)", 2)},
                  [_phase(PROGRAM_A, 3), _phase(PROGRAM_A, 1)])
    want = {"expand": 150 / 4, "steal": 75 / 4, "sync": 15 / 4}  # ns per step
    for name, scope in SCOPE_READERS.items():
        assert reader(name)(r) == pytest.approx(want[scope] / 1e3)
    by_scope = parts.scope_us(r)
    assert by_scope[None] == pytest.approx(75 / 4 / 1e3)  # the reduce-window
    # scopes plus the unscoped remainder are every op but the container
    assert sum(by_scope.values()) == pytest.approx((210 + 420) / 2 / 4 / 1e3)


def test_each_module_instance_takes_its_own_program():
    ops = {0: _ops("jit_program(11)")
           + [Interval("fusion.1", 300, 400, "jit_program(22)", "fusion"),
              Interval("copy.9", 400, 410, "jit_program(22)", "copy")]}
    r = _readings(ops, [_phase(PROGRAM_A, 1), _phase(PROGRAM_B, 1)])
    by_scope = parts.scope_us(r)
    assert by_scope["expand"] == pytest.approx(100 / 2 / 1e3)
    assert by_scope["sync"] == pytest.approx((10 + 100) / 2 / 1e3)
    assert by_scope[None] == pytest.approx((50 + 10) / 2 / 1e3)


def test_ops_are_clipped_to_the_window():
    r = _readings({0: _ops("jit_program(11)")}, [_phase(PROGRAM_A, 1)], window=(50, 10_000))
    assert reader("engine.expand_us_per_superstep.job")(r) == pytest.approx(50 / 1e3)


def test_span_readers_on_hand_made_intervals():
    spans = [Interval("closure", 100, 1_100_100), Interval("closure", 9_000, 12_000),
             Interval("correction", 0, 4_000_000), Interval("reconstruct", 0, 9_000)]
    r = _readings({0: []}, [], spans=spans, window=(0, 3_000_000))
    assert reader("session.closure_ms_per_query")(r) == pytest.approx(
        (1_100_000 + 3_000) / 1e6 / 2)
    assert reader("session.closure_ms_per_job")(r) == reader("session.closure_ms_per_query")(r)
    assert reader("session.correction_ms_per_job")(r) == pytest.approx(3.0 / 2)


def test_a_program_without_the_parts_reads_nothing():
    """The program before the scopes and spans: readers return None."""
    bare = PROGRAM_A.replace("/expand", "").replace("/steal", "").replace("/sync", "")
    spans = [Interval("reconstruct", 0, 9_000), Interval("postprocess", 0, 9_000)]
    for phases in ([SimpleNamespace(supersteps=4)], [_phase(bare, 4)]):
        r = _readings({0: _ops("jit_program(11)")}, phases, spans=spans)
        for name in list(SCOPE_READERS) + list(SPAN_READERS):
            assert reader(name)(r) is None, name


# ------------------------------------------------------------ recorded pieces
@pytest.fixture(params=RECORDED, ids=os.path.basename)
def recorded(request):
    return tracefile.load(request.param)


def test_both_cells_have_a_recorded_piece_with_scopes():
    names = {os.path.basename(p).split(".")[0] for p in RECORDED}
    assert {"hapmap_dom_20-serve", "mcf7-lamp23-4chip"} <= names


def _grid(tr, ivs):
    lo, hi = tr.window
    g = np.zeros((hi - lo) // STEP_NS + 1, dtype=bool)
    for iv in ivs:
        s, e = tr.clip(iv)
        if e > s:
            g[(s - lo) // STEP_NS:(e - lo) // STEP_NS] = True
    return g


def test_scopes_and_remainder_add_up_to_the_engine_op_time(recorded):
    tr, meta = recorded
    assert meta["device"]["platform"] == "tpu"
    programs = [{n: tuple(v) for n, v in prog.items()} for prog in meta["programs"]]
    engine = set(meta["engine_modules"])
    per_chip = parts.scope_ns(tr, engine, programs)
    for chip in tr.chips():
        ops = [op for op in tr.ops[chip]
               if stable_module(op.module) in engine and op_kind(op) != "container"]
        union = _grid(tr, ops).sum() * STEP_NS
        summed = sum(per_chip[chip].values())
        # ops of one chip's XLA Ops line do not overlap: their sum is their union
        assert abs(summed - union) <= 2 * STEP_NS * len(ops)
        assert per_chip[chip]["expand"] > 0.5 * summed
    if meta["chips"] > 1:
        assert all(per_chip[c]["steal"] > 0 and per_chip[c]["sync"] > 0 for c in tr.chips())
    whole = meta["parts"]
    assert whole["scoped_plus_unscoped_us"] == pytest.approx(whole["engine_op_us"], rel=0.01)
    assert whole["engine_op_us"] <= whole["engine_module_us"]
