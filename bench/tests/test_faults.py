"""A run whose timed path is broken underneath comes out not `correct`.

Each test plants one fault in the program, drives the rest of a run of a
cell cut to CPU size (the harness's look for a chip skipped), and checks
that the comparison with the reference catches it.  A sound run of the same
cell comes out correct.
"""

import dataclasses

import pytest
from small import lamp_cell, run, serve_cell

from repro.api.session import MinerSession


def _patch_run(monkeypatch, change):
    """Make `MinerSession.run` return `change(report, previous_report)`."""
    orig = MinerSession.run
    last = []

    def run_(self, dataset, query, **kw):
        report = orig(self, dataset, query, **kw)
        out = change(report, last[-1] if last else report)
        last.append(report)
        return out

    monkeypatch.setattr(MinerSession, "run", run_)


def _with_patterns(report, patterns):
    report.results.patterns = patterns
    return report


def altered(report, _):
    """One answer altered where it is produced."""
    p = report.results.patterns
    if p:
        p = [dataclasses.replace(p[0], support=p[0].support + 1)] + p[1:]
    return _with_patterns(report, p)


def altered_pvalue(report, _):
    p = report.results.patterns
    if p:
        p = [dataclasses.replace(p[0], pvalue=p[0].pvalue * (1 + 1e-3))] + p[1:]
    return _with_patterns(report, p)


def half_left_out(report, _):
    """Half of each answer left out."""
    return _with_patterns(report, report.results.patterns[::2])


def unchanged(report, previous):
    """A step that hands back its previous state: the last request's answer."""
    return previous


@pytest.mark.parametrize("trace", [False, True])
def test_sound_serve_run_is_correct(trace):
    result = run(serve_cell(), trace=trace)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"


def test_sound_lamp_run_on_four_devices_is_correct():
    result = run(lamp_cell())
    assert result["correct"], result["checks"]
    assert result["device"]["count"] == 4


@pytest.mark.parametrize("fault", [altered, half_left_out, unchanged])
def test_serve_fault_is_caught(monkeypatch, fault):
    _patch_run(monkeypatch, fault)
    result = run(serve_cell())
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("fault", [altered, altered_pvalue, half_left_out, unchanged])
def test_lamp_fault_is_caught(monkeypatch, fault):
    _patch_run(monkeypatch, fault)
    result = run(lamp_cell())
    assert not result["correct"], result["checks"]


def test_lamp_without_the_exchange_between_chips_is_caught(monkeypatch):
    """The psums across the 1x4 mesh left out: each chip keeps its own sums."""
    from repro.core import collectives

    monkeypatch.setattr(collectives, "psum", lambda x, axis_name=None: x)
    result = run(lamp_cell())
    assert not result["correct"], result["checks"]
