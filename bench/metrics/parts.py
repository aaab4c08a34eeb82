"""Readings of the program's parts: the device time of each named scope of
the engine's superstep, and the host time of each of its leaf spans.

The engine runs each part of its superstep under a `jax.named_scope`
(`expand`, `steal`, `sync`, `trace`), so the optimized HLO of its compiled
programs names the part in each instruction's metadata `op_name`.  A
program reports the executable that ran each pass (`PhaseReport.compiled`);
its HLO text maps instruction name -> (opcode, scope).  A device trace's op
is charged to the scope its own instruction names (for a fusion, its
root's), and to none where it names none (ops XLA makes, jnp.cumsum's
reduce-windows, which JAX lowers without the caller's scope): that is the
unscoped remainder.  Where several programs ran in one module name, each
module instance (`jit_program(<id>)`) takes the program whose instructions
match most of its ops by name and opcode; an op the tied programs scope
differently stays unscoped.

A program that reports no executable, or whose HLO names no scope, gives
no reading (the reader returns None).
"""

from __future__ import annotations

import re
import weakref
from collections import defaultdict

import numpy as np

from bench.harness.trace import op_kind, opcode, stable_module

SCOPES = ("expand", "steal", "sync", "trace")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%\S+ = .*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> str | None:
    parts = op_name.split("/")
    return next((s for s in SCOPES if s in parts), None)


def program_scopes(hlo_text: str) -> dict[str, tuple[str, str | None]]:
    """instruction name -> (opcode, scope) of one optimized HLO module."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name = _OP_NAME.search(line)
            out[m.group(1).split(" = ", 1)[0].lstrip("%")] = (
                opcode(m.group(1)), scope_of(name.group(1)) if name else None)
    return out


def phase_programs(phases) -> list[dict]:
    """The scope maps of the distinct executables that ran `phases`."""
    texts = {}
    for p in phases:
        compiled = getattr(p, "compiled", None)
        if compiled is not None and id(compiled) not in texts:
            texts[id(compiled)] = compiled.as_text()
    maps = [program_scopes(t) for t in texts.values()]
    return [m for m in maps if any(s is not None for _, s in m.values())]


def scope_ns(trace, engine_modules, programs) -> dict[int, dict]:
    """chip -> {scope (None: unscoped): device ns} over the engine's ops in
    the window, containers (while, conditional, call) left out."""
    ops = defaultdict(list)  # module instance -> ops
    for chip in trace.chips():
        for op in trace.ops[chip]:
            if stable_module(op.module) in engine_modules and op_kind(op) != "container":
                ops[op.module].append((chip, op))
    out = {chip: defaultdict(int) for chip in trace.chips()}
    for module, chip_ops in ops.items():
        keys = {(op.name, op.opcode) for _, op in chip_ops}
        score = [sum(prog.get(n, ("",))[0] == o for n, o in keys) for prog in programs]
        owners = [prog for prog, s in zip(programs, score) if s == max(score)]
        for chip, op in chip_ops:
            found = {prog.get(op.name, ("", None))[1] for prog in owners}
            s, e = trace.clip(op)
            out[chip][found.pop() if len(found) == 1 else None] += max(0, e - s)
    return out


#: (weak reference to the readings, their reading) of the last call: the
#: scope readers of one run share one pass over the trace
_last: list = [lambda: None, None]


def scope_us(r) -> dict | None:
    """{scope (None: unscoped): us per superstep, mean over chips}, or None."""
    if _last[0]() is r:
        return _last[1]
    steps = sum(p.supersteps for p in r.phases)
    programs = phase_programs(r.phases)
    chips = r.trace.chips()
    out = None
    if steps and programs and chips and r.engine_modules:
        per_chip = scope_ns(r.trace, r.engine_modules, programs)
        keys = {k for d in per_chip.values() for k in d}
        out = {k: float(np.mean([per_chip[c].get(k, 0) for c in chips])) / 1e3 / steps
               for k in keys}
    _last[:] = [weakref.ref(r), out]
    return out


def scope_us_per_superstep(r, scope: str) -> float | None:
    by_scope = scope_us(r)
    return by_scope.get(scope, 0.0) if by_scope is not None else None


def span_ms_per_request(r, name: str) -> float | None:
    """Summed time of the program's spans named `name`, clipped to the window,
    per completed request; None where the program has no such span."""
    spans = [r.trace.clip(iv) for iv in r.trace.spans if iv.name == name]
    if not r.n_requests or not spans:
        return None
    return sum(max(0, e - s) for s, e in spans) / 1e6 / r.n_requests
