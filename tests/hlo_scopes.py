"""The superstep scope each instruction of a compiled engine program names.

The engine wraps each part of its superstep in a `jax.named_scope`
(`expand`, `steal`, `sync`, and `trace` when the ring is on); the scope
lands as a component of the instruction's metadata `op_name`.  These
helpers read it back from `compiled.as_text()` for the tests.
"""

import re

SCOPES = ("expand", "steal", "sync", "trace")
#: instructions that only move values between computations or name them
BOOKKEEPING = frozenset({"parameter", "get-tuple-element", "tuple", "constant",
                         "copy", "bitcast"})
#: op_name components of code traced inside the superstep loop
_IN_LOOP = re.compile(r"(?:^|/)while/(?:body|cond)/")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
_OPCODE = re.compile(r"(?:^|[ )}])([a-z][a-z0-9_-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CONTROL = re.compile(
    r"(?:body|condition|true_computation|false_computation)=%([^,\s}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def scope_of(op_name: str) -> str | None:
    parts = op_name.split("/")
    return next((s for s in SCOPES if s in parts), None)


def computations(text: str) -> dict[str, list[tuple[str, str, str, str]]]:
    """computation -> [(name, opcode, op_name, line)] of its instructions."""
    out, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = out.setdefault(m.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if m and cur is not None:
            op = _OPCODE.search(m.group(2))
            name = _OP_NAME.search(line)
            cur.append((m.group(1), op.group(1) if op else "",
                        name.group(1) if name else "", line))
    return out


def loop_instructions(text: str) -> list[tuple[str, str, str]]:
    """(name, opcode, op_name) of every instruction the superstep loop runs:
    the while loops' bodies and conditions and the branches and calls they
    reach (fused computations are read through their fusion instruction)."""
    comps = computations(text)
    todo = [c for instrs in comps.values() for (_, opc, _, line) in instrs
            if opc == "while" for c in _CONTROL.findall(line)]
    seen, out = set(), []
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for name, opc, op_name, line in comps[c]:
            out.append((name, opc, op_name))
            todo += _CONTROL.findall(line)
            for group in _BRANCHES.findall(line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
            if opc == "call":
                todo += re.findall(r"to_apply=%([^,\s}]+)", line)
    return out


def unscoped(text: str) -> list[tuple[str, str, str]]:
    """Loop instructions traced inside the loop that do work but name no
    scope.  Left out: ops XLA moves into the loop from outside it (their
    op_name is the outer one), ops XLA makes without metadata, and
    jnp.cumsum's reduce-windows, which JAX lowers out of line with a fresh
    name stack (op_name `reduce_window_sum`)."""
    return [(n, opc, op) for n, opc, op in loop_instructions(text)
            if opc not in BOOKKEEPING and _IN_LOOP.search(op)
            and scope_of(op) is None]


def fused23_report(db, labels, runtime) -> dict:
    """One fused23 LAMP query (programs lamp1 and count2d) on this process's
    devices: its answer, and per program the scopes its loop names and the
    loop's unscoped working instructions."""
    from repro.api import Dataset, MinerSession, SignificantPatternQuery

    session = MinerSession(runtime=runtime)
    rep = session.run(Dataset.from_dense(db, labels),
                      SignificantPatternQuery(alpha=0.05, pipeline="fused23"))
    programs = {}
    for ph in rep.phases:
        text = ph.compiled.as_text()
        programs[ph.mode] = {
            "scopes": sorted({scope_of(op) for _, _, op in loop_instructions(text)}
                             - {None}),
            "unscoped": unscoped(text),
        }
    return {
        "lambda": rep.lambda_final, "k": rep.correction_factor,
        "n_significant": rep.n_significant,
        "hist2d": rep.phases[-1].output.hist2d.tolist(),
        "patterns": [[list(p.items), p.support, p.pos_support, p.pvalue]
                     for p in rep.results],
        "programs": programs,
    }
