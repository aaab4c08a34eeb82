"""Names the trace reduction needs from the program's compiled executables.

A session keeps its compiled phase programs; their optimized HLO gives the
module name of the engine's programs (`HloModule jit_program, ...`) and the
instruction names of the Pallas support-count kernel (instructions whose
metadata `op_name` ends in `pallas_call`).  Reading them from the
executables, rather than fixing them here, keeps the reduction right when
the program renames a function or XLA renumbers an instruction.
"""

from __future__ import annotations

import re

_MODULE = re.compile(r"^HloModule (\S+?),")
_PALLAS = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*op_name=\"[^\"]*pallas_call\"")


def compiled_programs(session) -> list:
    """The compiled phase programs a `MinerSession` holds."""
    with session._cache_lock:
        return [p.compiled for p in session._programs.values()]


def engine_names(sessions) -> tuple[frozenset, frozenset]:
    """(engine module names, Pallas kernel instruction names)."""
    modules, kernels = set(), set()
    for s in sessions:
        for compiled in compiled_programs(s):
            text = compiled.as_text()
            m = _MODULE.match(text)
            if m:
                modules.add(m.group(1))
            kernels.update(k.group(1) for k in map(_PALLAS.match, text.splitlines()) if k)
    return frozenset(modules), frozenset(kernels)
