"""A profiler trace of the measured window, reduced to what the metrics read.

`Profile` runs `jax.profiler` around the traced window and reads the
`.xplane.pb` it writes with `jax.profiler.ProfileData`.  The window itself is
marked by a host annotation, `bench_window`, so the device's events and the
host's spans share one clock.  `DeviceTrace` keeps, per chip, every XLA op
with the module (jitted program) it ran in, and the host spans the program
annotates (`pack`, `dispatch`, `reconstruct`, ...).

Op names are made stable by dropping XLA's numeric suffixes (`fusion.203`
becomes `fusion`), so a breakdown names the same op from one build to the
next.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_MARK = "bench_window"
#: ops that only contain other ops: they overlap their bodies and are not
#: work of their own
CONTAINER_OPS = ("while", "conditional", "call")
#: collective ops as XLA names them on TPU (synchronous and async halves)
COLLECTIVE_OPS = ("all-reduce", "collective-permute", "all-gather", "all-to-all",
                  "reduce-scatter", "collective-broadcast")

_SUFFIX = re.compile(r"\.\d+")
_OPCODE = re.compile(r"(?:^|[ )}])([a-z][a-z0-9_-]*)\(")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def instruction(event_name: str) -> str:
    """`%fusion.203 = s32[1024]{0} fusion(...)` -> `fusion.203`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def opcode(event_name: str) -> str:
    """`%psum.3 = s32[4]{0} all-reduce(...), ...` -> `all-reduce`; the name when
    the event carries no HLO text."""
    head, _, rest = event_name.partition(" = ")
    m = _OPCODE.search(rest)
    return m.group(1) if m else stable_name(head.lstrip("%"))


def stable_name(name: str) -> str:
    """`fusion.203` -> `fusion`; `collective-permute-done.3` -> `collective-permute-done`."""
    return _SUFFIX.sub("", name)


def stable_module(name: str) -> str:
    """`jit_program(12)` -> `jit_program`."""
    return re.sub(r"\(\d+\)$", "", name)


def op_kind(op: "Interval") -> str:
    base = op.opcode or stable_name(op.name)
    for c in COLLECTIVE_OPS:
        if base.startswith(c):
            return "collective"
    if base in CONTAINER_OPS:
        return "container"
    return "compute"


@dataclass
class Interval:
    name: str
    start: int  # ns, on the profiler's clock
    end: int
    module: str = ""
    opcode: str = ""  # the HLO opcode, where the event names one


@dataclass
class DeviceTrace:
    window: tuple[int, int]
    ops: dict[int, list[Interval]] = field(default_factory=dict)      # chip -> ops
    async_ops: dict[int, list[Interval]] = field(default_factory=dict)  # DMA, async collectives
    modules: dict[int, list[Interval]] = field(default_factory=dict)  # chip -> programs
    spans: list[Interval] = field(default_factory=list)               # host annotations

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def chips(self) -> list[int]:
        return sorted(self.ops)

    def clip(self, iv: Interval) -> tuple[int, int]:
        return max(iv.start, self.window[0]), min(iv.end, self.window[1])


def union_ns(intervals) -> int:
    """Length of the union of (start, end) pairs."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(tr: DeviceTrace, chip: int) -> int:
    return union_ns(tr.clip(op) for op in tr.ops.get(chip, ()))


def idle_gaps(tr: DeviceTrace, chip: int) -> list[tuple[int, int]]:
    """The intervals of the window in which no op ran on `chip`."""
    gaps, t = [], tr.window[0]
    for s, e in merged(tr.clip(op) for op in tr.ops.get(chip, ())):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if tr.window[1] > t:
        gaps.append((t, tr.window[1]))
    return gaps


def overlap_ns(a, b) -> int:
    """Length of (union of intervals a) intersected with (union of intervals b)."""
    a, b = merged(a), merged(b)
    total, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def exposed_collective_ns(tr: DeviceTrace, chip: int) -> int:
    """Time a collective was in flight on `chip` (synchronous, or between an
    async start and its done) while no compute op ran."""
    coll, comp = [], []
    for op in list(tr.ops.get(chip, ())) + list(tr.async_ops.get(chip, ())):
        kind = op_kind(op)
        if kind == "collective":
            coll.append(tr.clip(op))
        elif kind == "compute":
            comp.append(tr.clip(op))
    return union_ns(coll) - overlap_ns(coll, comp)


def host_activity(tr: DeviceTrace, start: int, end: int, names=frozenset()) -> str:
    """The innermost host span named in `names` covering the middle of [start, end)."""
    mid = (start + end) // 2
    best = None
    for sp in tr.spans:
        if sp.name in names and sp.start <= mid < sp.end:
            if best is None or sp.end - sp.start < best.end - best.start:
                best = sp
    return best.name if best is not None else "between requests"


def breakdown(tr: DeviceTrace, kernel_ops=frozenset(), span_names=frozenset(),
              top: int = 10) -> dict:
    """The ops that took most device time (summed over chips) and the longest
    idle gaps (on any chip) with what the host was doing in each.

    Ops in `kernel_ops` (instruction names of the Pallas kernel) are named
    `pallas_call`; gaps are put down to the innermost of the program's own
    host spans (`span_names`) that covers them.
    """
    per_op: dict[str, int] = defaultdict(int)
    for chip in tr.chips():
        for op in tr.ops[chip]:
            if op_kind(op) == "container":
                continue
            s, e = tr.clip(op)
            if e > s:
                name = "pallas_call" if op.name in kernel_ops else stable_name(op.name)
                per_op[f"{stable_module(op.module)}/{name}"] += e - s
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for chip in tr.chips():
        gaps += idle_gaps(tr, chip)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": [[host_activity(tr, s, e, span_names), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }


# ------------------------------------------------------------- reading
def _containing(modules: list[Interval], t: int) -> str:
    """Module whose execution contains time t (modules sorted by start)."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid].start <= t:
            lo = mid + 1
        else:
            hi = mid
    i = lo - 1
    if i >= 0 and modules[i].start <= t < modules[i].end:
        return modules[i].name
    return ""


def read_xplane(path: str) -> DeviceTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict[int, list[Interval]] = {}
    async_ops: dict[int, list[Interval]] = {}
    modules: dict[int, list[Interval]] = {}
    spans: list[Interval] = []
    window = None
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[chip] = sorted(
                        (Interval(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                         for e in line.events), key=lambda iv: iv.start)
                elif line.name in ("XLA Ops", "Async XLA Ops"):
                    evs = [Interval(instruction(e.name), int(e.start_ns),
                                    int(e.start_ns + e.duration_ns), opcode=opcode(e.name))
                           for e in line.events]
                    (ops if line.name == "XLA Ops" else async_ops)[chip] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    iv = Interval(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    if e.name == WINDOW_MARK:
                        window = (iv.start, iv.end)
                    spans.append(iv)
    if window is None:
        raise RuntimeError(f"{path}: no {WINDOW_MARK} annotation in the trace")
    def inside(ivs):
        return [iv for iv in ivs if iv.end > window[0] and iv.start < window[1]]

    for table in (ops, async_ops):
        for chip, evs in table.items():
            mods = modules.get(chip, [])
            for op in evs:
                op.module = _containing(mods, op.start)
    return DeviceTrace(
        window,
        ops={c: inside(ivs) for c, ivs in ops.items()},
        async_ops={c: inside(ivs) for c, ivs in async_ops.items()},
        modules={c: inside(ivs) for c, ivs in modules.items()},
        spans=inside(spans),
    )


class Profile:
    """`with Profile() as p: ...` traces the block; `p.trace` holds the result."""

    def __init__(self):
        self.trace: DeviceTrace | None = None
        self.file_bytes = 0
        self.read_s = 0.0

    def __enter__(self):
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
        self._mark.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                files = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"), recursive=True)
                if not files:
                    raise RuntimeError("the profiler wrote no .xplane.pb")
                self.file_bytes = os.path.getsize(files[0])
                t = time.perf_counter()
                self.trace = read_xplane(files[0])
                self.read_s = time.perf_counter() - t
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False
