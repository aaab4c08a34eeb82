"""A reduced device trace as a small JSON file, for the reduction's tests."""

from __future__ import annotations

import gzip
import json

from .trace import DeviceTrace, Interval


def crop(tr: DeviceTrace, start: int, end: int) -> DeviceTrace:
    """The part of `tr` inside [start, end) (ns), as a trace of that window."""
    def keep(ivs):
        return [Interval(iv.name, max(iv.start, start), min(iv.end, end), iv.module, iv.opcode)
                for iv in ivs if iv.end > start and iv.start < end]

    return DeviceTrace(
        (start, end),
        ops={c: keep(v) for c, v in tr.ops.items()},
        async_ops={c: keep(v) for c, v in tr.async_ops.items()},
        modules={c: keep(v) for c, v in tr.modules.items()},
        spans=keep(tr.spans),
    )


def _ivs(ivs):
    return [[iv.name, iv.start, iv.end, iv.module, iv.opcode] for iv in ivs]


def save(path: str, tr: DeviceTrace, **extra) -> None:
    doc = {
        "window": list(tr.window),
        "ops": {str(c): _ivs(v) for c, v in tr.ops.items()},
        "async_ops": {str(c): _ivs(v) for c, v in tr.async_ops.items()},
        "modules": {str(c): _ivs(v) for c, v in tr.modules.items()},
        "spans": _ivs(tr.spans),
        **extra,
    }
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def load(path: str) -> tuple[DeviceTrace, dict]:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)

    def ivs(rows):
        return [Interval(*row) for row in rows]

    tr = DeviceTrace(
        tuple(doc.pop("window")),
        ops={int(c): ivs(v) for c, v in doc.pop("ops").items()},
        async_ops={int(c): ivs(v) for c, v in doc.pop("async_ops").items()},
        modules={int(c): ivs(v) for c, v in doc.pop("modules").items()},
        spans=ivs(doc.pop("spans")),
    )
    return tr, doc
