"""Host span tracer — nested timing spans with Chrome-trace export.

The device superstep trace (obs/trace.py) answers "where did the miners'
time go"; this module answers the same question for the host orchestration
around them: pack, compile, dispatch, readback, postprocess, the LAMP
correction between passes, and the result build (closure, pvalues,
patterns).  A `SpanTracer` is a context-manager factory::

    tracer = SpanTracer()
    with tracer.request():                 # one id for every span below
        with tracer.span("phase:count", mode="count"):
            with tracer.span("dispatch"):
                ...
    tracer.save("trace.json")          # open in ui.perfetto.dev / chrome://tracing

Spans record wall-clock complete events (Chrome trace ``ph: "X"``) with
microsecond timestamps relative to the tracer's epoch; nesting follows the
with-statement structure, which is exactly what the Chrome trace viewer's
flame layout expects on one thread track.  Each event also carries its own
``id``, its ``parent``'s id (the enclosing span on the same thread, None at
the top) and the ``request`` id of the enclosing `request()` block (None
outside one): `MinerSession.run` opens one per query, so every span of a
query shares its request id.

Retention is a ring: a tracer keeps the newest `SPAN_CAP` events and drops
the oldest, so a long-running service that never reads its session's
tracer holds a bounded timeline.  `MinerSession` owns a tracer by default
and wraps every phase of every query.

`jax_profiler=True` additionally enters a ``jax.profiler.TraceAnnotation``
per span, under the span's bare name, so when a device profile is being
captured (``jax.profiler.trace``) the host spans line up with the XLA device
timeline in the same viewer.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager

__all__ = ["SPAN_CAP", "SpanTracer"]

#: events one tracer keeps (the newest); a query records about a dozen
SPAN_CAP = 65536


class SpanTracer:
    """Collects nested wall-clock spans; exports Chrome-trace JSON."""

    def __init__(self, *, jax_profiler: bool = False):
        self.jax_profiler = jax_profiler
        self._events: deque[dict] = deque(maxlen=SPAN_CAP)
        self._epoch_ns = time.perf_counter_ns()
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._request_ids = itertools.count(1)
        self._local = threading.local()  # per thread: open spans, request id
        self._annotation = None
        if jax_profiler:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._epoch_ns) / 1e3

    def _open(self) -> list:
        stack = getattr(self._local, "open", None)
        if stack is None:
            stack = self._local.open = []
        return stack

    @contextmanager
    def request(self):
        """Give every span opened inside this block, on this thread, one new
        request id; yields the id."""
        rid = next(self._request_ids)  # one C call: atomic under the GIL
        outer = getattr(self._local, "request", None)
        self._local.request = rid
        try:
            yield rid
        finally:
            self._local.request = outer

    @contextmanager
    def span(self, name: str, **args):
        """Time a nested region; extra kwargs land in the event's args.

        Yields that args dict: keys set on it before the region ends (a
        count known only inside it) land in the event too."""
        sid = next(self._ids)
        stack = self._open()
        parent = stack[-1] if stack else None
        stack.append(sid)
        ann = self._annotation(name) if self._annotation is not None else None
        if ann is not None:
            ann.__enter__()
        t0 = self._now_us()
        error = None
        try:
            yield args
        except BaseException as e:
            error = type(e).__name__
            raise
        finally:
            t1 = self._now_us()
            if ann is not None:
                ann.__exit__(None, None, None)
            stack.pop()
            event = {
                "name": name,
                "ph": "X",
                "ts": t0,
                "dur": t1 - t0,
                "pid": os.getpid(),
                "tid": threading.get_ident() & 0xFFFF,
                "id": sid,
                "parent": parent,
                "request": getattr(self._local, "request", None),
            }
            if args or error:
                event["args"] = {k: _jsonable(v) for k, v in args.items()}
                if error:
                    event["args"]["error"] = error
            with self._lock:
                self._events.append(event)

    # ------------------------------------------------------------- export
    def events(self) -> list[dict]:
        """The retained events (at most SPAN_CAP, oldest first)."""
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def to_chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object (ts/dur in microseconds)."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, indent=1)
            f.write("\n")
        return path


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)
