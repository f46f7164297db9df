"""Host spans and counters of the sweep pipeline, on the profiler's clock.

    with tracing.span("pack", chunks=4):
        ...
    tracing.count("fold.h2d", 13)

Recording is on exactly while a JAX profiler trace is running
(``jax.profiler.trace`` / ``start_trace``), checked once when a span is
opened; there is no flag of its own.  Off, ``span`` returns one shared
no-op context manager and ``count`` returns at once, so an untraced run
pays a single check per call.

On, a span opens ``jax.profiler.TraceAnnotation("host:<name>")``, which
writes it into the trace beside the device's operations, and on exit
appends a :class:`SpanRecord` (name, id, parent and root id, start and
end ``perf_counter_ns``, self time, whether it exited by an exception)
to an in-memory store.  Spans nest per thread, so a worker thread keeps
its own stack.  A counter adds to the root of the innermost open span on
the calling thread; outside any span it is dropped.

``summary(root="sweep")`` reduces the store over the roots of that name
that closed normally: per span name its calls, total and self seconds,
and every counter's total.  ``reset()`` empties the store.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections.abc import Mapping

from jax.profiler import TraceAnnotation

_recording = TraceAnnotation.is_enabled


class _Off:
    """The span handed out while nothing is recording."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


OFF = _Off()


class Recorder:
    """The span store: closed spans and per-root counters, shared by every
    thread; the stacks of open spans are per thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._spans: list[SpanRecord] = []
        self._counters: dict[tuple[int, str], int] = {}

    def stack(self) -> list[SpanRecord]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def next_id(self) -> int:
        with self._lock:
            return next(self._ids)

    def add(self, record: SpanRecord) -> None:
        with self._lock:
            self._spans.append(record)

    def count(self, name: str, n: int) -> None:
        stack = self.stack()
        if not stack:
            return
        key = (stack[-1].root_id, name)
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def reset(self) -> None:
        with self._lock:
            self._spans = []
            self._counters = {}

    def snapshot(self) -> tuple[list[SpanRecord], dict[tuple[int, str], int]]:
        with self._lock:
            return list(self._spans), dict(self._counters)

    def summary(self, root: str = "sweep") -> dict:
        spans, counters = self.snapshot()
        roots = {r.id for r in spans
                 if r.parent_id is None and r.name == root and not r.error}
        per: dict[str, dict] = {}
        for r in spans:
            if r.root_id not in roots:
                continue
            s = per.setdefault(r.name, {"calls": 0, "total_s": 0.0,
                                        "self_s": 0.0})
            s["calls"] += 1
            s["total_s"] += r.duration_ns * 1e-9
            s["self_s"] += r.self_ns * 1e-9
        totals: dict[str, int] = {}
        for (root_id, name), n in counters.items():
            if root_id in roots:
                totals[name] = totals.get(name, 0) + n
        return {"root": root, "roots": len(roots), "spans": per,
                "counters": totals}


class SpanRecord:
    """One span: open while its ``with`` block runs, then kept in the store
    as its record.  Times are ``time.perf_counter_ns()``; ``self_ns`` is
    the duration less the durations of its direct children."""

    __slots__ = ("name", "attrs", "id", "parent_id", "root_id", "start_ns",
                 "end_ns", "self_ns", "error", "_annotation", "_parent",
                 "_child_ns")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns

    def __enter__(self) -> SpanRecord:
        self.start_ns = time.perf_counter_ns()
        self._annotation = TraceAnnotation(f"host:{self.name}")
        self._annotation.__enter__()
        stack = _RECORDER.stack()
        parent = self._parent = stack[-1] if stack else None
        self.id = _RECORDER.next_id()
        if parent is None:
            self.parent_id, self.root_id = None, self.id
        else:
            self.parent_id, self.root_id = parent.id, parent.root_id
        self._child_ns = 0
        stack.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        _RECORDER.stack().pop()
        self.error = exc_type is not None
        self._annotation.__exit__(exc_type, exc, tb)
        self.end_ns = time.perf_counter_ns()
        duration = self.end_ns - self.start_ns
        self.self_ns = duration - self._child_ns
        if self._parent is not None:
            self._parent._child_ns += duration
        _RECORDER.add(self)
        return False


_RECORDER = Recorder()


def enabled() -> bool:
    """True while a profiler trace is running, so spans would record."""
    return _recording()


def span(name: str, **attrs):
    """A context manager timing one host span named ``name``; ``attrs``
    (such as a chunk's name) are kept with its record."""
    if not _recording():
        return OFF
    return SpanRecord(name, attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span's root."""
    if not _recording():
        return
    _RECORDER.count(name, n)


def summary(root: str = "sweep") -> dict:
    """``{"root", "roots", "spans": {name: {"calls", "total_s", "self_s"}},
    "counters": {name: total}}`` over the roots named ``root`` that closed
    without an exception."""
    return _RECORDER.summary(root)


def records() -> list[SpanRecord]:
    """Every closed span recorded since the last ``reset``, in closing
    order."""
    return _RECORDER.snapshot()[0]


def reset() -> None:
    _RECORDER.reset()


def table(s: Mapping) -> str:
    """``summary()`` as a text table, spans by self time."""
    rows = sorted(s["spans"].items(), key=lambda kv: -kv[1]["self_s"])
    lines = [f"{s['roots']} {s['root']} root(s); self time by span",
             f"  {'span':<16}{'calls':>8}{'total_s':>12}{'self_s':>12}"]
    lines += [f"  {name:<16}{v['calls']:>8}{v['total_s']:>12.4f}"
              f"{v['self_s']:>12.4f}" for name, v in rows]
    lines += [f"  counter {name}: {n}"
              for name, n in sorted(s["counters"].items())]
    return "\n".join(lines)
