"""The program's own host spans and counters, as the per-layer readers see
them.

A traced run records, inside the program, spans around each host layer of
a sweep and counters of the host-device copies (``repro.tracing``; on only
while the profiler trace runs).  ``summary`` reads them over the sweeps
that completed in the window, and copies the per-span table into the run's
detail file (``rec.extra["program_spans"]``).  It returns None in an
untraced run, where the program has no ``repro.tracing``, or where no
sweep completed; the helpers below also return None where a span or
counter they need is absent, so a renamed span leaves its metric out and
never reads 0.
"""

from __future__ import annotations

from chipbench import harness

ROOT = "sweep"


def summary(rec: harness.Record) -> dict | None:
    if rec.trace is None:
        return None
    try:
        from repro import tracing
    except ImportError:
        return None
    s = tracing.summary(root=ROOT)
    if not s["roots"]:
        return None
    rec.extra["program_spans"] = s
    return s


def self_ms_per_sweep(rec: harness.Record, names: tuple[str, ...]
                      ) -> float | None:
    """Self ms of the spans ``names``, summed, per completed sweep."""
    s = summary(rec)
    if s is None or any(n not in s["spans"] for n in names):
        return None
    return sum(s["spans"][n]["self_s"] for n in names) * 1e3 / s["roots"]


def per_chunk(rec: harness.Record, counter: str) -> float | None:
    """Counter ``counter`` over the chunks of the completed sweeps."""
    s = summary(rec)
    if s is None:
        return None
    n, chunks = s["counters"].get(counter), s["counters"].get("chunks")
    if n is None or not chunks:
        return None
    return n / chunks
