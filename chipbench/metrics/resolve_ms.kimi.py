"""Host ms per completed sweep in resolving the spec: the total time of the
program's `resolve` root spans (``SymbolicSweepSpec.resolve``: scenario
names through ``scenarios.resolve``, the serve/ streams built under it
as `lm.streams` spans, design points, platforms).  Each sweep resolves
once, so this is the mean over the resolves that closed normally."""

from chipbench import harness


def read(rec: harness.Record) -> float | None:
    if rec.trace is None:
        return None
    try:
        from repro import tracing
    except ImportError:
        return None
    s = tracing.summary(root="resolve")
    if not s["roots"] or "resolve" not in s["spans"]:
        return None
    rec.extra["resolve_spans"] = s
    return s["spans"]["resolve"]["total_s"] * 1e3 / s["roots"]
