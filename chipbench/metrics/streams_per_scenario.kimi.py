"""Access streams built per scenario resolved: the program's `streams`
counter (streams the serve/ builder made, on memo misses) over its
`scenarios` counter (names ``SymbolicSweepSpec.resolve`` resolved), over
the `resolve` roots that closed normally."""

from chipbench import harness


def read(rec: harness.Record) -> float | None:
    if rec.trace is None:
        return None
    try:
        from repro import tracing
    except ImportError:
        return None
    counters = tracing.summary(root="resolve")["counters"]
    streams, scenarios = counters.get("streams"), counters.get("scenarios")
    if streams is None or not scenarios:
        return None
    return streams / scenarios
