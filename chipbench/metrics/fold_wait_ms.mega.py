"""Host ms per completed sweep spent waiting for the fold to finish on the
device before its outputs are copied: the self time of the program's
`fold.wait` spans."""

from chipbench import harness, program_spans


def read(rec: harness.Record) -> float | None:
    return program_spans.self_ms_per_sweep(rec, ("fold.wait",))
