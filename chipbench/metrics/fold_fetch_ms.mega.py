"""Host ms per completed sweep in copying the finished fold's outputs to
the host: the self time of the program's `fold.fetch` spans."""

from chipbench import harness, program_spans


def read(rec: harness.Record) -> float | None:
    return program_spans.self_ms_per_sweep(rec, ("fold.fetch",))
