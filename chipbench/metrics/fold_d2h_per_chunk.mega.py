"""Device-to-host copies of the fold's outputs per chunk: the program's
`fold.d2h` counter (one per device buffer copied) over its `chunks`
counter, over the completed sweeps."""

from chipbench import harness, program_spans


def read(rec: harness.Record) -> float | None:
    return program_spans.per_chunk(rec, "fold.d2h")
