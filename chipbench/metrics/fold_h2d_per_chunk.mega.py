"""Host-to-device copies of the fold's inputs per chunk: the program's
`fold.h2d` counter (each host array times the devices it lands on) over
its `chunks` counter, over the completed sweeps."""

from chipbench import harness, program_spans


def read(rec: harness.Record) -> float | None:
    return program_spans.per_chunk(rec, "fold.h2d")
