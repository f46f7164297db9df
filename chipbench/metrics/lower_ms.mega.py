"""Host ms per completed sweep in lowering the design points: the self time
of the program's `lower`, `ppa.dispatch` and `ppa.fetch` spans (the PPA
kernel's call and the copy of its outputs), Algorithm 1 left out."""

from chipbench import harness, program_spans


def read(rec: harness.Record) -> float | None:
    return program_spans.self_ms_per_sweep(rec, ("lower", "ppa.dispatch",
                                                  "ppa.fetch"))
