"""Host ms per completed sweep in building the chunks' results and merging
them: the self time of the program's `assemble` spans (per-platform
tables, design-table slices) and `merge` spans (each part's scatter and
the final table build)."""

from chipbench import harness, program_spans


def read(rec: harness.Record) -> float | None:
    return program_spans.self_ms_per_sweep(rec, ("assemble", "merge"))
