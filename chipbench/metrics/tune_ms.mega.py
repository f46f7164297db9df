"""Host ms per completed sweep in Algorithm 1: the self time of the
program's `tune` spans (`DesignTable.tuned_index`, memo misses only)."""

from chipbench import harness, program_spans


def read(rec: harness.Record) -> float | None:
    return program_spans.self_ms_per_sweep(rec, ("tune",))
