"""Host ms per completed sweep in packing the fold's inputs: the self time
of the program's `pack` spans (stream tensors, design vectors, platform
matrix, and on several chips the stacking of a chunk group)."""

from chipbench import harness, program_spans


def read(rec: harness.Record) -> float | None:
    return program_spans.self_ms_per_sweep(rec, ("pack",))
