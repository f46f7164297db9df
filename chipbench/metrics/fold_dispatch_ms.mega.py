"""Host ms per completed sweep in calling the jitted fold, host-to-device
copies of its inputs included: the self time of the program's
`fold.dispatch` spans."""

from chipbench import harness, program_spans


def read(rec: harness.Record) -> float | None:
    return program_spans.self_ms_per_sweep(rec, ("fold.dispatch",))
