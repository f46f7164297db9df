"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window."""

from chipbench import harness


def read(rec: harness.Record) -> float | None:
    if rec.trace is None:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])
