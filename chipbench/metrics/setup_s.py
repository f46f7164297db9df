"""Seconds from process start to the start of the window (the first due
request or chunk): imports, device, compile-cache reads or compiles,
data and warm-up."""

from chipbench import harness


def read(rec: harness.Record) -> float | None:
    return rec.window_start - rec.t_process
