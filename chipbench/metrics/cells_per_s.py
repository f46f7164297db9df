"""Cells evaluated in the window over the window's seconds, counted by
finished chunk (``run_sharded``'s progress hook)."""

from chipbench import harness


def read(rec: harness.Record) -> float | None:
    if not rec.chunks:
        return None
    cells = sum(n for t, n in rec.chunks
                if rec.window_start <= t <= rec.window_end)
    return cells / rec.window_s
