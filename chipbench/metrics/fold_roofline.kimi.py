"""The fold kernel's share of its HBM roofline, in %: the logical bytes of
the sweeps' real, unpadded fold inputs and outputs (``fold_bytes`` in
``generators/mega.py``, 8 bytes per float64) over the chip's HBM
bandwidth (``peaks.json``), divided by the fold's device time.
Bytes-bound only: the v5e publishes no float64 peak."""

from chipbench import harness

FOLD_KERNEL = "_fold"


def read(rec: harness.Record) -> float | None:
    sweeps = harness.sweeps_in_window(rec)
    if rec.trace is None or not sweeps or rec.peaks is None:
        return None
    kernel_s = rec.trace["kernel_s"].get(FOLD_KERNEL, 0.0)
    if kernel_s <= 0:
        return None
    nbytes = rec.extra["fold_bytes_per_sweep"] * sweeps
    return 100.0 * nbytes / rec.peaks["hbm_bytes_per_s"] / kernel_s
