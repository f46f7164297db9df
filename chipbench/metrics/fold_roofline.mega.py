"""The fold kernel's share of its HBM roofline, in %: the logical bytes of
the sweeps' real, unpadded fold inputs and outputs (``fold_bytes`` in
``generators/mega.py``, 8 bytes per float64) over one chip's HBM
bandwidth (``peaks.json``), divided by the fold's device time summed over
the cell's chips, its plain and its ``shard_map``'d form together.
Bytes-bound only: the v5e publishes no float64 peak."""

from chipbench import harness

FOLD_KERNELS = ("_fold", "body", "shmap_body")


def read(rec: harness.Record) -> float | None:
    sweeps = harness.sweeps_in_window(rec)
    if rec.trace is None or not sweeps or rec.peaks is None:
        return None
    kernel_s = sum(rec.trace["kernel_s"].get(k, 0.0) for k in FOLD_KERNELS)
    if kernel_s <= 0:
        return None
    nbytes = rec.extra["fold_bytes_per_sweep"] * sweeps
    bound_s = nbytes / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * bound_s / kernel_s
