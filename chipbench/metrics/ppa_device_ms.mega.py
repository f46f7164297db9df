"""Device ms of the PPA kernel (``engine._ppa_kernel``, summed over the
cell's chips) per sweep's worth of cells finished in the traced window."""

from chipbench import harness


def read(rec: harness.Record) -> float | None:
    sweeps = harness.sweeps_in_window(rec)
    if rec.trace is None or not sweeps:
        return None
    s = rec.trace["kernel_s"].get("_ppa_kernel")
    return None if s is None else s * 1e3 / sweeps
