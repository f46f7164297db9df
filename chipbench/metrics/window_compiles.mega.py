"""Programs built inside the window (compiled, or read from the persistent
compile cache): JAX's backend-compile events between the window's start
and end.  Set-up warms every shape the traffic uses, so this reads 0."""

from chipbench import harness


def read(rec: harness.Record) -> float | None:
    if rec.compiles_before is None or rec.compiles_after is None:
        return None
    return float(rec.compiles_after["compiles"]
                 - rec.compiles_before["compiles"])
