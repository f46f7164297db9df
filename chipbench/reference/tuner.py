"""Algorithm 1 on the scalar path: one ``CacheModel.evaluate_scalar`` per
organization, then the min-EDAP nominee over every (optimization target,
access type) pool, in the order and with the tie-breaking of the paper's
loop."""

from __future__ import annotations

from collections.abc import Callable

from .cachemodel import ACCESS_TYPES, CacheDesign, CacheModel

OPT_TARGETS: dict[str, Callable[[CacheDesign], float]] = {
    "read_latency": lambda d: d.read_latency_s,
    "write_latency": lambda d: d.write_latency_s,
    "read_energy": lambda d: d.read_energy_j,
    "write_energy": lambda d: d.write_energy_j,
    "read_edp": lambda d: d.read_latency_s * d.read_energy_j,
    "write_edp": lambda d: d.write_latency_s * d.write_energy_j,
    "area": lambda d: d.area_mm2,
    "leakage": lambda d: d.leakage_w,
}


def tune_loop(model: CacheModel, capacity_bytes: int) -> CacheDesign:
    """Scalar Algorithm 1 for one (mem, capacity)."""
    designs = [model.evaluate_scalar(capacity_bytes, org)
               for org in model.design_space(capacity_bytes)]
    if not designs:
        raise ValueError(f"empty design space at {capacity_bytes} bytes")
    best: CacheDesign | None = None
    for metric in OPT_TARGETS.values():
        for access in ACCESS_TYPES:
            pool = [d for d in designs if d.org.access == access]
            nominee = min(pool, key=metric)
            if best is None or nominee.edap() < best.edap():
                best = nominee
    return best
