"""Sharded mega-sweep benchmark: cells/sec of the chunked + shard_map
lowering vs chunk size and device count, recorded in
benchmarks/BENCH_shard.json.

Three measurements:

  chunk scan    the full mega spec (repro.scenarios.mega_spec, 1e5+
                cells) through ``run_sharded`` at several (scenario_chunk,
                design_chunk) plans — the knob that trades per-chunk
                compile/dispatch overhead against padded-SoA tensor area.
                The unsharded path is *not* a baseline here: at 182
                scenarios the global-width [s, d, k] fold intermediates
                are multi-GB, which is exactly what the sharded path
                exists to avoid.

  device scan   the same spec with ``ShardPlan(devices=N)`` over the
                first N of this process's devices, for each N in
                DEVICE_COUNTS that the process has.  Everything runs in
                this one process, which holds the devices; on the CPU,
                set ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
                before starting it to get four host devices.  Host
                scaling is bounded by physical cores.

  parity        sharded-vs-unsharded max relative error on the quick
                spec (small enough to evaluate unsharded), pinned 1e-12.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

JSON_PATH = "benchmarks/BENCH_shard.json"

CHUNK_PLANS = ((4, 16), (8, 32), (16, 96), (64, 288))
DEVICE_COUNTS = (1, 2, 4)

_FIELDS = ("dram_tx", "runtime_s", "runtime_nodram_s", "dyn_read_j",
           "dyn_write_j", "leak_j", "leak_nodram_j", "dram_j")


def _spec(quick: bool):
    from repro import scenarios
    return scenarios.mega_spec(quick=quick)


def _time_plan(spec, plan) -> dict:
    from repro.core import sweep
    t0 = time.perf_counter()
    result = sweep.run_sharded(spec, plan)
    dt = time.perf_counter() - t0
    assert len(result.spec.scenarios) == len(spec.scenarios)
    return {"scenario_chunk": plan.scenario_chunk,
            "design_chunk": plan.design_chunk,
            "devices": plan.devices,
            "n_chunks": len(sweep.split(spec, plan)),
            "seconds": dt,
            "cells_per_s": sweep.n_cells(spec) / dt}


def _parity(quick_spec) -> float:
    from repro.core import sweep
    base = sweep.run(quick_spec)
    res = sweep.run_sharded(
        quick_spec, sweep.ShardPlan(scenario_chunk=7, design_chunk=5,
                                    by_width=True))
    worst = 0.0
    for pi in range(len(quick_spec.platforms)):
        for f in _FIELDS:
            a = getattr(res.tables[pi], f)
            b = getattr(base.tables[pi], f)
            worst = max(worst, float(np.max(
                np.abs(a - b) / np.maximum(np.abs(b), 1e-300))))
    assert worst <= 1e-12, f"sharded parity broke the 1e-12 pin: {worst}"
    return worst


def _device_point(spec, devices: int) -> dict:
    """The spec on a mesh of the first ``devices`` devices, timed warm."""
    from repro.core import sweep
    plan = sweep.ShardPlan(scenario_chunk=8, design_chunk=32,
                           devices=devices, by_width=True)
    _time_plan(spec, plan)  # warm: jit + design-table lowering
    return _time_plan(spec, plan)


def run(quick: bool = False) -> dict:
    import jax

    from repro.core import sweep
    spec = _spec(quick)
    cells = sweep.n_cells(spec)

    plans = CHUNK_PLANS[1:2] if quick else CHUNK_PLANS
    chunk_scan = []
    for sc, dc in plans:
        plan = sweep.ShardPlan(scenario_chunk=min(sc, len(spec.scenarios)),
                               design_chunk=min(dc, len(spec.designs)),
                               by_width=True)
        chunk_scan.append(_time_plan(spec, plan))

    counts = DEVICE_COUNTS[:1] + DEVICE_COUNTS[-1:] if quick \
        else DEVICE_COUNTS
    device_scan = [_device_point(spec, n) for n in counts
                   if n <= len(jax.devices())]

    parity = _parity(_spec(quick=True))

    best = max(chunk_scan + device_scan, key=lambda r: r["cells_per_s"])
    result = dict(
        shard="chunked + shard_map sweep lowering",
        device=jax.devices()[0].device_kind,
        spec=spec.name, cells=cells,
        chunk_scan=chunk_scan, device_scan=device_scan,
        parity_max_rel_err=parity,
        best_cells_per_s=best["cells_per_s"])
    os.makedirs(os.path.dirname(JSON_PATH), exist_ok=True)
    with open(JSON_PATH, "w") as f:
        json.dump(result, f, indent=2)

    flat_rows = [dict(kind="chunk", **r) for r in chunk_scan] + \
        [dict(kind="device", **r) for r in device_scan]
    scale = (device_scan[-1]["cells_per_s"] / device_scan[0]["cells_per_s"]
             if device_scan else float("nan"))
    return {"rows": flat_rows,
            "bench": {"cells": cells,
                      "best_cells_per_s": best["cells_per_s"],
                      "device_scale_x": scale,
                      "parity_max_rel_err": parity},
            "derived": (f"cells={cells},"
                        f"best={best['cells_per_s']:,.0f}/s,"
                        f"dev{device_scan[0]['devices']}->"
                        f"{device_scan[-1]['devices']}={scale:.2f}x,"
                        f"parity_err={parity:.2e}")}


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    print(run(quick=args.quick)["derived"])


if __name__ == "__main__":
    main()
