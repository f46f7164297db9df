"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (derived = the reproduced headline
quantities vs the paper's values) and writes detailed per-row CSVs to
runs/benchmarks/.

Every module run also **appends** one timestamped JSONL entry to
``benchmarks/BENCH_history.jsonl`` (schema ``deepnvm.bench/1``): the
perf-bench modules used to overwrite their ``BENCH_*.json`` with a single
latest sample, so the cross-PR perf trajectory was never recorded.  The
per-module headline metrics come from the optional ``bench`` key of a
module's ``run()`` result; modules without one still get their wall-clock
tracked.

``--only MODULE`` (repeatable, comma-separated) restricts the run — the
CI benchmark-smoke job runs ``--only fig3_4_isocap,lm_nvm,fig_dtco,fig_dtco_isoarea
--quick`` so analysis-layer regressions fail fast.  ``--quick`` is forwarded to
modules whose ``run`` accepts a ``quick`` keyword (reduced reps / arch
sets); the rest run unchanged.

Each module runs in a process of its own (``--module NAME``), and this
orchestrator never imports jax: a process that has touched jax holds the
device, and a module such as ``bench_serve`` starts children that need it.
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import subprocess
import sys
import time
from datetime import datetime, timezone

HISTORY_PATH = "benchmarks/BENCH_history.jsonl"
HISTORY_SCHEMA = "deepnvm.bench/1"

MODULES = (
    "table1_bitcell",
    "table2_cache",
    "fig3_4_isocap",
    "fig5_batch",
    "fig6_dram",
    "fig7_8_isoarea",
    "fig9_10_scaling",
    "fig_dtco",
    "fig_dtco_isoarea",
    "lm_nvm",
    "bench_engine",
    "bench_workload_engine",
    "bench_sweep",
    "bench_shard",
    "bench_serve",
    "bench_analysis",
    "bench_inverse",
    "fig_sensitivity",
)


def append_history(name: str, us_per_call: float, result: dict,
                   quick: bool, path: str = HISTORY_PATH) -> dict:
    """One appended trajectory entry per module run.  The schema is
    stable: fixed envelope keys, module-specific numbers confined to
    ``metrics`` (the module's ``bench`` dict)."""
    entry = {
        "schema": HISTORY_SCHEMA,
        "ts": datetime.now(timezone.utc).isoformat(timespec="seconds"),
        "module": name,
        "quick": quick,
        "us_per_call": round(us_per_call, 1),
        "metrics": result.get("bench", {}),
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(entry, sort_keys=True) + "\n")
    return entry


def select(only: list[str] | None) -> tuple[str, ...]:
    if not only:
        return MODULES
    wanted = [n for arg in only for n in arg.split(",") if n]
    unknown = sorted(set(wanted) - set(MODULES))
    if unknown:
        raise SystemExit(f"unknown benchmark module(s): {', '.join(unknown)}"
                         f" (choose from: {', '.join(MODULES)})")
    return tuple(n for n in MODULES if n in wanted)


def run_module(name: str, quick: bool) -> None:
    """One module in this process: its CSV line, history entry and rows."""
    mod = importlib.import_module(f"benchmarks.{name}")
    kwargs = {"quick": True} if quick and \
        "quick" in inspect.signature(mod.run).parameters else {}
    t0 = time.perf_counter()
    result = mod.run(**kwargs)
    dt_us = (time.perf_counter() - t0) * 1e6
    # imported after the run: it loads jax, and bench_serve starts its
    # children only from a process that has not
    from repro.core.report import write_csv

    derived = result.get("derived", "")
    print(f'{name},{dt_us:.0f},"{derived}"', flush=True)
    append_history(name, dt_us, result, quick)
    if result.get("rows"):
        write_csv(f"runs/benchmarks/{name}.csv", result["rows"])
    if result.get("ppa"):
        write_csv(f"runs/benchmarks/{name}_ppa.csv", result["ppa"])


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", action="append", metavar="MODULE",
                    help="run only this module (repeatable, comma-separated)")
    ap.add_argument("--quick", action="store_true",
                    help="reduced work where a module supports it")
    ap.add_argument("--module", choices=MODULES,
                    help="run this one module in this process")
    args = ap.parse_args(argv)
    if args.module:
        run_module(args.module, args.quick)
        return

    print("name,us_per_call,derived", flush=True)
    for name in select(args.only):
        subprocess.run([sys.executable, "-m", "benchmarks.run",
                        "--module", name] + (["--quick"] if args.quick
                                             else []), check=True)


if __name__ == "__main__":
    main()
