"""Drive one cell on the CPU with its timed path broken underneath.

    python3 chipbench/tests/faulted_run.py CELL FAULT

FAULT is ``none``, ``fold_answer``, ``winner`` or ``float32``.  Prints the
run's result line.  A cell that asks for n chips runs on n virtual CPU
devices, which the caller sets up with ``XLA_FLAGS`` (see ``run``).  The
faults are planted from here, never in the program's files:

- ``fold_answer``: the fold returns every runtime 1e-7 too large, where
  the cells are produced, on its plain and its ``shard_map``'d path;
- ``winner``: Algorithm 1 hands back the organization next to its winner
  for every STT design;
- ``float32``: the control, the program's 64-bit JAX contexts switched
  to 32 bits (the precision below the float64 the configuration states).
"""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**31 + 777
SECONDS = 3.0


@contextlib.contextmanager
def fold_answer():
    import jax

    from repro.core import workload_engine

    fold = workload_engine._fold

    def broken(*args):
        out = dict(fold(*args))
        out["runtime_s"] = out["runtime_s"] * (1 + 1e-7)
        return out

    workload_engine._fold = broken          # traced into the shard_map body
    workload_engine._fold_kernel = jax.jit(broken)
    yield


@contextlib.contextmanager
def winner():
    from repro.core import engine

    tuned_index = engine.DesignTable.tuned_index

    def broken(self, mem, capacity_bytes, node=None):
        best = tuned_index(self, mem, capacity_bytes, node)
        if mem != "stt":
            return best
        c = self.capacities_bytes.index(capacity_bytes)
        valid = [o for o in range(len(engine.ORGS)) if self.valid[c, o]]
        i = valid.index(best)
        return valid[i + 1] if i + 1 < len(valid) else valid[i - 1]

    engine.DesignTable.tuned_index = broken
    yield


@contextlib.contextmanager
def float32():
    import jax

    enable_x64 = jax.enable_x64
    jax.enable_x64 = lambda new_val=True: enable_x64(False)
    try:
        yield
    finally:
        jax.enable_x64 = enable_x64


FAULTS = {"none": contextlib.nullcontext, "fold_answer": fold_answer,
          "winner": winner, "float32": float32}


def small_traffic(cell: str) -> dict:
    """The cell's own traffic with a smaller check sample, so a CPU run
    holds it; the sweeps and the limits are the cell's."""
    from chipbench import harness

    bench = harness.load_benchmark()
    w = harness.find(bench["workloads"], cell, "workload")
    t = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                       f"{w['traffic']}.json"))
    t["check_cells"] = 48
    return t


def run(cell: str, fault: str, chips: int) -> dict:
    """The faulted run in a process of its own, on ``chips`` CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    out = subprocess.run([sys.executable, os.path.abspath(__file__), cell,
                          fault], env=env, capture_output=True, text=True,
                         timeout=600, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def main(cell: str, fault: str) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness

    t = small_traffic(cell)
    with FAULTS[fault]():
        result, _ = harness.run_cell(cell, SEED, SECONDS,
                                     False, check_device=False, traffic=t)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
