"""Run one benchmark cell and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic mix, generator and metric readers are
found by the names in ``BENCHMARK.json`` (see ``harness.py``).  Stdout's
last line is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each number compared beside its limit (also the last lines
of stderr).  Without a TPU, or with fewer chips than the cell asks for,
it prints no result and exits 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, summary = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_process=T_PROCESS)
    except harness.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    except harness.BenchmarkError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 3
    print(json.dumps({"summary": summary}), flush=True)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
