"""The control: the cell's own timed path in float32, the precision below
the float64 its configuration states, must come out not correct.

The program has no float32 path of its own, and the plain reference is
Python floats, which have no float32 form; so the control switches the
program's 64-bit JAX contexts to 32 bits from outside the program
(``faulted_run.float32``), the step a later change could be tempted to
take on the TPU.

    python -m pytest chipbench/tests/test_control.py             # CPU
    python3 chipbench/tests/test_control.py CELL SECONDS SEED...  # chip

On the chip it runs the cell at its own size and rate, once per seed, in
one process, and prints each run's compared numbers.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench.tests import faulted_run  # noqa: E402

CELLS = [(w["name"], w["chips"])
         for w in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell,chips", CELLS)
def test_float32_control_is_not_correct(cell, chips):
    result = faulted_run.run(cell, "float32", chips)
    assert result["correct"] is False, result["checks"]
    err = result["checks"]["max_rel_err"]
    assert err["value"] > err["limit"]


def main(argv: list[str]) -> int:
    cell, seconds, seeds = argv[0], float(argv[1]), argv[2:]
    for seed in seeds:
        with faulted_run.float32():
            result, _ = harness.run_cell(cell, int(seed), seconds, False)
        print(json.dumps({"cell": cell, "seed": int(seed),
                          "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
