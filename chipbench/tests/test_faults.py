"""Each cell, driven on the CPU with its timed path broken underneath
(``faulted_run.py``), comes out not correct; unbroken, it comes out
correct.  The cells carry no state across steps and exchange nothing
between chips (the sharded fold has no collectives), so an altered
answer is the fault they can have: an altered fold output, and an
altered Algorithm-1 winner."""

import pytest

from chipbench import harness
from chipbench.tests import faulted_run

CELLS = [(w["name"], w["chips"])
         for w in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell,chips", CELLS)
def test_sound_run_is_correct(cell, chips):
    result = faulted_run.run(cell, "none", chips)
    assert result["correct"] is True, result["checks"]


@pytest.mark.parametrize("fault", ["fold_answer", "winner"])
@pytest.mark.parametrize("cell,chips", CELLS)
def test_planted_fault_is_not_correct(cell, chips, fault):
    result = faulted_run.run(cell, fault, chips)
    assert result["correct"] is False, result["checks"]
