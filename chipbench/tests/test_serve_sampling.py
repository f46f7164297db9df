"""The serve/ cell's check draws its compared sweep and its cells as the
sweeps finish (``serve_spec.Workload.keep``): each draw must be uniform
over every finished sweep, as a draw after the window from all of them
is, and the window must hold no sweep but the one drawn.  Runs on the
CPU without JAX: the sweeps are stand-ins whose cells carry their
sweep's number.

    python -m pytest chipbench/tests/test_serve_sampling.py
"""

from __future__ import annotations

import types
import weakref

import numpy as np

from chipbench import harness
from chipbench.generators import serve_spec
from chipbench.generators.mega import CELL_METRICS

CELLS = 256


class FakeSweep:
    """A finished sweep of 1 platform x 3 scenarios x 4 designs whose
    every cell reads its sweep's number k."""

    def __init__(self, k: int):
        self.k = k
        self.spec = types.SimpleNamespace(
            platforms=[types.SimpleNamespace(name="tpu-v5e")],
            scenarios=[f"s{i}" for i in range(3)],
            designs=[f"d{j}" for j in range(4)])

    def metric(self, name, include_dram=False):
        return np.full((1, 3, 4), float(self.k))


def workload(seed: int) -> serve_spec.Workload:
    cell = types.SimpleNamespace(traffic={"check_cells": CELLS})
    ctx = harness.Context(cell, seed, harness.Record(cell="t",
                                                     t_process=0.0))
    return serve_spec.Workload(ctx)


def drawn(n_sweeps: int, seed: int) -> tuple[int, list[int]]:
    w = workload(seed)
    for k in range(n_sweeps):
        w.keep(FakeSweep(k))
    return w.full.k, [int(got[CELL_METRICS[0][0]])
                      for _, _, _, got in w.sample]


def test_every_draw_is_uniform_over_the_finished_sweeps():
    n, seeds = 10, 400
    full = np.zeros(n)
    cells = np.zeros(n)
    for seed in range(seeds):
        f, ks = drawn(n, 2**31 + seed)
        full[f] += 1
        cells += np.bincount(ks, minlength=n)
    # 102,400 cell draws: 10,240 per sweep expected, sd ~96
    assert np.all(np.abs(cells / (seeds * CELLS) * n - 1) < 0.05), cells
    # 400 sweep draws: 40 per sweep expected, sd ~6
    assert np.all(np.abs(full - seeds / n) < 25), full


def test_the_sample_is_full_and_repeats_with_its_seed():
    a = drawn(7, 2**31 + 1)
    assert len(a[1]) == CELLS and all(0 <= k < 7 for k in a[1])
    assert drawn(7, 2**31 + 1) == a
    assert drawn(7, 2**31 + 2) != a


def test_the_window_holds_only_the_drawn_sweep():
    w = workload(2**31 + 5)
    refs = []
    for k in range(50):
        s = FakeSweep(k)
        refs.append(weakref.ref(s))
        w.keep(s)
        del s
    alive = [r() for r in refs if r() is not None]
    assert alive == [w.full]
