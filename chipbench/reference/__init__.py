"""The plain reference: the DeepNVM++ scalar path, in pure Python floats.

A frozen copy of the program's scalar modules (device model ``mtj``,
``bitcell``, the ``cachemodel`` PPA equations, the Table II
``calibration`` fit, the scalar Algorithm-1 ``tuner`` loop, the CNN
``workloads`` and the ``traffic`` stream builder and energy fold), with
their imports pointed at one another.  Nothing here imports the program,
so a later change to the program cannot move what its answers are judged
against.  It never touches the device.

Inputs are symbolic: a scenario name, a design point (mem, capacity,
node name) and a platform name.  CNN scenarios are built here from the
layer tables.  LM scenarios (``lm/<arch>/<shape>``, keyed
``<arch>/<shape>``) are read from
``lm_streams.json``, a table of their access streams frozen from the
program's byte accounting on the CPU (its ``made_from`` says where), so
the fold of an LM cell is judged against streams the program did not
make in the run.
"""

from __future__ import annotations

import functools
import json
import math
import os

from . import calibration, tech, traffic, tuner, workloads
from .cachemodel import CacheModel

CELL_FIELDS = ("runtime_s", "dyn_j", "leak_j", "energy_j", "edp_js")
DESIGN_FIELDS = ("read_latency_s", "write_latency_s", "read_energy_j",
                 "write_energy_j", "leakage_w", "area_mm2")


@functools.cache
def design(mem: str, capacity_bytes: int, node_name: str):
    """Algorithm 1 on the scalar path at one (mem, capacity, node)."""
    node = tech.node(node_name)
    model = CacheModel(mem, node=node, calibration=calibration.get(mem, node))
    return tuner.tune_loop(model, capacity_bytes)


@functools.cache
def cnn_stats(name: str) -> traffic.TrafficStats:
    """``cnn/<workload>/<train|infer>@b<batch>`` from the layer tables."""
    kind, workload, stage_spec = name.split("/")
    stage, _, batch = stage_spec.partition("@b")
    if kind != "cnn" or stage not in ("train", "infer"):
        raise ValueError(f"not a CNN scenario: {name!r}")
    return traffic.build(workloads.get(workload), int(batch),
                         stage == "train")


LM_TABLE = os.path.join(os.path.dirname(__file__), "lm_streams.json")


@functools.cache
def lm_table() -> dict:
    with open(LM_TABLE) as f:
        return json.load(f)["scenarios"]


@functools.cache
def lm_stats(key: str) -> traffic.TrafficStats:
    """``<arch>/<shape>`` from the frozen LM stream table."""
    row = lm_table()[key]
    streams = tuple(
        traffic.AccessStream(label, nbytes, is_write,
                             math.inf if reuse is None else reuse, wb)
        for label, nbytes, is_write, reuse, wb in row["streams"])
    return traffic.TrafficStats(key, row["batch"], row["training"], streams,
                                row["macs_per_batch"])


def cell(stats: traffic.TrafficStats, mem: str, capacity_bytes: int,
         node_name: str, platform_name: str) -> dict[str, float]:
    """One cell's rows() metrics (DRAM excluded from energy and EDP, as
    the served rows report them)."""
    rep = traffic.energy(stats, design(mem, capacity_bytes, node_name),
                         tech.platform(platform_name))
    return {"runtime_s": rep.runtime_s, "dyn_j": rep.dyn_j,
            "leak_j": rep.leak_j, "energy_j": rep.total_j(False),
            "edp_js": rep.edp(False)}
