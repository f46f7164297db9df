"""Closed-loop DTCO mega-sweeps through ``sweep.run_sharded``.

One client runs sweeps back to back.  Each sweep is what ``python -m
repro.sweep mega`` runs: the scenario and platform axes of
``scenarios.mega_spec()`` (every CNN workload x stage x ``MEGA_BATCHES``
batch, every supported LM arch x shape) crossed with the shipped
capacity ladder (the traffic's ``capacities_mb``, a copy of
``MEGA_CAPACITIES_MB``) x 3 memories x the 4 nodes, under the CLI's
``mega`` plan (on more than one chip, chunk groups are ``shard_map``'d
over a mesh of the cell's chips).  Before each sweep the program's memos
are cleared through its public hooks, as a fresh CLI process finds them,
so each sweep builds its design table (the PPA kernel) and runs every
Algorithm-1 tuning again.  Cells are counted per finished chunk through
``run_sharded``'s progress hook; a sweep that the window's end cuts off
contributes the chunks it finished inside it and is then abandoned.

Set-up runs one whole sweep, which builds every program the window's
sweeps use.  Every sweep does the same work whatever the seed; the seed
draws the sample of answers that the check compares.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import harness
from chipbench import reference as R
from chipbench.compare import ErrorTable

MEMS = ("sram", "stt", "sot")
CELL_METRICS = (("runtime_s", "runtime"), ("dyn_j", "dyn"),
                ("leak_j", "leak"), ("energy_j", "energy"),
                ("edp_js", "edp"))


class WindowClosed(Exception):
    """Raised from the progress hook once the window has ended."""


def fold_bytes(spec) -> int:
    """Logical bytes the fold must move for one whole sweep, independent of
    how it is chunked or padded: every real input read once and every
    output written once, 8 bytes per float64 and 1 per flag.

    Inputs: per access stream its bytes and reuse distance (float64) and
    its write and DRAM-visible flags; per scenario its MACs; per design its
    read/write latency, read/write energy, leakage and capacity; per
    platform its 4 parameters.  Outputs: per scenario the L2 read and
    write transactions; per (scenario, design) DRAM transactions and the
    read and write dynamic energy; per (platform, scenario, design)
    runtime, runtime without DRAM, leakage energy with and without DRAM,
    and DRAM energy."""
    s, d, p = len(spec.scenarios), len(spec.designs), len(spec.platforms)
    streams = sum(len(x.streams) for x in spec.scenarios)
    inputs = streams * (8 + 8 + 1 + 1) + s * 8 + d * 6 * 8 + p * 4 * 8
    outputs = (2 * s + 3 * s * d + 5 * p * s * d) * 8
    return inputs + outputs


class Workload:
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        self.t = ctx.cell.traffic
        self.rng_check = np.random.default_rng(ctx.seed)
        self.results: list = []
        self.started = 0
        self.errors: list[str] = []

    def setup(self) -> None:
        from repro import scenarios
        from repro.core import sweep, tech

        p = self.ctx.cell.config["plan"]
        # one chip keeps chunks on the default device, as the CLI does;
        # more shard_map chunk groups over a mesh of all of them
        chips = self.ctx.cell.chips
        self.plan = sweep.ShardPlan(scenario_chunk=p["scenario_chunk"],
                                    design_chunk=p["design_chunk"],
                                    devices=chips if chips > 1 else None,
                                    by_width=p["by_width"])
        base = scenarios.mega_spec()
        nodes = tuple(tech.node(n) for n in self.t["nodes"])
        self.spec = sweep.SweepSpec(
            name="mega", scenarios=base.scenarios,
            designs=sweep.design_grid(MEMS, tuple(self.t["capacities_mb"]),
                                      nodes=nodes),
            platforms=base.platforms)
        self.clear_memos()
        sweep.run_sharded(self.spec, self.plan)
        extra = self.ctx.record.extra
        extra["cells_per_sweep"] = sweep.n_cells(self.spec)
        extra["fold_bytes_per_sweep"] = fold_bytes(self.spec)

    @staticmethod
    def clear_memos() -> None:
        """Drop every memo a sweep fills (design tables with their
        Algorithm-1 winners, sweep results, workload tables)."""
        from repro.core import engine, sweep, workload_engine

        engine.design_table.cache_clear()
        sweep.clear_cache()
        workload_engine.clear_caches()

    def window(self) -> None:
        from repro.core import sweep

        rec = self.ctx.record
        end = rec.window_end

        def progress(i, total, part):
            now = time.perf_counter()
            if now > end:
                raise WindowClosed
            rec.chunks.append((now, sweep.n_cells(part.spec)))

        sweep_s = rec.extra["sweep_s"] = []     # per finished sweep, detail
        while time.perf_counter() < end:
            t0 = time.perf_counter()
            self.clear_memos()
            self.started += 1
            try:
                result = sweep.run_sharded(self.spec, self.plan,
                                           progress=progress)
            except WindowClosed:
                break
            except Exception as e:  # noqa: BLE001 - reported as a failure
                self.errors.append(f"{type(e).__name__}: {e}")
                continue
            self.results.append(result)
            sweep_s.append(time.perf_counter() - t0)
            rec.sweeps += 1
        rec.extra["sweeps_started"] = self.started
        rec.extra["errors"] = self.errors

    def close(self) -> None:
        self.clear_memos()

    def counts(self) -> tuple[int, int]:
        return self.started, len(self.errors)

    def check(self) -> list[harness.Check]:
        lim = self.t["limits"]
        errs = ErrorTable()
        stats_of = {}

        def ref_stats(s):
            key = (s.workload, s.batch, s.training)
            if key not in stats_of:
                if "/" in s.workload:      # LM: the frozen stream table
                    stats_of[key] = R.lm_stats(s.workload)
                else:
                    stage = "train" if s.training else "infer"
                    stats_of[key] = R.cnn_stats(
                        f"cnn/{s.workload}/{stage}@b{s.batch}")
            return stats_of[key]

        if self.results:
            # every Algorithm-1 winner and tuned PPA of one sweep
            full = self.results[int(self.rng_check.integers(
                len(self.results)))]
            for p, got in zip(full.spec.designs, full.designs):
                want = R.design(p.mem, p.capacity_bytes, p.node.name)
                errs.winner(f"{p.mem}@{p.capacity_mb:g}MB@{p.node.name}",
                            got.org, want.org)
                for field in R.DESIGN_FIELDS:
                    errs.add(field, getattr(got, field),
                             getattr(want, field))
            # a seeded sample of cells across every finished sweep
            tensors = [{f: res.metric(m, include_dram=False)
                        for f, m in CELL_METRICS} for res in self.results]
            for _ in range(int(self.t["check_cells"])):
                k = int(self.rng_check.integers(len(self.results)))
                res = self.results[k]
                pi = int(self.rng_check.integers(len(res.spec.platforms)))
                si = int(self.rng_check.integers(len(res.spec.scenarios)))
                di = int(self.rng_check.integers(len(res.spec.designs)))
                p = res.spec.designs[di]
                ref = R.cell(ref_stats(res.spec.scenarios[si]), p.mem,
                             p.capacity_bytes, p.node.name,
                             res.spec.platforms[pi].name)
                for field, _ in CELL_METRICS:
                    errs.add(field, tensors[k][field][pi, si, di],
                             ref[field])
        self.ctx.record.extra["check"] = {"sweeps": len(self.results),
                                          **errs.summary()}
        return [harness.Check("max_rel_err", errs.max_err(),
                              lim["max_rel_err"]),
                harness.Check("winners_differing",
                              len(errs.winners_differing), 0),
                harness.Check("failed_sweeps", len(self.errors), 0)]
