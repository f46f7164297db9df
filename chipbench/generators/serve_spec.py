"""Closed-loop sweeps of a symbolic spec file through the normal path.

One client runs sweeps back to back.  Each sweep is what ``python -m
repro.sweep run <spec> --shard 8 --design-chunk 32 --by-width`` runs: the
spec document (the configuration's ``spec``, read once at set-up) goes
through ``SymbolicSweepSpec.from_json(...).resolve()``, which resolves
every scenario name through ``scenarios.resolve``, and then through
``sweep.run_sharded`` under the configuration's plan on the default
device.  Before each sweep the program's memos are cleared through its
public hooks, the serve/ stream builder's among them, so each sweep
builds its scenarios' streams, its design table (the PPA kernel) and
every Algorithm-1 tuning again.  Cells are counted per finished chunk
through ``run_sharded``'s progress hook; a sweep that the window's end
cuts off contributes the chunks it finished inside it.

Set-up runs one whole sweep, which builds every program the window's
sweeps use, then clears the memos and moves every object left (the
imports, the compiled programs, the loaded spec) into the collector's
permanent generation, as a long-running service does after its
warm-up: the window's collections then scan what the window's sweeps
make, not the set-up's heap.  The window's collections and their
seconds go to the run's detail (``extra.gc``), with the process's CPU
seconds, page faults and context switches over the window
(``extra.rusage``), which tell a busy host from a slow program.  Every
sweep does the same work whatever the seed; the seed draws which sweep's
winners and DRAM transactions are compared, and the sample of cells.

The check compares against ``chipbench/reference``: every Algorithm-1
winner and its PPA of one sweep; its DRAM transactions at every
[scenario, design], which is where the streams' reuse distances act; and
a seeded sample of cells across every finished sweep.  Both are drawn as
the sweeps finish (``Workload.keep``), so the window holds one sweep's
results and the sample's cells, as a deployment that hands each result
on would, rather than every sweep's.  The reference's streams are built
by ``reference/lm_serve.py`` from the configuration file's published
widths.
"""

from __future__ import annotations

import gc
import json
import os
import resource
import time

import numpy as np

from chipbench import harness
from chipbench import reference as R
from chipbench.compare import ErrorTable
from chipbench.generators.mega import CELL_METRICS, WindowClosed, fold_bytes
from chipbench.reference import lm_serve as R_serve


RUSAGE_FIELDS = ("ru_utime", "ru_stime", "ru_minflt", "ru_majflt",
                 "ru_nvcsw", "ru_nivcsw")


def rusage_since(before) -> dict:
    """This process's CPU seconds, page faults and context switches since
    ``before`` (a ``resource.getrusage`` reading)."""
    now = resource.getrusage(resource.RUSAGE_SELF)
    return {f[3:]: getattr(now, f) - getattr(before, f)
            for f in RUSAGE_FIELDS}


class Workload:
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        self.t = ctx.cell.traffic
        self.rng_check = np.random.default_rng(ctx.seed)
        self.finished = 0                # sweeps finished in the window
        self.full = None                 # the sweep compared whole
        self.sample: list = [None] * int(self.t["check_cells"])
        self.started = 0
        self.errors: list[str] = []

    def resolve(self):
        from repro.core import sweep

        return sweep.SymbolicSweepSpec.from_json(self.doc).resolve()

    def setup(self) -> None:
        from repro.core import sweep

        cfg = self.ctx.cell.config
        p = cfg["plan"]
        self.plan = sweep.ShardPlan(scenario_chunk=p["scenario_chunk"],
                                    design_chunk=p["design_chunk"],
                                    by_width=p["by_width"])
        with open(os.path.join(harness.ROOT, cfg["spec"])) as f:
            self.doc = json.load(f)
        self.clear_memos()
        spec = self.resolve()
        sweep.run_sharded(spec, self.plan)
        extra = self.ctx.record.extra
        extra["cells_per_sweep"] = sweep.n_cells(spec)
        extra["fold_bytes_per_sweep"] = fold_bytes(spec)
        del spec
        self.clear_memos()
        gc.collect()
        gc.freeze()

    @staticmethod
    def clear_memos() -> None:
        """Drop every memo a sweep fills (serve/ streams, design tables
        with their Algorithm-1 winners, sweep results, workload tables)."""
        from repro import scenarios
        from repro.core import engine, sweep, workload_engine

        scenarios.serve_traffic.cache_clear()
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

        gc_stats = rec.extra["gc"] = {"collections": [0, 0, 0],
                                      "seconds": 0.0}
        gc_start = [0.0]

        def collected(phase, info):
            now = time.perf_counter()
            if phase == "start":
                gc_start[0] = now
            else:
                gc_stats["collections"][info["generation"]] += 1
                gc_stats["seconds"] += now - gc_start[0]

        sweep_s = rec.extra["sweep_s"] = []     # per finished sweep, detail
        usage = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(collected)
        try:
            while time.perf_counter() < end:
                t0 = time.perf_counter()
                self.clear_memos()
                self.started += 1
                try:
                    result = sweep.run_sharded(self.resolve(), self.plan,
                                               progress=progress)
                except WindowClosed:
                    break
                except Exception as e:  # noqa: BLE001 - reported as a failure
                    self.errors.append(f"{type(e).__name__}: {e}")
                    continue
                sweep_s.append(time.perf_counter() - t0)
                self.keep(result)
                rec.sweeps += 1
        finally:
            gc.callbacks.remove(collected)
            rec.extra["rusage"] = rusage_since(usage)
        rec.extra["sweeps_started"] = self.started
        rec.extra["errors"] = self.errors

    def keep(self, result) -> None:
        """Keep what the check reads of one finished sweep, and let the
        sweep go.  The sweep compared whole replaces the kept one with
        probability 1/n at the n-th finished sweep, and each of the
        sample's draws likewise takes a cell of this sweep, so every
        draw is uniform over the cells of every finished sweep, as if
        drawn after the window from all of them; the window holds one
        sweep's results, not every sweep's."""
        self.finished += 1
        n = self.finished
        rng = self.rng_check
        if rng.random() * n < 1:
            self.full = result
        take = np.flatnonzero(rng.random(len(self.sample)) * n < 1)
        if not take.size:
            return
        tensors = {f: result.metric(m, include_dram=False)
                   for f, m in CELL_METRICS}
        spec = result.spec
        for j in take:
            pi = int(rng.integers(len(spec.platforms)))
            si = int(rng.integers(len(spec.scenarios)))
            di = int(rng.integers(len(spec.designs)))
            self.sample[j] = (spec.platforms[pi].name, spec.scenarios[si],
                              spec.designs[di],
                              {f: float(t[pi, si, di])
                               for f, t in tensors.items()})

    def close(self) -> None:
        self.clear_memos()
        gc.unfreeze()

    def counts(self) -> tuple[int, int]:
        return self.started, len(self.errors)

    def check(self) -> list[harness.Check]:
        lim = self.t["limits"]
        cfg = self.ctx.cell.config
        errs = ErrorTable()
        stats_of: dict = {}

        def ref_stats(s):
            if s.workload not in stats_of:
                stats_of[s.workload] = R_serve.stats(cfg, s.workload)
            return stats_of[s.workload]

        full = self.full
        if full is not None:
            # every Algorithm-1 winner and tuned PPA of one sweep, and its
            # DRAM transactions at every [scenario, design]
            for p, got in zip(full.spec.designs, full.designs):
                want = R.design(p.mem, p.capacity_bytes, p.node.name)
                errs.winner(f"{p.mem}@{p.capacity_mb:g}MB@{p.node.name}",
                            got.org, want.org)
                for field in R.DESIGN_FIELDS:
                    errs.add(field, getattr(got, field),
                             getattr(want, field))
            dram_tx = full.tables[0].dram_tx
            for si, s in enumerate(full.spec.scenarios):
                ref = ref_stats(s)
                for di, p in enumerate(full.spec.designs):
                    errs.add("dram_tx", dram_tx[si, di],
                             ref.dram_tx(p.capacity_bytes))
            # the seeded sample of cells across every finished sweep
            for platform, scenario, p, got in self.sample:
                ref = R.cell(ref_stats(scenario), p.mem, p.capacity_bytes,
                             p.node.name, platform)
                for field, _ in CELL_METRICS:
                    errs.add(field, got[field], ref[field])
        self.ctx.record.extra["check"] = {"sweeps": self.finished,
                                          **errs.summary()}
        return [harness.Check("max_rel_err", errs.max_err(),
                              lim["max_rel_err"]),
                harness.Check("winners_differing",
                              len(errs.winners_differing), 0),
                harness.Check("failed_sweeps", len(self.errors), 0)]
