"""On-chip smoke test of the design-space sweep service's main path.

    python chip_smoke.py               # one TPU chip: every phase below
    python chip_smoke.py --four-chips  # four chips: the sharded mega-sweep
                                       # on a 4-device mesh vs one chip

Everything runs in this one process (a process that has touched JAX holds
the chip), and the first fault ends it with a non-zero exit.  Phases:

  a. device       a TPU must be present; there is no CPU branch.
  b. served path  a warmed ``SweepService`` behind ``SweepHTTPServer``
                  answers the five golden specs concurrently over HTTP,
                  a repeat from its result cache, and a stats op.
  c. mega-sweep   ``scenarios.mega_spec()`` (104,832 cells) through the
                  sharded lowering with the ``python -m repro.sweep mega``
                  plan, plus a seeded sample of its cells against the
                  scalar reference.
  d. inverse      ``inverse.solve`` on ``specs/inverse_isocap.json``.
  e. correctness  every served ``isocap`` and ``dtco_isoarea`` cell and
                  every Algorithm-1 winner against the pure-Python scalar
                  path (``CacheModel.evaluate_scalar`` + ``tuner.tune_loop``
                  + ``traffic.energy``, with its own host-side calibration
                  fit), and the Table II / iso-area paper anchors.

The JAX persistent compilation cache is on: ``$JAX_COMPILATION_CACHE_DIR``
where set, else ``.jax_cache/`` at the checkout root.  Each phase prints
its XLA compile seconds, so a second run shows the cache hitting.  The
last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

GOLDENS = ("isocap", "dtco", "dtco_isoarea", "lm_nvm", "mixed_cnn_lm")
# Worst relative error admitted against the float64 CPU-side reference.
TOL = 1e-6
CELL_FIELDS = ("runtime_s", "dyn_j", "leak_j", "energy_j", "edp_js")
DESIGN_FIELDS = ("read_latency_s", "write_latency_s", "read_energy_j",
                 "write_energy_j", "leakage_w", "area_mm2")
TABLE_FIELDS = ("l2_read_tx", "l2_write_tx", "dram_tx", "runtime_s",
                "runtime_nodram_s", "dyn_read_j", "dyn_write_j", "leak_j",
                "leak_nodram_j", "dram_j")
MEGA_CELLS = 104_832
MEGA_SAMPLE = 256


class Fault(Exception):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Fault(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got: float, want: float) -> float:
    return abs(got - want) / abs(want) if want else abs(got)


class CompileClock:
    """XLA compile seconds and persistent-cache hits, from JAX's own
    monitoring events (the backend compile span includes a cache read)."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1

    def snapshot(self) -> tuple[float, int]:
        with self._lock:
            return self.seconds, self.hits


class Phase:
    """Wall and compile seconds of one phase, printed when it ends."""

    def __init__(self, name: str, clock: CompileClock, kind: str):
        self.name, self.clock, self.kind = name, clock, kind

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0 = self.clock.snapshot()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            self.wall_s = time.perf_counter() - self.t0
            c1, h1 = self.clock.snapshot()
            self.compile_s = c1 - self.c0
            say(f"[{self.kind}] {self.name}: wall {self.wall_s!r} s, "
                f"compile {self.compile_s!r} s, cache hits {h1 - self.h0}")
        return False


# ---------------------------------------------------------------------------
# The scalar reference: pure Python, never on the device
# ---------------------------------------------------------------------------


@functools.cache
def reference_calibration(mem: str, node):
    """``calibration.get``'s fit, run on the scalar ``tuner.tune_loop``
    instead of the engine: the same Table II fixed point at 16 nm and the
    same derivation rule at the scaled nodes."""
    from repro.core import calibration, tech, tuner
    from repro.core.cachemodel import CacheModel

    if node != tech.TECH_16NM:
        anchor = reference_calibration(mem, tech.TECH_16NM)
        s = tech.scale_factor(node)
        return dataclasses.replace(
            anchor,
            peri_area_lin=anchor.peri_area_lin * s ** tech.PERI_AREA_EXP,
            peri_area_sqrt=anchor.peri_area_sqrt * s ** tech.PERI_AREA_EXP,
            leak_lin=anchor.leak_lin * s ** tech.PERI_LEAK_EXP,
            leak_sqrt=anchor.leak_sqrt * s ** tech.PERI_LEAK_EXP)
    base = calibration._BASE[mem]
    table2 = calibration.TABLE2[mem]
    cal = base
    for _ in range(2):
        d = tuner.tune_loop(CacheModel(mem, calibration=cal),
                            table2["cap"] * 2**20)
        cal = dataclasses.replace(
            base,
            k_read_lat=table2["rlat"] * 1e-9
            / (d.read_latency_s / cal.k_read_lat),
            k_write_lat=table2["wlat"] * 1e-9
            / (d.write_latency_s / cal.k_write_lat),
            k_read_e=table2["re"] * 1e-9 / (d.read_energy_j / cal.k_read_e),
            k_write_e=table2["we"] * 1e-9
            / (d.write_energy_j / cal.k_write_e))
    return cal


@functools.cache
def reference_design(mem: str, capacity_bytes: int, node):
    """Algorithm 1 on the scalar path."""
    from repro.core import tuner
    from repro.core.cachemodel import CacheModel

    model = CacheModel(mem, node=node,
                       calibration=reference_calibration(mem, node))
    return tuner.tune_loop(model, capacity_bytes)


def reference_cell(stats, point, platform) -> dict:
    """One cell's rows() metrics from ``traffic.energy``."""
    from repro.core import traffic

    rep = traffic.energy(stats, reference_design(
        point.mem, point.capacity_bytes, point.node), platform)
    return {"runtime_s": rep.runtime_s, "dyn_j": rep.dyn_j,
            "leak_j": rep.leak_j, "energy_j": rep.total_j(False),
            "edp_js": rep.edp(False), "dyn_read_j": rep.dyn_read_j,
            "dyn_write_j": rep.dyn_write_j, "dram_j": rep.dram_j}


class ErrorTable:
    """Worst relative error per field, and Algorithm-1 winner mismatches."""

    def __init__(self):
        self.worst: dict[str, float] = {}
        self.winners = 0
        self.winners_differing: list[str] = []

    def add(self, field: str, got: float, want: float) -> None:
        check(math.isfinite(got), f"{field}: non-finite value {got!r}")
        self.worst[field] = max(self.worst.get(field, 0.0),
                                rel_err(got, want))

    def winner(self, label: str, got_org, want_org) -> None:
        self.winners += 1
        if got_org != want_org:
            self.winners_differing.append(
                f"{label}: device {got_org} vs scalar {want_org}")

    def report(self, label: str) -> None:
        for field, err in sorted(self.worst.items()):
            say(f"  {label} worst rel err {field}: {err!r}")
        say(f"  {label} Algorithm-1 winners differing: "
            f"{len(self.winners_differing)} of {self.winners}")
        for line in self.winners_differing:
            say(f"    {line}")

    def gate(self, label: str) -> None:
        check(not self.winners_differing,
              f"{label}: {len(self.winners_differing)} Algorithm-1 "
              "winners differ from the scalar reference")
        field, err = max(self.worst.items(), key=lambda kv: kv[1])
        check(err <= TOL, f"{label}: worst rel err {err!r} ({field}) "
                          f"exceeds {TOL}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_device(expect: int) -> dict:
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    say(f"[a] device: platform {platform}, kind {devs[0].device_kind}, "
        f"count {len(devs)}, jax {jax.__version__}")
    if platform != "tpu":
        raise Fault(f"no TPU: JAX found {platform!r} devices")
    check(len(devs) >= expect,
          f"needs {expect} TPU chips, JAX found {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def phase_served(clock: CompileClock) -> dict:
    from concurrent.futures import ThreadPoolExecutor

    from repro.core.sweep import SymbolicSweepSpec, n_cells
    from repro.sweep import client
    from repro.sweep.service import SweepHTTPServer, SweepService

    paths = {n: os.path.join(ROOT, "specs", f"{n}.json") for n in GOLDENS}
    docs = {}
    for name, path in paths.items():
        with open(path) as f:
            docs[name] = json.load(f)
    svc = SweepService()
    server = SweepHTTPServer(("127.0.0.1", 0), svc)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        url = f"127.0.0.1:{server.server_address[1]}"
        with Phase("warmup, five golden specs", clock, "b"):
            info = svc.warmup(specs=list(paths.values()))
        say(f"  warmup: {info['fold_shapes']} fold shapes, "
            f"specs {info['specs']}")
        check(client.wait_ready(url, timeout=60.0), "server not ready")

        def ask(doc):
            return client.http_request(url, doc)

        with Phase("five golden specs, concurrent over HTTP", clock, "b"):
            with ThreadPoolExecutor(max_workers=len(GOLDENS)) as pool:
                answers = dict(zip(GOLDENS, pool.map(
                    ask, [{"spec": docs[n], "want": ["rows", "summary"]}
                          for n in GOLDENS])))
        for name, resp in answers.items():
            check(resp.get("ok") is True,
                  f"{name}: response not ok: {resp.get('error')}")
            want_rows = n_cells(SymbolicSweepSpec.from_json(
                docs[name]).resolve())
            check(len(resp["rows"]) == want_rows,
                  f"{name}: {len(resp['rows'])} rows, expected {want_rows}")
            check(isinstance(resp.get("summary"), dict) and resp["summary"],
                  f"{name}: no summary")
            for row in resp["rows"]:
                for field in CELL_FIELDS:
                    check(math.isfinite(row[field]) and row[field] > 0,
                          f"{name}: {field}={row[field]!r}")
            say(f"  {name}: ok, {len(resp['rows'])} rows, source "
                f"{resp['source']}, {resp['elapsed_ms']!r} ms server-side")
        check(len(answers["isocap"]["rows"]) == 30,
              "isocap must answer 30 rows")

        with Phase("repeat request", clock, "b"):
            again = ask({"spec": docs["isocap"], "want": ["summary"]})
        check(again.get("ok") is True, f"repeat not ok: {again}")
        check(again.get("source") == "cache",
              f"repeat came from {again.get('source')!r}, not the cache")
        stats = ask({"op": "stats"})
        check(stats.get("ok") is True, f"stats op not ok: {stats}")
        reqs = stats["stats"]["requests"]
        check(reqs["errors"] == 0, f"service counted errors: {reqs}")
        say(f"  repeat: source cache; stats: requests {reqs}, "
            f"result cache {stats['stats']['result_cache']}, "
            f"coalesce {stats['stats']['coalesce']}")
    finally:
        server.shutdown()
        server.server_close()
        svc.close()
        thread.join(timeout=30.0)
    return {name: answers[name]["rows"] for name in ("isocap", "dtco_isoarea")}


def mega_run(devices: int | None, clock: CompileClock, kind: str, label: str):
    import numpy as np

    from repro import scenarios
    from repro.core import sweep

    spec = scenarios.mega_spec()
    cells = sweep.n_cells(spec)
    check(cells == MEGA_CELLS, f"mega spec has {cells} cells")
    plan = sweep.ShardPlan(scenario_chunk=8, design_chunk=32,
                           devices=devices, by_width=True)
    with Phase(label, clock, kind) as ph:
        result = sweep.run_sharded(spec, plan)
    s, d = len(spec.scenarios), len(spec.designs)
    for table in result.tables:
        for field in TABLE_FIELDS:
            v = getattr(table, field)
            check(v.shape[0] == s and (v.ndim == 1 or v.shape == (s, d)),
                  f"{label}: {field} has shape {v.shape}")
            check(bool(np.isfinite(v).all()), f"{label}: {field} not finite")
    say(f"  {label}: {cells} cells ({len(spec.platforms)} platforms x {s} "
        f"scenarios x {d} designs), plan {plan}, {cells / ph.wall_s!r} "
        "cells/s wall")
    return spec, result


def phase_mega(clock: CompileClock, kind: str) -> None:
    import numpy as np

    spec, result = mega_run(None, clock, "c",
                            f"mega-sweep on one {kind} chip")
    rng = np.random.default_rng(0)
    errs = ErrorTable()
    for _ in range(MEGA_SAMPLE):
        pi = int(rng.integers(len(spec.platforms)))
        si = int(rng.integers(len(spec.scenarios)))
        di = int(rng.integers(len(spec.designs)))
        ref = reference_cell(spec.scenarios[si], spec.designs[di],
                             spec.platforms[pi])
        table = result.tables[pi]
        for field in ("runtime_s", "dyn_read_j", "dyn_write_j", "leak_j",
                      "dram_j"):
            errs.add(field, float(getattr(table, field)[si, di]), ref[field])
    for j, p in enumerate(spec.designs):
        if j % 7 == 0:   # every seventh design: all mems, nodes, capacities
            ref = reference_design(p.mem, p.capacity_bytes, p.node)
            errs.winner(f"{p.mem}@{p.capacity_mb}MB@{p.node.name}",
                        result.designs[j].org, ref.org)
    errs.report(f"mega sample ({MEGA_SAMPLE} cells)")
    errs.gate("mega sample")


def phase_inverse(clock: CompileClock) -> None:
    from repro import inverse

    prob = inverse.InverseProblem.load(
        os.path.join(ROOT, "specs", "inverse_isocap.json"))
    with Phase(f"inverse solve ({prob.starts} starts x {prob.iters} iters)",
               clock, "d"):
        res = inverse.solve(prob)
    say(f"  inverse: best {res.best_value!r}, standard-path re-eval "
        f"{res.standard_value!r}, parity {res.parity_rel_err!r}")
    say(f"  inverse: grid argmin {res.grid_best_value!r}, gain vs grid "
        f"{res.gain_vs_grid!r}, corner {res.corner}")
    check(math.isfinite(res.best_value), "inverse: non-finite optimum")
    check(res.parity_rel_err <= TOL,
          f"inverse parity {res.parity_rel_err!r} exceeds {TOL}")
    check(res.best_value <= res.grid_best_value,
          "inverse solve loses to the best grid corner")


def phase_correctness(served_rows: dict) -> None:
    from repro.core import sweep, tuner
    from repro.core.calibration import TABLE2
    from repro.core.sweep import SymbolicSweepSpec

    for name, rows in served_rows.items():
        spec = SymbolicSweepSpec.load(
            os.path.join(ROOT, "specs", f"{name}.json")).resolve()
        # the same memoized lowering the service ran: its tuned designs
        _, designs = sweep.lower_designs(spec.designs, pad_caps=True)
        errs = ErrorTable()
        for point, got in zip(spec.designs, designs):
            want = reference_design(point.mem, point.capacity_bytes,
                                    point.node)
            errs.winner(f"{point.mem}@{point.capacity_mb}MB@"
                        f"{point.node.name}", got.org, want.org)
            for field in DESIGN_FIELDS:
                errs.add(field, getattr(got, field), getattr(want, field))
        it = iter(rows)
        for platform in spec.platforms:
            for stats in spec.scenarios:
                for point in spec.designs:
                    row = next(it)
                    check((row["platform"], row["workload"], row["mem"],
                           row["node"]) == (platform.name, stats.workload,
                                            point.mem, point.node.name),
                          f"{name}: row order differs at {row}")
                    ref = reference_cell(stats, point, platform)
                    for field in CELL_FIELDS:
                        errs.add(field, row[field], ref[field])
        check(next(it, None) is None, f"{name}: extra rows")
        errs.report(name)
        errs.gate(name)

    # the paper anchors tests/test_paper_core.py pins, on the device path
    for mem in ("sram", "stt", "sot"):
        d = tuner.tuned_design(mem, 3)
        ref = TABLE2[mem]
        got = {"rlat": d.read_latency_s * 1e9, "wlat": d.write_latency_s * 1e9,
               "re": d.read_energy_j * 1e9, "we": d.write_energy_j * 1e9,
               "leak": d.leakage_w * 1e3, "area": d.area_mm2}
        for key, v in got.items():
            check(rel_err(v, ref[key]) <= 0.01,
                  f"Table II {mem} {key}: {v!r} vs paper {ref[key]}")
    caps = {mem: tuner.iso_area_capacity(mem) for mem in ("stt", "sot")}
    check(caps == {"stt": 7, "sot": 10},
          f"iso-area capacities {caps}, paper 7 MB STT / 10 MB SOT")
    for mem in ("stt", "sot"):
        ref = TABLE2[f"{mem}_isoarea"]
        d = tuner.tuned_design(mem, ref["cap"])
        check(rel_err(d.leakage_w * 1e3, ref["leak"]) <= 0.01
              and rel_err(d.area_mm2, ref["area"]) <= 0.01
              and rel_err(d.read_latency_s * 1e9, ref["rlat"]) <= 0.40,
              f"Table II {mem} iso-area column out of its bands")
    say(f"  paper anchors: Table II within bands, iso-area capacities "
        f"{caps['stt']} MB STT / {caps['sot']} MB SOT")


def phase_four_chips(clock: CompileClock, kind: str) -> None:
    import numpy as np

    from repro.core import workload_engine
    from repro.distributed.sharding import sweep_mesh

    mesh = sweep_mesh(4)
    ids = {d.id for d in mesh.devices.flat}
    check(len(ids) == 4, f"sweep mesh spans devices {sorted(ids)}")
    say(f"  sweep mesh: {mesh.devices.size} devices, ids {sorted(ids)}")

    # record where each shard_map'd group's packed output lives
    placed: list[set[int]] = []
    fold_for = workload_engine._sharded_fold

    def recording(m):
        fold = fold_for(m)

        def run(*args):
            out = fold(*args)
            placed.append({s.device.id for s in out.addressable_shards})
            return out
        return run

    workload_engine._sharded_fold = recording
    try:
        _, four = mega_run(4, clock, "4x", f"mega-sweep on 4 {kind} chips")
    finally:
        workload_engine._sharded_fold = fold_for
    check(bool(placed), "no chunk group ran shard_map'd")
    check(all(p == ids for p in placed),
          f"chunk groups landed on {sorted(set().union(*placed))}, "
          f"not on all of {sorted(ids)}")
    say(f"  {len(placed)} chunk groups, each on all 4 devices")
    spec, one = mega_run(1, clock, "4x", f"mega-sweep on 1 {kind} chip")
    worst = 0.0
    for a, b in zip(four.tables, one.tables):
        for field in TABLE_FIELDS:
            x, y = getattr(a, field), getattr(b, field)
            worst = max(worst, float(np.max(
                np.abs(x - y) / np.maximum(np.abs(y), 1e-300))))
    say(f"  4-chip vs 1-chip worst rel err: {worst!r}")
    check(worst <= TOL, f"4-chip parity {worst!r} exceeds {TOL}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded mega-sweep and its "
                         "1-chip comparison")
    args = ap.parse_args(argv)

    try:
        device = phase_device(4 if args.four_chips else 1)
        from repro.sweep.service import enable_compilation_cache
        say(f"compile cache: {enable_compilation_cache()}")
        clock = CompileClock()
        kind = device["kind"]
        if args.four_chips:
            phase_four_chips(clock, kind)
        else:
            served_rows = phase_served(clock)
            phase_mega(clock, kind)
            phase_inverse(clock)
            phase_correctness(served_rows)
    except Fault as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
