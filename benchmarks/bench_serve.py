"""Sweep-service benchmark: cold start vs warmup, and coalesced vs
serial throughput, recorded in benchmarks/BENCH_serve.json.

Three measurements:

  cold start  first-request latency of a fresh process (subprocess, jax
              import excluded — the same methodology as BENCH_sweep.json's
              ``e2e_cold_s``) against a process that called
              ``SweepService.warmup`` on the same spec first.  The warmed
              service answers its first request at warm-dispatch cost
              because every compile (bitcell characterization,
              calibration, PPA traces, the bucketed fold) already
              happened before traffic arrived.  Two more warmed runs
              with the persistent compilation cache on (the first fills
              it, the second reads it) measure how much of the warmup
              itself survives restarts.  The children need the device,
              so they run before this process imports jax.

  throughput  8 concurrent compatible golden-derived requests (isocap
              scenario slices x capacity variants) through the coalescing
              service vs the same requests answered one-at-a-time with
              coalescing disabled.  Identical per-request cells both
              ways; the coalesced path evaluates ONE superset fold per
              window instead of eight.

  parity      every coalesced response's rows vs its individual
              ``sweep.run()`` (worst relative error, asserted <= 1e-12).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
JSON_PATH = "benchmarks/BENCH_serve.json"
REPS = 5

# child process: time warmup (optional) and the first real request,
# excluding interpreter + jax import (argv[1] is a JSON config)
_CHILD = r"""
import json, sys, time
cfg = json.loads(sys.argv[1])
from repro.sweep.service import SweepService, enable_compilation_cache
if cfg["compile_cache"]:
    enable_compilation_cache()
svc = SweepService(window_ms=0.0)
out = {}
if cfg["warmup"]:
    t0 = time.perf_counter()
    svc.warmup(specs=[cfg["spec_path"]])
    out["warmup_s"] = time.perf_counter() - t0
with open(cfg["spec_path"]) as f:
    doc = json.load(f)
t0 = time.perf_counter()
resp = svc.handle({"spec": doc, "want": ["summary"]})
out["first_request_s"] = time.perf_counter() - t0
out["ok"] = resp["ok"]
svc.close()
print(json.dumps(out))
"""


def _child_run(warmup: bool, compile_cache: bool = False) -> dict:
    """One fresh process.  A parent that has imported jax may hold the
    device the child needs, so that is refused outright."""
    if "jax" in sys.modules:
        raise RuntimeError("bench_serve's cold-start children must be "
                           "started before this process imports jax")
    cfg = {"warmup": warmup, "compile_cache": compile_cache,
           "spec_path": os.path.join(ROOT, "specs", "isocap.json")}
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    if not compile_cache:   # a cold child must not read a cache from env
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, json.dumps(cfg)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"bench child failed:\n{proc.stderr}")
    out = json.loads(proc.stdout)
    assert out["ok"]
    return out


# -- the concurrent request set ---------------------------------------------


GOLDENS = ("isocap", "dtco", "dtco_isoarea", "lm_nvm")


def _request_docs(copies: int) -> list[dict]:
    """The concurrent request set: every golden spec, ``copies`` clients
    each — the thundering-herd traffic the coalescer exists for.
    Identical in-flight copies collapse to one evaluation (dedup), and
    the distinct same-platform goldens merge through the superset union;
    the serial baseline answers all of them one full evaluation each."""
    docs = []
    for name in GOLDENS:
        with open(os.path.join(ROOT, "specs", f"{name}.json")) as f:
            docs.append(json.load(f))
    return [d for d in docs for _ in range(copies)]


def _fire(svc, docs: list[dict],
          want=("summary",)) -> tuple[list[dict], float]:
    # threads are spawned outside the timed region and released together:
    # the clock measures burst-to-last-response wall time only
    barrier = threading.Barrier(len(docs) + 1)
    responses = [None] * len(docs)

    def shoot(i, d):
        barrier.wait()
        responses[i] = svc.handle({"spec": d, "want": list(want)})

    threads = [threading.Thread(target=shoot, args=(i, d))
               for i, d in enumerate(docs)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    assert all(r["ok"] for r in responses), \
        [r.get("error") for r in responses if not r["ok"]]
    return responses, dt


def _serial(svc, docs: list[dict]) -> float:
    t0 = time.perf_counter()
    for d in docs:
        resp = svc.handle({"spec": d, "want": ["summary"]})
        assert resp["ok"], resp.get("error")
    return time.perf_counter() - t0


def _parity(responses: list[dict], docs: list[dict]) -> float:
    from repro.core import sweep
    from repro.core.sweep import SymbolicSweepSpec

    worst = 0.0
    for d, resp in zip(docs, responses):
        want = sweep.run(SymbolicSweepSpec.from_json(d).resolve()).rows()
        got = resp["rows"]
        assert len(got) == len(want)
        for g, w in zip(got, want):
            for key, wv in w.items():
                gv = g[key]
                if isinstance(wv, float) and wv == wv and wv not in (
                        float("inf"), float("-inf")):
                    err = abs(gv - wv) / (abs(wv) or 1.0)
                    worst = max(worst, err)
                elif not isinstance(wv, float):
                    assert gv == wv
    assert worst <= 1e-12, worst
    return worst


def run(quick: bool = False) -> dict:
    reps = 2 if quick else REPS
    copies = 2 if quick else 8

    # cold start vs warmed first request (fresh process each), all before
    # this process imports jax
    cold = _child_run(warmup=False)
    warmed = _child_run(warmup=True)
    warm_hist = {}
    if not quick:
        _child_run(warmup=True, compile_cache=True)        # populate
        reused = _child_run(warmup=True, compile_cache=True)
        warm_hist = {"warmup_s_fresh": warmed["warmup_s"],
                     "warmup_s_cached": reused["warmup_s"]}

    from repro.core.sweep import SymbolicSweepSpec
    from repro.sweep.service import SweepService

    # concurrent coalesced vs serial throughput on the golden specs.
    # A near-zero window: a simultaneous burst coalesces through queueing
    # and in-flight dedup (requests pile up while an evaluation is in
    # flight), so the wall clock pays no batching delay.
    docs = _request_docs(copies)
    k = len(docs)
    cells = sum(
        len(SymbolicSweepSpec.from_json(d).resolve().scenarios)
        * len(SymbolicSweepSpec.from_json(d).resolve().designs)
        * len(SymbolicSweepSpec.from_json(d).resolve().platforms)
        for d in docs)                 # requested cells per round

    with SweepService(window_ms=1.0, cache_size=0) as absorb:
        _serial(absorb, docs)          # member + union shapes compile here
        _fire(absorb, docs)

    serial_svc = SweepService(coalesce=False, cache_size=0)
    serial_s = min(_serial(serial_svc, docs) for _ in range(reps))
    serial_svc.close()

    coal_svc = SweepService(window_ms=1.0, cache_size=0)
    coal_s = min(_fire(coal_svc, docs)[1] for _ in range(reps))
    stats = coal_svc.stats()           # before the rows-parity round
    responses, _ = _fire(coal_svc, docs, want=("rows",))
    coalesced = sum(r["source"] == "coalesced" for r in responses)
    worst = _parity(responses, docs)
    coal_stats = coal_svc.stats()["coalesce"]
    coal_svc.close()

    result = dict(
        serve="concurrent sweep service (coalescing + warmup)",
        n_requests=k,
        cells_per_round=cells,
        cold_first_request_s=cold["first_request_s"],
        warm_first_request_s=warmed["first_request_s"],
        warmup_s=warmed["warmup_s"],
        cold_warm_ratio_x=(cold["first_request_s"]
                           / warmed["first_request_s"]),
        **warm_hist,
        serial_s=serial_s,
        coalesced_s=coal_s,
        serial_cells_s=cells / serial_s,
        coalesced_cells_s=cells / coal_s,
        coalesce_speedup_x=serial_s / coal_s,
        requests_s=k / coal_s,
        coalesced_responses=coalesced,
        union_coalesced_requests=coal_stats["coalesced_requests"],
        deduped_requests=coal_stats["deduped_requests"],
        elapsed_ms_p50=stats["elapsed_ms"]["p50"],
        elapsed_ms_p95=stats["elapsed_ms"]["p95"],
        parity_worst_rel_err=worst,
    )
    os.makedirs(os.path.dirname(JSON_PATH), exist_ok=True)
    with open(JSON_PATH, "w") as f:
        json.dump(result, f, indent=2)
    return {"rows": [result],
            "bench": {"cold_first_request_s": cold["first_request_s"],
                      "warm_first_request_s": warmed["first_request_s"],
                      "cold_warm_ratio_x": result["cold_warm_ratio_x"],
                      "coalesce_speedup_x": result["coalesce_speedup_x"],
                      "coalesced_cells_s": result["coalesced_cells_s"],
                      "parity_worst_rel_err": worst},
            "derived": (f"cold={cold['first_request_s']:.2f}s,"
                        f"warm={warmed['first_request_s']*1e3:.1f}ms,"
                        f"ratio={result['cold_warm_ratio_x']:.0f}x,"
                        f"coalesce={result['coalesce_speedup_x']:.1f}x,"
                        f"parity_err={worst:.2e}")}


if __name__ == "__main__":
    print(run()["derived"])
