"""Tests for the sweep pipeline's own spans and counters (repro.tracing).

Structural only: which spans and counters a quick mega sweep records,
how they nest, and what is left out; never how long anything took.
Each traced run holds a JAX profiler trace in-process, on the CPU.
"""

import glob
import json
import os
import threading

import jax
import numpy as np
import pytest

from repro import scenarios, sweep_cli, tracing
from repro.core import engine, sweep, workload_engine

# the mega CLI's plan
PLAN = sweep.ShardPlan(scenario_chunk=8, design_chunk=32, by_width=True)
SPANS = ("sweep", "lower", "ppa.dispatch", "ppa.fetch", "tune", "pack",
         "fold.dispatch", "fold.wait", "fold.fetch", "assemble", "merge")
PER_CHUNK = ("pack", "fold.dispatch", "fold.fetch", "assemble")
FOLD_INPUTS = 13


def clear_memos():
    engine.design_table.cache_clear()
    sweep.clear_cache()
    workload_engine.clear_caches()


def traced(log_dir, fn):
    """``fn()`` on fresh memos under a profiler trace into ``log_dir``;
    returns its value and what the trace recorded."""
    clear_memos()
    tracing.reset()
    with jax.profiler.trace(str(log_dir)):
        value = fn()
    return value, tracing.summary(), tracing.records()


def same_result(a: sweep.SweepResult, b: sweep.SweepResult) -> bool:
    return a.designs == b.designs and all(
        np.array_equal(getattr(ta, f), getattr(tb, f))
        for ta, tb in zip(a.tables, b.tables)
        for f in ("l2_read_tx", "dram_tx", "runtime_s", "leak_j",
                  "dyn_read_j", "dram_j"))


@pytest.fixture(scope="module")
def spec():
    return scenarios.mega_spec(quick=True)


@pytest.fixture(scope="module")
def untraced(spec):
    clear_memos()
    tracing.reset()
    result = sweep.run_sharded(spec, PLAN)
    return result, tracing.summary(), tracing.records()


@pytest.fixture(scope="module")
def plain(spec, tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("trace-plain")
    result, s, recs = traced(log_dir, lambda: sweep.run_sharded(spec, PLAN))
    return result, s, recs, log_dir


def test_nothing_is_recorded_outside_a_profiler_trace(untraced):
    assert not tracing.enabled()
    _, s, recs = untraced
    assert recs == []
    assert s["roots"] == 0 and s["spans"] == {} and s["counters"] == {}
    with tracing.span("sweep") as sp:
        tracing.count("chunks")
    assert sp is tracing.OFF and tracing.span("pack", chunk="x") is sp
    assert tracing.records() == []


def test_one_sweep_root_holds_every_span(plain, spec):
    _, s, recs, _ = plain
    assert s["roots"] == 1
    assert set(s["spans"]) == set(SPANS)
    assert s["spans"]["sweep"]["calls"] == 1
    chunks = len(sweep.split(spec, PLAN))
    assert s["counters"]["chunks"] == chunks
    for name in PER_CHUNK:
        assert s["spans"][name]["calls"] == chunks, name
    root, = [r for r in recs if r.parent_id is None]
    assert root.name == "sweep" and not root.error
    assert all(r.root_id == root.id for r in recs)
    by_id = {r.id: r for r in recs}
    for r in recs:
        if r.parent_id is not None:
            parent = by_id[r.parent_id]
            assert parent.start_ns <= r.start_ns <= r.end_ns <= parent.end_ns
    # self times partition the root's wall time exactly
    assert sum(r.self_ns for r in recs) == root.duration_ns
    assert all(r.self_ns >= 0 for r in recs)


def test_every_chunk_pack_and_fetch_sits_under_its_assemble(plain):
    _, _, recs, _ = plain
    by_id = {r.id: r for r in recs}
    for r in recs:
        if r.name in ("pack", "fold.dispatch", "fold.wait", "fold.fetch"):
            assert by_id[r.parent_id].name == "assemble", r.name
    assert {r.attrs["chunk"] for r in recs if r.name == "assemble"} \
        == {r.attrs["chunk"] for r in recs
            if r.name == "merge" and "chunk" in r.attrs}


@pytest.mark.parametrize("devices", [None, 1])
def test_transfer_counters_per_chunk(spec, tmp_path, devices):
    plan = sweep.ShardPlan(scenario_chunk=8, design_chunk=32, by_width=True,
                           devices=devices)
    _, s, _ = traced(tmp_path, lambda: sweep.run_sharded(spec, plan))
    chunks = s["counters"]["chunks"]
    assert chunks == len(sweep.split(spec, plan))
    assert s["counters"]["fold.h2d"] == FOLD_INPUTS * chunks
    # the fold's ten outputs come back packed: one copy per device buffer
    assert s["counters"]["fold.d2h"] == chunks
    # one copy-out per chunk on either path, never one more
    assert s["spans"]["fold.fetch"]["calls"] == chunks


def test_a_sweep_that_raises_is_left_out(spec, tmp_path):
    class Stop(Exception):
        pass

    def progress(i, total, part):
        if i == 2:
            raise Stop

    def run():
        with pytest.raises(Stop):
            sweep.run_sharded(spec, PLAN, progress=progress)

    _, s, recs = traced(tmp_path, run)
    assert s["roots"] == 0 and s["spans"] == {} and s["counters"] == {}
    root, = [r for r in recs if r.parent_id is None]
    assert root.name == "sweep" and root.error
    # the raise came from the pull, outside every span but the root
    assert [r.name for r in recs if r.error] == ["sweep"]


def test_threads_keep_separate_stacks(tmp_path):
    barrier = threading.Barrier(2, timeout=30)

    def work():
        with tracing.span("sweep"):
            barrier.wait()           # both roots open
            with tracing.span("pack"):
                barrier.wait()       # both children open
                tracing.count("chunks")
            barrier.wait()

    def run():
        threads = [threading.Thread(target=work) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)

    _, s, recs = traced(tmp_path, run)
    assert s["roots"] == 2
    assert s["counters"] == {"chunks": 2}
    roots = {r.id for r in recs if r.name == "sweep"}
    packs = [r for r in recs if r.name == "pack"]
    assert len(roots) == 2 and len(packs) == 2
    assert {p.parent_id for p in packs} == roots
    assert all(p.root_id == p.parent_id for p in packs)


def test_traced_result_equals_untraced(plain, untraced):
    assert same_result(plain[0], untraced[0])


def test_trace_file_holds_the_spans(plain):
    log_dir = plain[3]
    paths = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    data = jax.profiler.ProfileData.from_file(paths[0])
    names = {e.name for plane in data.planes for line in plane.lines
             for e in line.events if e.name.startswith("host:")}
    assert {f"host:{n}" for n in SPANS} <= names


def test_summary_table_names_every_span_and_counter(plain):
    text = tracing.table(plain[1])
    for name in SPANS + ("chunks", "fold.h2d", "fold.d2h"):
        assert name in text


def test_cli_mega_quick_profile(tmp_path, capsys):
    clear_memos()
    sweep_cli.main(["mega", "--quick", "--profile", str(tmp_path)])
    out = capsys.readouterr()
    assert json.loads(out.out)
    assert "1 sweep root(s)" in out.err
    for name in SPANS + ("fold.h2d", "fold.d2h"):
        assert name in out.err
    assert glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)
    assert not tracing.enabled()


# ---------------------------------------------------------------------------
# Resolving a symbolic spec: the `resolve` root, `lm.streams` per new
# serve/ scenario, counters `scenarios` and `streams`
# ---------------------------------------------------------------------------

SERVE_SPEC = os.path.join(os.path.dirname(__file__), "..", "specs",
                          "kimi_linear_serve.json")


@pytest.fixture(scope="module")
def serve_sym():
    return sweep.SymbolicSweepSpec.load(SERVE_SPEC)


def test_resolve_is_a_root_with_one_lm_streams_span_per_new_scenario(
        serve_sym, tmp_path):
    scenarios.serve_traffic.cache_clear()
    spec, s, recs = traced(tmp_path, serve_sym.resolve)
    n = len(serve_sym.scenarios)
    assert tracing.summary()["roots"] == 0     # no sweep ran
    r = tracing.summary(root="resolve")
    assert r["roots"] == 1 and set(r["spans"]) == {"resolve", "lm.streams"}
    assert r["spans"]["lm.streams"]["calls"] == n
    assert r["counters"] == {
        "scenarios": n, "streams": sum(len(x.streams) for x in spec.scenarios)}
    root, = [x for x in recs if x.parent_id is None]
    assert root.name == "resolve" and not root.error
    assert {x.parent_id for x in recs if x.name == "lm.streams"} == {root.id}
    assert {x.attrs["scenario"] for x in recs if x.name == "lm.streams"} \
        == set(serve_sym.scenarios)
    assert sum(x.self_ns for x in recs) == root.duration_ns


def test_a_memo_hit_builds_no_streams(serve_sym, tmp_path):
    scenarios.serve_traffic.cache_clear()
    serve_sym.resolve()                        # untraced: fills the memo

    def twice():
        return serve_sym.resolve(), serve_sym.resolve()

    (a, b), _, _ = traced(tmp_path, twice)
    assert a == b
    r = tracing.summary(root="resolve")
    assert r["roots"] == 2 and set(r["spans"]) == {"resolve"}
    assert r["counters"] == {"scenarios": 2 * len(serve_sym.scenarios)}


def test_resolve_records_nothing_untraced(serve_sym):
    scenarios.serve_traffic.cache_clear()
    tracing.reset()
    serve_sym.resolve()
    assert tracing.records() == []
    assert tracing.summary(root="resolve")["counters"] == {}
