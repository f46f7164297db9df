"""The readers of the kimi_linear_decode cell's per-layer metrics: the
program's `resolve` spans and counters from a real resolve of the cell's
spec under a profiler trace, and the device readers on a synthetic trace
reduction.  Runs on the CPU."""

import json
import os
import sys

import jax
import pytest

from chipbench import harness


def traced_record(**kw) -> harness.Record:
    return harness.Record(cell="kimi_linear_decode", t_process=0.0,
                          trace=kw.pop("trace", {}), **kw)


@pytest.fixture(scope="module")
def resolved(tmp_path_factory):
    """Three resolves of the cell's spec in one trace, the first on a
    cleared memo: 38 scenarios each, streams built only the first time."""
    from repro import scenarios, tracing
    from repro.core import sweep

    bench = harness.load_benchmark()
    cfg = harness.load_json(os.path.join(harness.ROOT, harness.find(
        bench["configs"], "kimi_linear_serve", "configuration")["file"]))
    with open(os.path.join(harness.ROOT, cfg["spec"])) as f:
        doc = json.load(f)
    scenarios.serve_traffic.cache_clear()
    tracing.reset()
    with jax.profiler.trace(str(tmp_path_factory.mktemp("resolve"))):
        specs = [sweep.SymbolicSweepSpec.from_json(doc).resolve()
                 for _ in range(3)]
    yield specs
    tracing.reset()


def test_streams_per_scenario(resolved):
    streams = sum(len(s.streams) for s in resolved[0].scenarios)
    got = harness.reader("streams_per_scenario.kimi")(traced_record())
    assert got == pytest.approx(streams / (3 * 38))


def test_resolve_ms_is_the_mean_resolve_root(resolved):
    from repro import tracing

    rec = traced_record()
    got = harness.reader("resolve_ms.kimi")(rec)
    s = tracing.summary(root="resolve")
    assert s["roots"] == 3
    assert got == pytest.approx(s["spans"]["resolve"]["total_s"] * 1e3 / 3)
    assert rec.extra["resolve_spans"] == s


def test_untraced_or_without_tracing_reads_nothing(resolved, monkeypatch):
    rec = harness.Record(cell="kimi_linear_decode", t_process=0.0)
    assert harness.reader("resolve_ms.kimi")(rec) is None
    assert harness.reader("streams_per_scenario.kimi")(rec) is None
    import repro

    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    monkeypatch.delattr(repro, "tracing", raising=False)
    assert harness.reader("resolve_ms.kimi")(traced_record()) is None
    assert harness.reader("streams_per_scenario.kimi")(traced_record()) \
        is None


def test_no_resolve_reads_nothing(monkeypatch):
    from repro import tracing

    monkeypatch.setattr(tracing, "summary", lambda root="sweep": {
        "root": root, "roots": 0, "spans": {}, "counters": {}})
    assert harness.reader("resolve_ms.kimi")(traced_record()) is None
    assert harness.reader("streams_per_scenario.kimi")(traced_record()) \
        is None


def test_device_readers():
    trace = {"busy_s": 0.3, "window_s": 30.0,
             "kernel_s": {"_fold": 0.02, "_ppa_kernel": 0.01}}
    rec = traced_record(trace=trace, window_start=0.0, window_end=30.0,
                        chunks=[(1.0, 5_000), (2.0, 5_944), (31.0, 9)],
                        peaks={"hbm_bytes_per_s": 819e9},
                        extra={"cells_per_sweep": 10_944,
                               "fold_bytes_per_sweep": 1_000_000})
    assert harness.reader("idle_share.kimi")(rec) == pytest.approx(99.0)
    # one sweep's bytes at 819 GB/s over 20 ms of fold
    assert harness.reader("fold_roofline.kimi")(rec) == pytest.approx(
        100.0 * 1e6 / 819e9 / 0.02)
    rec.trace["kernel_s"] = {"_ppa_kernel": 0.01}
    assert harness.reader("fold_roofline.kimi")(rec) is None
