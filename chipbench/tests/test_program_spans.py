"""The readers of the program's own spans and counters, on a synthetic
``repro.tracing.summary()``.  Runs on the CPU."""

import sys

import pytest

from chipbench import harness, program_spans

SUMMARY = {
    "root": "sweep", "roots": 4,
    "spans": {
        "sweep": {"calls": 4, "total_s": 6.0, "self_s": 0.04},
        "lower": {"calls": 8, "total_s": 0.9, "self_s": 0.02},
        "ppa.dispatch": {"calls": 8, "total_s": 0.1, "self_s": 0.1},
        "ppa.fetch": {"calls": 8, "total_s": 0.03, "self_s": 0.03},
        "fold.fetch": {"calls": 828, "total_s": 4.2, "self_s": 4.2},
    },
    "counters": {"chunks": 828, "fold.h2d": 10764, "fold.d2h": 8280},
}


@pytest.fixture
def summary(monkeypatch):
    from repro import tracing

    def fake(root="sweep"):
        assert root == program_spans.ROOT
        return SUMMARY

    monkeypatch.setattr(tracing, "summary", fake)


def traced_record() -> harness.Record:
    return harness.Record(cell="mega_fresh", t_process=0.0, trace={})


def test_self_ms_per_sweep_sums_the_spans(summary):
    rec = traced_record()
    # (0.02 + 0.1 + 0.03) s over 4 sweeps
    assert harness.reader("lower_ms.mega")(rec) == pytest.approx(37.5)
    assert harness.reader("fold_fetch_ms.mega")(rec) == pytest.approx(1050.0)
    assert rec.extra["program_spans"] == SUMMARY


def test_per_chunk_counters(summary):
    rec = traced_record()
    assert harness.reader("fold_h2d_per_chunk.mega")(rec) == 13.0
    assert harness.reader("fold_d2h_per_chunk.mega")(rec) == 10.0


def test_an_absent_span_leaves_the_metric_out(summary):
    rec = traced_record()
    assert harness.reader("tune_ms.mega")(rec) is None
    assert program_spans.self_ms_per_sweep(rec, ("lower", "gone")) is None
    assert program_spans.per_chunk(rec, "gone") is None


def test_untraced_run_reads_nothing(summary):
    rec = harness.Record(cell="mega_fresh", t_process=0.0)
    assert harness.reader("lower_ms.mega")(rec) is None
    assert harness.reader("fold_h2d_per_chunk.mega")(rec) is None
    assert "program_spans" not in rec.extra


def test_no_completed_sweep_reads_nothing(monkeypatch):
    from repro import tracing

    monkeypatch.setattr(tracing, "summary", lambda root="sweep": {
        "root": root, "roots": 0, "spans": {}, "counters": {}})
    assert harness.reader("assemble_ms.mega")(traced_record()) is None
    assert harness.reader("fold_d2h_per_chunk.mega")(traced_record()) is None


def test_a_program_without_tracing_reads_nothing(monkeypatch):
    import repro

    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    monkeypatch.delattr(repro, "tracing", raising=False)
    assert program_spans.summary(traced_record()) is None
    assert harness.reader("pack_ms.mega")(traced_record()) is None
