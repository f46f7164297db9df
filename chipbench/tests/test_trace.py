"""The trace-to-metrics reduction, on a hand-made trace and on a small
trace recorded on a TPU v5e (``data/trace_v5e.json``, written by
``record_trace.py``).  Runs on the CPU."""

import json
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_v5e.json")


def planes(ops, modules, host):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": trace.OPS_LINE, "events": ops},
            {"name": trace.MODULES_LINE, "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host}]},
    ]


def test_busy_is_the_union_of_overlapping_ops_clipped_to_the_window():
    ops = [("a", 0, 30), ("b", 20, 20), ("c", 90, 30), ("d", 200, 10)]
    host = [(trace.WINDOW, 10, 100)]
    r = trace.reduce(planes(ops, [], host), [0])
    # [10, 40) from a and b together, [90, 110) from c; d is outside
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)


def test_kernel_time_is_keyed_by_the_jitted_function_name():
    modules = [("jit__fold(12)", 10, 5), ("jit__fold(13)", 30, 5),
               ("jit__ppa_kernel(7)", 50, 20)]
    host = [(trace.WINDOW, 0, 100)]
    r = trace.reduce(planes([], modules, host), [0])
    assert r["kernel_s"] == {"_fold": pytest.approx(10e-9),
                             "_ppa_kernel": pytest.approx(20e-9)}
    assert r["kernel_calls"] == {"_fold": 2, "_ppa_kernel": 1}


def test_idle_gaps_go_to_the_innermost_open_host_span():
    ops = [("op", 40, 10)]
    host = [(trace.WINDOW, 0, 100), ("host:lower_designs", 0, 60),
            ("host:tuned_index", 10, 20), ("host:views", 70, 10)]
    r = trace.reduce(planes(ops, [], host), [0])
    idle = r["idle_by_host_span_s"]
    # idle is [0, 40) and [50, 100): tuned_index holds [10, 30), its
    # parent the rest of [0, 60) that is idle, views [70, 80)
    assert idle["tuned_index"] == pytest.approx(20e-9)
    assert idle["lower_designs"] == pytest.approx(30e-9)
    assert idle["views"] == pytest.approx(10e-9)
    assert idle[trace.NO_SPAN] == pytest.approx(30e-9)
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(100e-9)
    labels = [k for k, _ in r["breakdown"]["idle_gaps"]]
    assert labels[0] in ("lower_designs", trace.NO_SPAN)


def test_a_trace_without_the_window_annotation_is_refused():
    with pytest.raises(RuntimeError, match="annotation"):
        trace.reduce(planes([("op", 0, 1)], [], []), [0])


def test_stable_names():
    assert trace.stable_name("jit__ppa_kernel(3)") == "_ppa_kernel"
    assert trace.stable_name("jit__fold") == "_fold"
    assert trace.stable_name("jit_body(4)") == "body"


def test_recorded_v5e_trace():
    with open(DATA) as f:
        recorded = json.load(f)
    r = trace.reduce(recorded, [0])
    assert 0 < r["busy_s"] < r["window_s"]
    assert {"_ppa_kernel", "_fold"} <= set(r["kernel_s"])
    assert all(v > 0 for v in r["kernel_s"].values())
    # busy and labelled idle time tile the window
    idle = sum(r["idle_by_host_span_s"].values())
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-9)
    assert {"tuned_index", "ppa_dispatch"} & set(r["idle_by_host_span_s"])
    assert len(r["breakdown"]["device_ops"]) <= trace.TOP
