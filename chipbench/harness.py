"""The benchmark's harness: finds a cell's configuration, traffic mix,
generator and metric readers by the names in ``BENCHMARK.json``, runs one
measured window, reduces what it saw to metrics, checks the window's
answers against the plain reference, and prints the result line.

Nothing here knows a particular cell.  A configuration is
``configs/<config>.json``, a traffic mix ``traffic/<traffic>.json``
naming its generator ``generators/<kind>.py``, and every metric a reader
``metrics/<metric>.py`` with ``read(record) -> float | None``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import platform
import shutil
import sys
import tempfile
import threading
import time
from collections.abc import Callable

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SRC = os.path.join(ROOT, "src")
# Per-run detail (chunks, checks, host, trace reduction) goes here,
# one JSON file per run; the result line stays one line.
DETAIL_DIR = os.path.join(ROOT, ".chipbench")

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
WINDOW_SPAN = "chipbench:window"   # trace.WINDOW


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class BenchmarkError(RuntimeError):
    """The benchmark's own files are missing or do not fit together."""


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding a cell's pieces by name
# ---------------------------------------------------------------------------


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no BENCHMARK.json at {ROOT}")
    return load_json(path)


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchmarkError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: str, name: str):
    """Import one of the benchmark's own files by path (metric files carry
    dots in their names, so they are not importable as packages)."""
    if not os.path.isfile(path):
        raise BenchmarkError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: object            # the generators/<kind>.py module
    metrics: list[dict]          # the metrics this cell reports, per mode


def load_cell(bench: dict, name: str, trace: bool,
              traffic: dict | None = None) -> Cell:
    w = find(bench["workloads"], name, "workload")
    cfg_entry = find(bench["configs"], w["config"], "configuration")
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    if traffic is None:
        traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                         f"{w['traffic']}.json"))
    kind = traffic["generator"]
    gen = load_module(os.path.join(BENCH_DIR, "generators", f"{kind}.py"),
                      f"chipbench_generator_{kind}")
    metrics = [m for m in bench["per_layer" if trace else "end_to_end"]
               if applies(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, gen, metrics)


def reader(metric: str) -> Callable:
    mod = load_module(os.path.join(BENCH_DIR, "metrics", f"{metric}.py"),
                      "chipbench_metric_" + metric.replace(".", "_"))
    return mod.read


# ---------------------------------------------------------------------------
# Device and compile cache
# ---------------------------------------------------------------------------


def tpu_devices(chips: int) -> list:
    """The chips this cell runs on; there is no fallback to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU: {devs[0].platform!r} "
                            f"devices only")
    if len(devs) < chips:
        raise NoAccelerator(f"cell needs {chips} TPU chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's own persistent compile cache placement:
    ``$JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache/`` at the
    checkout root (a fixed path: the directory is part of the key)."""
    from repro.sweep.service import enable_compilation_cache

    return enable_compilation_cache()


class CompileClock:
    """XLA compile seconds, backend-compile events and persistent-cache
    hits, from JAX's own monitoring events.  A backend-compile event is
    recorded for every program built, whether compiled or read from the
    persistent cache, so its count inside the window is the number of
    programs the window had to build."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.hits += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.seconds, "compiles": self.compiles,
                    "cache_hits": self.hits}


def host_state() -> dict:
    """The host this run shares: its name, CPUs (all, and those this
    process may use), CPU model and load averages.  Spreads on the host's
    clock follow the host, so every run records it."""
    model = None
    try:
        with open("/proc/cpuinfo") as f:
            model = next((line.split(":", 1)[1].strip() for line in f
                          if line.startswith("model name")), None)
    except OSError:
        pass
    load = os.getloadavg()
    return {"node": platform.node(), "cpus": os.cpu_count(),
            "cpus_usable": len(os.sched_getaffinity(0)),
            "cpu_model": model, "load_1m": load[0], "load_5m": load[1]}


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# What a run leaves for the metric readers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Record:
    """Everything a metric reader may read.  Times are
    ``time.perf_counter()`` seconds."""

    cell: str
    t_process: float
    window_start: float = 0.0
    window_end: float = 0.0
    chunks: list[tuple[float, int]] = dataclasses.field(default_factory=list)
    sweeps: int = 0
    compiles_before: dict | None = None
    compiles_after: dict | None = None
    trace: dict | None = None          # trace.reduce() output
    peaks: dict | None = None          # peaks.json entry of this device
    extra: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start


def sweeps_in_window(rec: Record) -> float | None:
    """Sweeps' worth of cells finished inside the window (a sweep the
    window's end cut off counts by its finished chunks)."""
    per = rec.extra.get("cells_per_sweep")
    if not per or not rec.chunks:
        return None
    cells = sum(n for t, n in rec.chunks
                if rec.window_start <= t <= rec.window_end)
    return cells / per


def load_peaks(kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table["devices"]:
        raise BenchmarkError(f"device kind {kind!r} is not in peaks.json "
                             f"(has {sorted(table['devices'])})")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# Correctness: each number compared beside its limit
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def passed(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a generator gets: the cell, its seed, and the record it fills
    (the window's start and end among it)."""

    cell: Cell
    seed: int
    record: Record


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             check_device: bool = True, traffic: dict | None = None,
             t_process: float | None = None) -> tuple[dict, dict]:
    """Run one cell; return its result line (a dict) and a summary of the
    run (counts, compile-cache hits, the host it ran on).

    ``check_device=False`` skips the look for a TPU (the harness's own
    tests drive a run on the CPU with that); ``traffic`` replaces the
    cell's traffic file."""
    t_process = time.perf_counter() if t_process is None else t_process
    bench = load_benchmark()
    cell = load_cell(bench, name, trace, traffic)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise BenchmarkError(f"the program under test is not in this "
                             f"checkout (no {os.path.relpath(SRC, ROOT)}/"
                             f"repro)")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import jax

    devices = tpu_devices(cell.chips) if check_device \
        else jax.devices()[:cell.chips]
    dev0 = devices[0]
    say(f"chipbench: cell {name}, seed {seed}, {seconds} s window, trace "
        f"{int(trace)}, device {dev0.platform} {dev0.device_kind} x "
        f"{len(jax.devices())}")
    cache_dir = enable_compile_cache() if check_device else None
    say(f"chipbench: compile cache {cache_dir}")
    clock = CompileClock()
    record = Record(cell=name, t_process=t_process)
    if check_device:
        record.peaks = load_peaks(dev0.device_kind)
    detail: dict = {"cell": name, "seed": seed, "seconds": seconds,
                    "trace": int(trace), "host_before": host_state()}
    ctx = Context(cell, seed, record)
    work = cell.generator.Workload(ctx)

    trace_dir = None
    skipped_spans: list[str] = []
    try:
        work.setup()
        if trace:
            from chipbench import spans

            skipped_spans = spans.install()
            if skipped_spans:
                say(f"chipbench: host spans not found, left out: "
                    f"{', '.join(skipped_spans)}")
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # host spans come from spans.py
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        record.compiles_before = clock.snapshot()
        record.window_start = time.perf_counter()
        record.window_end = record.window_start + seconds
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            work.window()
        record.compiles_after = clock.snapshot()
        if trace:
            jax.profiler.stop_trace()
        peak = memory_peak_bytes(devices)
        work.close()
    finally:
        if trace:
            from chipbench import spans

            spans.uninstall()
    if trace:
        from chipbench import trace as trace_mod

        record.trace = trace_mod.reduce_dir(trace_dir,
                                            [d.id for d in devices])
        shutil.rmtree(trace_dir, ignore_errors=True)

    host_after = host_state()
    t_check = time.perf_counter()
    checks = work.check()
    t_check = time.perf_counter() - t_check
    correct = bool(checks) and all(c.passed for c in checks)

    metrics = {}
    for m in cell.metrics:
        value = reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted, failed = work.counts()
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result: dict = {"correct": correct, "attempted": attempted,
                    "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
        result["breakdown"] = record.trace["breakdown"]
        result["spans_skipped"] = skipped_spans
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}

    compiles = clock.snapshot()
    summary = {"sweeps": record.sweeps, "chunks": len(record.chunks),
               "check_s": t_check,
               "cpus": [host_after["cpus"], host_after["cpus_usable"]],
               "load_1m": [detail["host_before"]["load_1m"],
                           host_after["load_1m"]],
               "compile_s": compiles["compile_s"],
               "compile_cache_hits": compiles["cache_hits"],
               "window_compiles": (record.compiles_after["compiles"]
                                   - record.compiles_before["compiles"])}
    detail.update(result=result, summary=summary, compiles=compiles,
                  host_after=host_after,
                  trace_reduction=record.trace, extra=record.extra)
    write_detail(name, seed, trace, detail)
    return result, summary


def write_detail(name: str, seed: int, trace: bool, detail: dict) -> None:
    os.makedirs(DETAIL_DIR, exist_ok=True)
    path = os.path.join(DETAIL_DIR, f"{name}.{seed}.{int(trace)}.json")
    with open(path, "w") as f:
        json.dump(detail, f, default=str)
