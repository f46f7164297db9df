"""Reduction of a profiler trace to the benchmark's device numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (or the same planes as plain dicts, which is
how the recorded test trace is stored).  The run marks its measured
window with a host annotation named ``WINDOW``; everything is clipped to
it.

- busy time: the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line), averaged over the cell's devices;
- per-kernel device time: the summed durations of a program's events on
  the devices' ``XLA Modules`` lines, summed over the cell's devices and
  keyed by a stable name (the jitted function's name: ``jit__fold(42)``
  -> ``_fold``); the top device operations are summed the same way;
- idle gaps: the complement of busy time on the first device, each slice
  labelled with the innermost host span (``host:<layer>``) open at that
  moment on any host thread, or ``no host span``.
"""

from __future__ import annotations

import glob
import heapq
import os
import re

WINDOW = "chipbench:window"
HOST_PREFIX = "host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "no host span"
TOP = 10

_MODULE_RE = re.compile(r"^(?:jit_)?(?P<name>.*?)(?:\(\d+\))?$")


def stable_name(module: str) -> str:
    """``jit__ppa_kernel(7)`` -> ``_ppa_kernel``; ``jit_body`` -> ``body``."""
    return _MODULE_RE.match(module).group("name")


def short_name(name: str) -> str:
    """An XLA op event carries its whole HLO instruction as its name;
    keep the instruction's own name (``%fusion.315 = (...) ...`` ->
    ``fusion.315``)."""
    head, sep, _ = name.partition(" = ")
    return head.lstrip("%") if sep else name


def planes_from_profile(profile) -> list[dict]:
    """ProfileData -> [{"name", "lines": [{"name", "events": [(name,
    start_ns, duration_ns)]}]}], keeping device planes and host lines that
    carry the benchmark's annotations."""
    out = []
    for plane in profile.planes:
        is_device = plane.name.startswith("/device:")
        if not (is_device or plane.name.startswith("/host:")):
            continue
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [(short_name(e.name), float(e.start_ns),
                       float(e.duration_ns))
                      for e in line.events
                      if is_device or e.name.startswith(HOST_PREFIX)
                      or e.name == WINDOW]
            if events:
                lines.append({"name": line.name, "events": events})
        out.append({"name": plane.name, "lines": lines})
    return out


def load_dir(trace_dir: str) -> list[dict]:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return planes_from_profile(ProfileData.from_file(paths[0]))


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _device_planes(planes: list[dict], device_ids) -> list[dict]:
    devs = [p for p in planes if p["name"].startswith("/device:")]
    want = {f"/device:TPU:{i}" for i in device_ids}
    chosen = [p for p in devs if p["name"] in want]
    return chosen or devs[:len(device_ids)]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def window_bounds(planes: list[dict]) -> tuple[float, float]:
    for p in planes:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                for name, start, dur in line["events"]:
                    if name == WINDOW:
                        return start, start + dur
    raise RuntimeError(f"no {WINDOW!r} annotation in the trace")


def attribute_gaps(gaps: list[tuple[float, float]],
                   spans: list[tuple[str, float, float]]) -> dict[str, float]:
    """Idle nanoseconds per label: each piece of a gap goes to the
    innermost (latest-started) host span open over it."""
    # timeline of (start, end, label) pieces from a sweep over the span
    # boundaries; the open span that started last labels each piece
    bounds = sorted([(a, 1, i) for i, (_, a, _b) in enumerate(spans)]
                    + [(b, 0, i) for i, (_, _a, b) in enumerate(spans)])
    heap: list[tuple[float, int]] = []
    closed: set[int] = set()
    pieces: list[tuple[float, float, str]] = []
    t_prev = None
    for t, is_start, i in bounds:
        while heap and heap[0][1] in closed:
            heapq.heappop(heap)
        if t_prev is not None and t > t_prev:
            label = spans[heap[0][1]][0][len(HOST_PREFIX):] if heap \
                else NO_SPAN
            pieces.append((t_prev, t, label))
        t_prev = t
        if is_start:
            heapq.heappush(heap, (-spans[i][1], i))
        else:
            closed.add(i)
    out: dict[str, float] = {}
    k = 0
    for g0, g1 in gaps:
        covered = 0.0
        while k < len(pieces) and pieces[k][1] <= g0:
            k += 1
        j = k
        while j < len(pieces) and pieces[j][0] < g1:
            a, b, label = pieces[j]
            c = clip(a, b, g0, g1)
            if c:
                out[label] = out.get(label, 0.0) + (c[1] - c[0])
                covered += c[1] - c[0]
            j += 1
        if g1 - g0 > covered:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (g1 - g0 - covered)
    return out


def reduce(planes: list[dict], device_ids) -> dict:
    """The trace's numbers for one run (seconds, over the window)."""
    w0, w1 = window_bounds(planes)
    window_ns = w1 - w0
    devices = _device_planes(planes, device_ids)
    if not devices:
        raise RuntimeError("the trace holds no device plane")
    busy = []
    kernels: dict[str, float] = {}
    calls: dict[str, int] = {}
    ops: dict[str, float] = {}
    first_busy: list[tuple[float, float]] = []
    for k, plane in enumerate(devices):
        iv = [c for _, s, d in _line(plane, OPS_LINE)
              if (c := clip(s, s + d, w0, w1))]
        merged = union(iv)
        busy.append(sum(b - a for a, b in merged))
        if k == 0:
            first_busy = merged
        for name, s, d in _line(plane, MODULES_LINE):
            c = clip(s, s + d, w0, w1)
            if c:
                key = stable_name(name)
                kernels[key] = kernels.get(key, 0.0) + (c[1] - c[0])
                calls[key] = calls.get(key, 0) + 1
        for name, s, d in _line(plane, OPS_LINE):
            c = clip(s, s + d, w0, w1)
            if c:
                ops[name] = ops.get(name, 0.0) + (c[1] - c[0])
    gaps, t = [], w0
    for a, b in first_busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    spans = [(name, s, s + d)
             for p in planes if p["name"].startswith("/host:")
             for line in p["lines"] for name, s, d in line["events"]
             if name.startswith(HOST_PREFIX)]
    idle = attribute_gaps(gaps, spans)
    n = len(devices)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "devices": n,
        "kernel_s": {k: v * 1e-9 for k, v in kernels.items()},
        "kernel_calls": calls,
        "idle_by_host_span_s": {k: v * 1e-9 for k, v in idle.items()},
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": [[k, v * 1e-9] for k, v in top_idle],
        },
    }


def reduce_dir(trace_dir: str, device_ids) -> dict:
    return reduce(load_dir(trace_dir), device_ids)
