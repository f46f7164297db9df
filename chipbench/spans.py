"""Host spans for the traced run, written from the benchmark's side.

The program records no spans of its own yet.  In a ``--trace 1`` run the
benchmark wraps the calls into each host layer with
``jax.profiler.TraceAnnotation``, so the profiler's trace carries them on
the same clock as the device's operations, and the reduction can say what
the host was doing in each idle gap.  ``uninstall`` puts every function
back.  Untraced runs never install them.

The targets are names inside the program, which a change to the program
may move or remove.  ``install`` skips a target that is not there (or is
not callable) and returns its name; the run goes on, and idle time that
span would have labelled falls to the next span open, or to no span.

Each entry: (module, attribute path, span name).
"""

from __future__ import annotations

import functools
import importlib

WRAPPED = (
    ("repro.core.sweep", "SymbolicSweepSpec.resolve", "spec_resolve"),
    ("repro.core.sweep", "lower_designs", "lower_designs"),
    ("repro.core.engine", "_run_kernel", "ppa_dispatch"),
    ("repro.core.engine", "DesignTable.tuned_index", "tuned_index"),
    ("repro.core.workload_engine", "pack", "pack"),
    ("repro.core.workload_engine", "_fold_kernel", "fold_dispatch"),
    ("repro.core.sweep", "merge_results", "merge_results"),
    ("repro.core.sweep", "_chunk_result", "chunk_result"),
    ("repro.core.workload_engine", "evaluate_chunk", "evaluate_chunk"),
    ("repro.core.workload_engine", "evaluate_chunk_group",
     "evaluate_chunk_group"),
    ("repro.core.workload_engine", "_tables_from", "tables_from"),
    ("repro.core.engine", "DesignTable.subset", "table_subset"),
)

_saved: list[tuple[object, str, object]] = []


def _annotated(fn, name: str):
    import jax

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.profiler.TraceAnnotation(f"host:{name}"):
            return fn(*args, **kwargs)
    return wrapper


def _target(module: str, path: str):
    """The owner and current value of ``module.path``, or None where any
    part of it is missing."""
    try:
        owner = importlib.import_module(module)
    except ImportError:
        return None
    *parents, attr = path.split(".")
    for p in parents:
        owner = getattr(owner, p, None)
        if owner is None:
            return None
    if isinstance(owner, type):
        original = owner.__dict__.get(attr)
    else:
        original = getattr(owner, attr, None)
    if not callable(original):
        return None
    return owner, attr, original


def install() -> list[str]:
    """Wrap every target that is there; return the ``module.path`` of each
    one that is not."""
    if _saved:
        return []
    skipped = []
    for module, path, name in WRAPPED:
        found = _target(module, path)
        if found is None:
            skipped.append(f"{module}.{path}")
            continue
        owner, attr, original = found
        _saved.append((owner, attr, original))
        setattr(owner, attr, _annotated(original, name))
    return skipped


def uninstall() -> None:
    while _saved:
        owner, attr, original = _saved.pop()
        setattr(owner, attr, original)
