"""Tests for the repro.analysis static-analysis suite.

Each rule gets a planted-violation fixture that must fire and a
corrected twin that must stay silent; on top of that the suppression
markers, the baseline round-trip, and the CLI exit codes are exercised,
and the analyzer is required to run clean over ``src/repro/core``.

The DNVM001 wrapper test replays the PR-4 incident: ``design_table``
grew a ``nodes`` parameter but kept forwarding into its memoized worker
without it, so every node silently shared the 16 nm tables.  Reverting
that fix must be caught by the analyzer, not by luck.
"""

from __future__ import annotations

import os
import textwrap
from pathlib import Path

import pytest

from repro.analysis import __main__ as cli
from repro.analysis import common, driver

REPO_ROOT = Path(__file__).resolve().parents[1]


def run_source(tmp_path, source, rules=None, name="sample.py",
               baseline=None):
    path = tmp_path / name
    path.write_text(textwrap.dedent(source))
    return driver.run_paths([str(path)], rules=rules, baseline=baseline)


def messages(result):
    return [f"{f.rule} {f.message}" for f in result.active]


# ---------------------------------------------------------------------------
# DNVM001 — memo-key completeness


class TestMemoKeys:
    def test_varying_global_read_fires(self, tmp_path):
        res = run_source(tmp_path, """
            import functools

            counter = 0

            def bump():
                global counter
                counter += 1

            @functools.lru_cache(maxsize=None)
            def lookup(x):
                return x + counter
            """, rules=["DNVM001"])
        assert len(res.active) == 1
        assert "mutable module state 'counter'" in res.active[0].message

    def test_constant_registry_read_is_silent(self, tmp_path):
        res = run_source(tmp_path, """
            import functools

            TABLE = {"stt": 1.0, "sot": 2.0}

            @functools.lru_cache(maxsize=None)
            def lookup(mem):
                return TABLE[mem]
            """, rules=["DNVM001"])
        assert res.active == []

    def test_mutable_default_fires_and_tuple_twin_is_silent(self, tmp_path):
        fires = run_source(tmp_path, """
            import functools

            @functools.cache
            def grid(caps=[1024, 2048]):
                return sum(caps)
            """, rules=["DNVM001"])
        assert len(fires.active) == 1
        assert "mutable default" in fires.active[0].message

        silent = run_source(tmp_path, """
            import functools

            @functools.cache
            def grid(caps=(1024, 2048)):
                return sum(caps)
            """, rules=["DNVM001"], name="twin.py")
        assert silent.active == []

    def test_pr4_node_blind_wrapper_fires(self, tmp_path):
        """Reverting the PR-4 design_table fix must be caught: the
        wrapper takes ``nodes`` but never forwards it into the key."""
        res = run_source(tmp_path, """
            import functools

            @functools.lru_cache(maxsize=None)
            def _design_table_cached(mems, capacities_bytes):
                return (mems, capacities_bytes)

            def design_table(mems, capacities_bytes, nodes=None):
                return _design_table_cached(tuple(mems),
                                            tuple(capacities_bytes))
            """, rules=["DNVM001"])
        assert len(res.active) == 1
        msg = res.active[0].message
        assert "'nodes' is never read" in msg
        assert "PR-4 design_table bug class" in msg

    def test_forwarding_wrapper_twin_is_silent(self, tmp_path):
        res = run_source(tmp_path, """
            import functools

            @functools.lru_cache(maxsize=None)
            def _design_table_cached(nodes, mems, capacities_bytes):
                return (nodes, mems, capacities_bytes)

            def design_table(mems, capacities_bytes, nodes=None):
                return _design_table_cached(nodes, tuple(mems),
                                            tuple(capacities_bytes))
            """, rules=["DNVM001"])
        assert res.active == []

    def test_real_design_table_wrapper_forwards_every_param(self):
        """The live engine.py wrapper stays key-complete."""
        res = driver.run_paths(
            [str(REPO_ROOT / "src/repro/core/engine.py")],
            rules=["DNVM001"])
        assert messages(res) == []


# ---------------------------------------------------------------------------
# DNVM002 — jit/retrace discipline


class TestRetrace:
    def test_traced_branch_fires(self, tmp_path):
        res = run_source(tmp_path, """
            import jax

            @jax.jit
            def kernel(x, fast_path):
                if fast_path:
                    return x * 2.0
                return x
            """, rules=["DNVM002"])
        assert len(res.active) == 1
        assert "branches on traced argument 'fast_path'" in \
            res.active[0].message

    def test_static_argnames_twin_is_silent(self, tmp_path):
        res = run_source(tmp_path, """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("fast_path",))
            def kernel(x, fast_path):
                if fast_path:
                    return x * 2.0
                return x
            """, rules=["DNVM002"])
        assert res.active == []

    def test_jit_call_assignment_with_static_argnums(self, tmp_path):
        res = run_source(tmp_path, """
            import jax

            def kernel(x, mode):
                if mode:
                    return x * 2.0
                return x

            fast = jax.jit(kernel, static_argnums=(1,))
            """, rules=["DNVM002"])
        assert res.active == []

    def test_varying_global_capture_fires(self, tmp_path):
        res = run_source(tmp_path, """
            import jax

            scale = 1.0

            def set_scale(s):
                global scale
                scale = s

            @jax.jit
            def kernel(x):
                return x * scale
            """, rules=["DNVM002"])
        assert len(res.active) == 1
        assert "captures mutable module state 'scale'" in \
            res.active[0].message

    def test_dtype_narrowing_fires_only_under_x64(self, tmp_path):
        src = """
            import contextlib

            import jax
            import jax.numpy as jnp

            @jax.jit
            def kernel(x):
                return x.astype(jnp.float32)

            def run(x):
                with {ctx}:
                    return kernel(x)
            """
        fires = run_source(
            tmp_path, src.format(ctx="jax.enable_x64(True)"),
            rules=["DNVM002"])
        assert len(fires.active) == 1
        assert "narrows the enable_x64 float64 contract" in \
            fires.active[0].message

        silent = run_source(tmp_path,
                            src.format(ctx="contextlib.nullcontext()"),
                            rules=["DNVM002"], name="no_x64.py")
        assert silent.active == []


# ---------------------------------------------------------------------------
# DNVM003 — unit consistency


class TestUnits:
    def test_seconds_plus_joules_fires(self, tmp_path):
        res = run_source(tmp_path, """
            def edp(read_latency_s, read_energy_j):
                return read_latency_s + read_energy_j
            """, rules=["DNVM003"])
        assert len(res.active) == 1
        assert "unit mismatch" in res.active[0].message

    def test_seconds_plus_seconds_is_silent(self, tmp_path):
        res = run_source(tmp_path, """
            def total(read_latency_s, write_latency_s):
                return read_latency_s + write_latency_s
            """, rules=["DNVM003"])
        assert res.active == []

    def test_farads_times_ohms_binds_to_seconds(self, tmp_path):
        """RC products are the bread and butter of cachemodel.py — the
        F*ohm -> s identity must be understood, not flagged."""
        res = run_source(tmp_path, """
            def rc_delay(c_bitline_f, r_driver_ohm):
                tau_s = c_bitline_f * r_driver_ohm
                return tau_s
            """, rules=["DNVM003"])
        assert res.active == []

    def test_keyword_unit_mismatch_fires(self, tmp_path):
        res = run_source(tmp_path, """
            def record(energy_j):
                return energy_j

            def caller(leakage_w):
                return record(energy_j=leakage_w)
            """, rules=["DNVM003"])
        assert len(res.active) == 1
        assert "keyword 'energy_j'" in res.active[0].message

    def test_scaled_seconds_stay_seconds(self, tmp_path):
        res = run_source(tmp_path, """
            def slowdown(read_latency_s):
                padded_s = 1.15 * read_latency_s
                return padded_s
            """, rules=["DNVM003"])
        assert res.active == []


# ---------------------------------------------------------------------------
# DNVM004 — lock discipline


class TestLocks:
    def test_unguarded_counter_fires(self, tmp_path):
        res = run_source(tmp_path, """
            import threading

            class Service:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.batches = 0

                def tick(self):
                    self.batches += 1
            """, rules=["DNVM004"])
        assert len(res.active) == 1
        assert "mutates 'self.batches' outside" in res.active[0].message

    def test_guarded_twin_is_silent(self, tmp_path):
        res = run_source(tmp_path, """
            import threading

            class Service:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.batches = 0

                def tick(self):
                    with self._lock:
                        self.batches += 1
            """, rules=["DNVM004"])
        assert res.active == []

    def test_any_owned_lock_counts(self, tmp_path):
        """Guardedness, not lock-to-field assignment: holding the
        class's condition variable is as good as holding its lock."""
        res = run_source(tmp_path, """
            import threading

            class Service:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cv = threading.Condition(self._lock)
                    self.pending = {}

                def enqueue(self, key, item):
                    with self._cv:
                        self.pending[key] = item
            """, rules=["DNVM004"])
        assert res.active == []

    def test_module_global_outside_lock_fires(self, tmp_path):
        res = run_source(tmp_path, """
            import threading

            _registry_lock = threading.Lock()
            _registry = None

            def install(r):
                global _registry
                _registry = r
            """, rules=["DNVM004"])
        assert len(res.active) == 1
        assert "global '_registry' assigned outside" in \
            res.active[0].message

    def test_lockless_class_is_out_of_scope(self, tmp_path):
        res = run_source(tmp_path, """
            class Accumulator:
                def __init__(self):
                    self.total = 0.0

                def add(self, x):
                    self.total += x
            """, rules=["DNVM004"])
        assert res.active == []


# ---------------------------------------------------------------------------
# suppressions, baseline, driver, CLI


PLANTED = """
    import functools

    state = {{}}

    def poke(k, v):
        state[k] = v

    @functools.cache
    def lookup(k):{marker}
        return state.get(k)
    """


class TestSuppression:
    def test_marker_suppresses_own_and_next_line(self, tmp_path):
        res = run_source(
            tmp_path,
            PLANTED.format(marker="  # dnvm: ok(DNVM001, fixture)"),
            rules=["DNVM001"])
        assert res.active == []
        assert res.suppressed == 1

    def test_without_marker_fires(self, tmp_path):
        res = run_source(tmp_path, PLANTED.format(marker=""),
                         rules=["DNVM001"])
        assert len(res.active) == 1

    def test_malformed_marker_is_a_finding(self, tmp_path):
        res = run_source(tmp_path, """
            x = 1  # dnvm: ok(DNVM001)
            """)
        assert len(res.active) == 1
        assert res.active[0].rule == "DNVM000"
        assert "non-empty reason" in res.active[0].message

    def test_wrong_rule_marker_does_not_suppress(self, tmp_path):
        res = run_source(
            tmp_path,
            PLANTED.format(marker="  # dnvm: ok(DNVM004, wrong rule)"),
            rules=["DNVM001"])
        assert len(res.active) == 1


class TestBaseline:
    def test_round_trip(self, tmp_path):
        src_path = tmp_path / "planted.py"
        src_path.write_text(textwrap.dedent(PLANTED.format(marker="")))
        first = driver.run_paths([str(src_path)], rules=["DNVM001"])
        assert len(first.active) == 1

        baseline_path = tmp_path / "baseline.txt"
        common.write_baseline(str(baseline_path), first.findings)
        accepted = common.load_baseline(str(baseline_path))
        assert len(accepted) == 1

        second = driver.run_paths([str(src_path)], rules=["DNVM001"],
                                  baseline=accepted)
        assert second.active == []
        assert second.baselined == 1

    def test_keys_survive_line_shifts(self, tmp_path):
        src_path = tmp_path / "planted.py"
        src_path.write_text(textwrap.dedent(PLANTED.format(marker="")))
        baseline = {f.baseline_key() for f in driver.run_paths(
            [str(src_path)], rules=["DNVM001"]).findings}

        shifted = "# a new comment line\n# another\n" + \
            textwrap.dedent(PLANTED.format(marker=""))
        src_path.write_text(shifted)
        res = driver.run_paths([str(src_path)], rules=["DNVM001"],
                               baseline=baseline)
        assert res.active == []
        assert res.baselined == 1

    def test_missing_baseline_file_is_empty(self, tmp_path):
        assert common.load_baseline(str(tmp_path / "absent.txt")) == set()


class TestDriver:
    def test_syntax_error_becomes_dnvm000(self, tmp_path):
        res = run_source(tmp_path, "def broken(:\n")
        assert len(res.active) == 1
        assert res.active[0].rule == "DNVM000"

    def test_counts_by_rule(self, tmp_path):
        res = run_source(tmp_path, PLANTED.format(marker=""),
                         rules=["DNVM001"])
        assert res.counts["DNVM001"] == 1

    def test_clean_over_repro_core(self):
        """The shipped core must analyze clean with no baseline at all."""
        res = driver.run_paths([str(REPO_ROOT / "src/repro/core")])
        assert messages(res) == []
        assert res.files >= 10


class TestCLI:
    def test_strict_exit_codes(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(PLANTED.format(marker="")))
        assert cli.main([str(bad), "--strict", "--no-baseline"]) == 1
        out = capsys.readouterr().out
        assert "DNVM001" in out

        good = tmp_path / "good.py"
        good.write_text("x = 1\n")
        assert cli.main([str(good), "--strict", "--no-baseline"]) == 0

    def test_write_baseline_then_strict_passes(self, tmp_path,
                                               monkeypatch):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(PLANTED.format(marker="")))
        monkeypatch.chdir(tmp_path)
        assert cli.main([str(bad), "--write-baseline"]) == 0
        assert os.path.exists(tmp_path / common.BASELINE_DEFAULT)
        assert cli.main([str(bad), "--strict"]) == 0

    def test_unknown_rule_is_usage_error(self, tmp_path):
        with pytest.raises(SystemExit) as e:
            cli.main([str(tmp_path), "--rules", "DNVM999"])
        assert e.value.code == 2

    def test_repo_baseline_covers_src_repro(self, monkeypatch):
        """The acceptance gate itself: strict run over src/repro with the
        checked-in baseline exits 0."""
        monkeypatch.chdir(REPO_ROOT)
        assert cli.main(["src/repro", "--strict"]) == 0
