"""The traced run's host spans: a target the program no longer has is
left out and named, and every wrapped function is put back.  Runs on the
CPU."""

from chipbench import spans


def test_install_skips_missing_targets_and_uninstall_restores(monkeypatch):
    from repro.core import engine, sweep

    before = (sweep.merge_results, engine.DesignTable.__dict__["tuned_index"])
    monkeypatch.setattr(spans, "WRAPPED", (
        ("repro.core.sweep", "merge_results", "merge_results"),
        ("repro.core.engine", "DesignTable.tuned_index", "tuned_index"),
        ("repro.core.sweep", "no_such_function", "gone"),
        ("repro.core.engine", "NoSuchClass.method", "gone"),
        ("repro.no_such_module", "f", "gone"),
        ("repro.core.engine", "ORGS", "not_callable"),
    ))
    try:
        skipped = spans.install()
        assert skipped == ["repro.core.sweep.no_such_function",
                           "repro.core.engine.NoSuchClass.method",
                           "repro.no_such_module.f",
                           "repro.core.engine.ORGS"]
        assert sweep.merge_results is not before[0]
        assert sweep.merge_results.__wrapped__ is before[0]
    finally:
        spans.uninstall()
    assert (sweep.merge_results,
            engine.DesignTable.__dict__["tuned_index"]) == before


def test_every_shipped_target_is_there():
    """The program as it stands has every span target; a change that
    moves one shows here first (the traced run then goes on without it)."""
    try:
        assert spans.install() == []
    finally:
        spans.uninstall()
