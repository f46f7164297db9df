"""Tests for the concurrent sweep service (repro/sweep/service.py) and
the LM ``@b<n>`` scenario namespace that rides with it.

Families:

  coalesce   seeded property test: N concurrent compatible specs through
             the coalescing window match their individual ``run()``
             results at <= 1e-12, delivered exactly once; incompatible
             platform axes pass through as separate evaluations;
  cache      result cache hits/misses, canonical-key stability, bounded
             eviction;
  lifecycle  graceful shutdown drains a slow in-flight request and the
             coalescing window before the worker stops; handle() after
             close answers with an error document;
  transport  HTTP (ephemeral port) and unix-socket servers speak the
             same handler as stdin; subprocess SIGTERM exits 0 with
             --stats-on-exit output after answering real traffic;
  stats      the {"op": "stats"} document: request counters, cache and
             coalesce counters, cells/elapsed_ms percentiles;
  backpressure  oversize documents answer 413 (HTTP refuses before
             reading the body), the bounded admission gate answers 429
             with cache hits and ops exempt, and both are counted in
             stats()["limits"];
  lm         lm/<arch>/<shape>@b<n> resolution, inverse, registry names,
             and end-to-end service evaluation of batch-override cells.
"""

import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

import pytest

from repro import scenarios, sweep_cli
from repro.core import sweep
from repro.core.sweep import SymbolicSweepSpec, spec_union
from repro.sweep import client
from repro.sweep import service as service_mod
from repro.sweep.service import (
    Coalescer,
    ResultCache,
    SweepService,
    evaluate_spec,
    spec_key,
)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# A small scenario/design pool so the whole module compiles a handful of
# bucketed fold shapes at most (shapes are shared across tests).
SCENARIOS = ("cnn/alexnet/infer@b4", "cnn/alexnet/train@b64",
             "cnn/squeezenet/infer@b4", "cnn/resnet18/train@b64")
CAPS = ("3MB", "8MB")


def designs_at(caps=("3MB",)):
    # full mem triple per capacity so every spec carries its own baseline
    return [f"{m}@{c}" for c in caps for m in ("sram", "stt", "sot")]


def doc(name, scens=SCENARIOS[:2], designs=None, platforms=("gtx-1080ti",)):
    return {"schema": "deepnvm.sweepspec/2", "name": name,
            "scenarios": list(scens),
            "designs": list(designs or designs_at()),
            "platforms": list(platforms), "baseline_mem": "sram"}


def assert_doc_close(got, want, tol=1e-12):
    """Recursive numeric comparison for nested summary documents."""
    if isinstance(want, dict):
        assert set(got) == set(want)
        for k in want:
            assert_doc_close(got[k], want[k], tol)
    elif isinstance(want, float):
        assert got == pytest.approx(want, rel=tol, nan_ok=True)
    else:
        assert got == want


def assert_rows_match(got, want, tol=1e-12):
    """Service rows vs sweep.run rows: same shape, same labels, floats
    within rel tol (the coalesced/bucketed path reassociates sums)."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert set(g) == set(w)
        for k, wv in w.items():
            if isinstance(wv, float):
                assert g[k] == pytest.approx(wv, rel=tol, nan_ok=True), k
            else:
                assert g[k] == wv, k


# ---------------------------------------------------------------------------
# Coalescing: parity, exactly-once, passthrough
# ---------------------------------------------------------------------------


def _fire_concurrently(svc, docs, want=("rows", "summary")):
    """Submit every envelope from its own thread, released together so
    they land inside one coalescing window."""
    barrier = threading.Barrier(len(docs))
    responses = [None] * len(docs)

    def fire(i, d):
        barrier.wait()
        responses[i] = svc.handle({"spec": d, "want": list(want)})

    threads = [threading.Thread(target=fire, args=(i, d))
               for i, d in enumerate(docs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return responses


def test_coalesced_specs_match_individual_runs():
    # seeded property test (no hypothesis in the image): random compatible
    # spec subsets, fired concurrently, must match sweep.run() per member
    rng = random.Random(20260808)
    svc = SweepService(window_ms=250.0)
    try:
        for rnd in range(3):
            docs = []
            for i in range(4):
                scens = rng.sample(SCENARIOS,
                                   rng.randint(1, len(SCENARIOS)))
                caps = rng.choice([("3MB",), ("8MB",), CAPS])
                docs.append(doc(f"prop-{rnd}-{i}", scens,
                                designs_at(caps)))
            responses = _fire_concurrently(svc, docs)
            # exactly-once: every request got exactly one response
            assert all(r is not None for r in responses)
            for d, resp in zip(docs, responses):
                assert resp["ok"], resp.get("error")
                expected = sweep.run(SymbolicSweepSpec.from_json(d)
                                     .resolve())
                assert_rows_match(resp["rows"], expected.rows())
                assert_doc_close(resp["summary"], expected.summary())
        assert svc.coalescer.coalesced_requests > 0
        assert svc.coalescer.max_group >= 2
        assert svc.requests == svc.ok == 3 * 4
    finally:
        svc.close()


def test_identical_inflight_requests_dedup():
    d = doc("dedup-spec")
    svc = SweepService(window_ms=250.0)
    try:
        responses = _fire_concurrently(svc, [d, d, d], want=("summary",))
        assert all(r["ok"] for r in responses)
        # identical documents share one queue entry and one evaluation
        assert all(r["source"] == "coalesced" for r in responses)
        assert svc.coalescer.deduped_requests == 2
        assert svc.coalescer.batches == 1
        assert_doc_close(responses[0]["summary"], responses[1]["summary"],
                         tol=0.0)
    finally:
        svc.close()


def test_incompatible_platforms_pass_through():
    a = doc("pt-gtx", SCENARIOS[:1], platforms=("gtx-1080ti",))
    b = doc("pt-tpu", SCENARIOS[:1], platforms=("tpu-v5e",))
    svc = SweepService(window_ms=250.0)
    try:
        responses = _fire_concurrently(svc, [a, b], want=("summary",))
        assert all(r["ok"] for r in responses)
        # same batch, but different platform axes -> separate evaluations
        assert all(r["source"] == "evaluated" for r in responses)
        assert svc.coalescer.coalesced_requests == 0
    finally:
        svc.close()
    with pytest.raises(ValueError, match="platform axis"):
        spec_union([SymbolicSweepSpec.from_json(a).resolve(),
                    SymbolicSweepSpec.from_json(b).resolve()])


def test_coalescer_delivers_errors_exactly_once():
    boom = RuntimeError("engine down")

    def failing(spec):
        raise boom

    co = Coalescer(evaluate=failing, window_ms=0.0)
    try:
        spec = SymbolicSweepSpec.from_json(doc("err")).resolve()
        with pytest.raises(RuntimeError, match="engine down"):
            co.submit(spec)
    finally:
        co.close()
    with pytest.raises(RuntimeError, match="closed"):
        co.submit(spec)


# ---------------------------------------------------------------------------
# Result cache
# ---------------------------------------------------------------------------


def test_result_cache_hits_and_spec_key_stability():
    d = doc("cache-spec")
    svc = SweepService(window_ms=0.0)
    try:
        first = svc.handle({"spec": d, "want": ["summary"]})
        second = svc.handle({"spec": d, "want": ["rows"]})
        assert first["ok"] and second["ok"]
        assert first["source"] == "evaluated"
        assert second["source"] == "cache"     # want differs, spec doesn't
        assert svc.cache.hits == 1 and svc.cache.misses == 1
    finally:
        svc.close()
    sym = SymbolicSweepSpec.from_json(d)
    assert spec_key(sym) == spec_key(SymbolicSweepSpec.from_json(
        json.loads(json.dumps(d))))


def test_result_cache_bounded_eviction():
    cache = ResultCache(maxsize=2)
    for i in range(4):
        cache.put(f"k{i}", f"r{i}")
    assert len(cache) == 2
    assert cache.get("k0") is None and cache.get("k3") == "r3"
    assert (cache.hits, cache.misses) == (1, 1)


# ---------------------------------------------------------------------------
# Lifecycle: graceful shutdown
# ---------------------------------------------------------------------------


def test_close_drains_slow_inflight_request():
    release = threading.Event()

    def slow(spec):
        release.wait(5.0)
        return evaluate_spec(spec)

    svc = SweepService(window_ms=50.0, evaluate=slow)
    responses = []

    def transport():
        with svc.track():   # what every real transport does
            responses.append(svc.handle({"spec": doc("slow-spec"),
                                         "want": ["summary"]}))

    t = threading.Thread(target=transport)
    t.start()
    time.sleep(0.15)        # let the request enter the coalescing window
    release.set()
    svc.close()             # must drain: the response is delivered first
    t.join(10.0)
    assert not t.is_alive()
    assert len(responses) == 1 and responses[0]["ok"]
    # after close the service refuses evaluation but still answers
    post = svc.handle({"spec": doc("post-close"), "want": ["summary"]})
    assert not post["ok"] and "closed" in post["error"]
    svc.close()             # idempotent


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------


def test_stats_document_and_ops():
    svc = SweepService(window_ms=0.0)
    try:
        assert svc.handle({"op": "ping"}) == {"ok": True, "op": "ping"}
        bad = svc.handle({"op": "reboot"})
        assert not bad["ok"] and "unknown op" in bad["error"]
        d = doc("stats-spec", SCENARIOS[:1])
        svc.handle({"spec": d})
        svc.handle({"spec": d})
        svc.handle({"spec": {"schema": "bogus"}})
        stats = svc.handle({"op": "stats"})["stats"]
        # the unknown-op error above counts too: 4 requests, 2 ok
        assert stats["requests"] == {"total": 4, "ok": 2, "errors": 2}
        assert stats["result_cache"]["hits"] == 1
        assert stats["result_cache"]["misses"] == 1
        assert stats["coalesce"]["enabled"]
        assert stats["cells"]["total"] == 2 * 1 * 3  # 2 ok x 1 scen x 3 des
        assert stats["cells"]["p50"] == 3.0
        assert stats["elapsed_ms"]["p50"] > 0
        assert stats["elapsed_ms"]["p95"] >= stats["elapsed_ms"]["p50"]
        json.dumps(stats)   # the whole document must serialize
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# Transports
# ---------------------------------------------------------------------------


def test_http_transport_roundtrip():
    svc = SweepService(window_ms=5.0)
    srv = service_mod.SweepHTTPServer(("127.0.0.1", 0), svc)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"127.0.0.1:{port}"
    try:
        assert client.wait_ready(url, timeout=10.0)
        resp = client.http_request(url, {"spec": doc("http-spec"),
                                         "want": ["summary"]})
        assert resp["ok"] and "summary" in resp
        bad = client.http_request(url, {"spec": {"schema": "bogus"}})
        assert not bad["ok"] and "error" in bad
        stats = client.http_stats(url)
        assert stats["ok"] and stats["stats"]["requests"]["total"] == 2
    finally:
        srv.shutdown()
        srv.server_close()
        svc.close()


@pytest.mark.skipif(service_mod.SweepUnixServer is None,
                    reason="no AF_UNIX on this platform")
def test_unix_transport_roundtrip(tmp_path):
    path = str(tmp_path / "sweep.sock")
    svc = SweepService(window_ms=5.0)
    srv = service_mod.SweepUnixServer(path, svc)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        resps = client.unix_request(path, [
            {"spec": doc("unix-spec"), "want": ["summary"]},
            {"op": "stats"},
            {"spec": {"schema": "bogus"}},
        ])
        assert resps[0]["ok"] and "summary" in resps[0]
        assert resps[1]["ok"] and resps[1]["op"] == "stats"
        assert not resps[2]["ok"]
    finally:
        srv.shutdown()
        srv.server_close()
        svc.close()


def test_serve_subprocess_sigterm_graceful(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax-cache")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.sweep", "serve",
         "--http", "127.0.0.1:0", "--stats-on-exit"],
        cwd=ROOT, env=env, stdin=subprocess.DEVNULL,
        stderr=subprocess.PIPE, text=True)
    try:
        url = None
        for _ in range(200):
            line = proc.stderr.readline()
            if not line:
                break
            if line.startswith("listening on http://"):
                url = line.split("http://", 1)[1].strip()
                break
        assert url, "server never reported its address"
        resp = client.http_request(
            url, {"spec": doc("sigterm-spec", SCENARIOS[:1]),
                  "want": ["summary"]}, timeout=120.0)
        assert resp["ok"]
        proc.send_signal(signal.SIGTERM)
        _, err = proc.communicate(timeout=60.0)
        assert proc.returncode == 0
        stats = json.loads(err)
        assert stats["requests"]["ok"] >= 1
        # the CLI keeps its compiles where the environment says
        assert os.listdir(tmp_path / "jax-cache")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()


@pytest.fixture
def restore_compile_cache_config():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("env,flag", [
    pytest.param("env-cache", None, id="env"),
    pytest.param(None, None, id="default"),
    pytest.param(None, "flag-cache", id="flag"),
    pytest.param("env-cache", "env-cache", id="flag-agrees-with-env"),
])
def test_compilation_cache_placement(monkeypatch, tmp_path, env, flag,
                                     restore_compile_cache_config):
    """JAX_COMPILATION_CACHE_DIR, where set, is the cache; otherwise the
    directory asked for, else the fixed .jax_cache/ at the checkout root."""
    import jax
    import jax.numpy as jnp

    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(tmp_path / (env or flag)) if env or flag \
        else os.path.join(ROOT, ".jax_cache")
    got = service_mod.enable_compilation_cache(
        str(tmp_path / flag) if flag else None)
    assert got == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)
    if env or flag:
        fn = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
        fn(jnp.ones(7)).block_until_ready()
        assert os.listdir(want)


def test_compile_cache_flag_contradicting_env_is_an_error(
        monkeypatch, tmp_path, capsys, restore_compile_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    with pytest.raises(ValueError, match="contradicts"):
        service_mod.enable_compilation_cache(str(tmp_path / "other"))
    with pytest.raises(SystemExit) as exc:
        sweep_cli.main(["serve", "--compile-cache", str(tmp_path / "other")])
    assert exc.value.code == 2
    assert "contradicts" in capsys.readouterr().err
    assert not (tmp_path / "other").exists()


# ---------------------------------------------------------------------------
# LM @b<n> scenario namespace
# ---------------------------------------------------------------------------


def test_lm_batch_override_resolve_and_inverse():
    base = scenarios.resolve("lm/qwen3-14b/prefill_32k")
    s8 = scenarios.resolve("lm/qwen3-14b/prefill_32k@b8")
    assert s8.batch == 8
    assert s8.workload == "qwen3-14b/prefill_32k@b8"
    assert scenarios.name_of(s8) == "lm/qwen3-14b/prefill_32k@b8"
    assert scenarios.resolve("lm/qwen3-14b/prefill_32k@b8") is s8
    assert s8 is not base
    # both cells can share one scenario axis (distinct scenario keys)
    from repro.core.tech import GTX_1080TI
    spec = sweep.SweepSpec(name="lm-b", scenarios=(base, s8),
                           designs=sweep.design_grid(("sram", "stt"),
                                                     (3.0,)),
                           platforms=(GTX_1080TI,))
    assert len(spec.scenarios) == 2


def test_lm_batch_override_errors():
    for bad in ("lm/qwen3-14b/prefill_32k@b0",
                "lm/qwen3-14b/prefill_32k@bx",
                "lm/qwen3-14b/prefill_32k@b-1"):
        with pytest.raises(ValueError):
            scenarios.resolve(bad)
    with pytest.raises(ValueError, match="positive int"):
        scenarios.lm_traffic("qwen3-14b", "prefill_32k", batch=0)


def test_lm_batch_names_registered():
    names = scenarios.names()
    assert "lm/qwen3-14b/prefill_32k" in names
    for b in scenarios.LM_BATCHES:
        assert f"lm/qwen3-14b/prefill_32k@b{b}" in names
    # every emitted name resolves and round-trips
    for name in names:
        if name.startswith("lm/") and "@b8" in name:
            assert scenarios.name_of(scenarios.resolve(name)) == name


def test_lm_batch_cells_through_service():
    d = doc("lm-b-mix",
            scens=("lm/qwen3-14b/decode_32k", "lm/qwen3-14b/decode_32k@b32"),
            designs=designs_at(("3MB",)))
    svc = SweepService(window_ms=0.0)
    try:
        resp = svc.handle({"spec": d, "want": ["rows"]})
        assert resp["ok"], resp.get("error")
        expected = sweep.run(SymbolicSweepSpec.from_json(d).resolve())
        assert_rows_match(resp["rows"], expected.rows())
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# Backpressure: size limit (413) and admission gate (429)
# ---------------------------------------------------------------------------


def test_oversize_request_refused_with_413():
    svc = SweepService(window_ms=0.0, max_body_bytes=128)
    try:
        resp = svc.handle("x" * 256)
        assert resp["ok"] is False
        assert resp["status"] == 413
        assert "RequestTooLarge" in resp["error"]
        limits = svc.stats()["limits"]
        assert limits["rejected_too_large"] == 1
        assert limits["max_body_bytes"] == 128
        # a normally-sized request still works on the same service
        ok = svc.handle(json.dumps({"op": "ping"}))
        assert ok["ok"]
    finally:
        svc.close()


def test_overload_refused_with_429_and_cache_hits_exempt():
    release = threading.Event()

    def slow(spec):
        release.wait(timeout=60.0)
        return evaluate_spec(spec)

    svc = SweepService(window_ms=0.0, coalesce=False, evaluate=slow,
                       max_pending=1)
    warm = doc("bp-warm")
    try:
        # warm one result into the cache (no contention yet)
        release.set()
        assert svc.handle(warm)["ok"]
        release.clear()

        # occupy the single admission slot with a slow evaluation
        first = {}
        t = threading.Thread(
            target=lambda: first.update(resp=svc.handle(doc("bp-slow"))))
        t.start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with svc._lock:
                if svc._pending:
                    break
            time.sleep(0.01)

        # a second evaluation is refused with 429...
        refused = svc.handle(doc("bp-refused"))
        assert refused["ok"] is False
        assert refused["status"] == 429
        assert "ServiceOverloaded" in refused["error"]
        # ...but ops and cache hits are never refused
        assert svc.handle({"op": "stats"})["ok"]
        hit = svc.handle(warm)
        assert hit["ok"] and hit["source"] == "cache"

        release.set()
        t.join(timeout=60.0)
        assert first["resp"]["ok"]
        limits = svc.stats()["limits"]
        assert limits["rejected_overloaded"] == 1
        assert limits["pending"] == 0
    finally:
        release.set()
        svc.close()


def test_http_oversize_body_refused_before_read():
    svc = SweepService(window_ms=0.0, max_body_bytes=512)
    srv = service_mod.SweepHTTPServer(("127.0.0.1", 0), svc)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"127.0.0.1:{port}"
    try:
        assert client.wait_ready(url, timeout=10.0)
        big = doc("http-too-big",
                  scens=tuple(SCENARIOS) * 40,
                  designs=designs_at(CAPS) * 40)
        assert len(json.dumps(big)) > 512
        resp = client.http_request(url, big)
        assert resp["ok"] is False
        assert resp["status"] == 413
        small = client.http_request(url, {"op": "ping"})
        assert small["ok"]
        stats = client.http_stats(url)["stats"]["limits"]
        assert stats["rejected_too_large"] == 1
    finally:
        srv.shutdown()
        srv.server_close()
        svc.close()
