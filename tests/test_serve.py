"""Online inference subsystem (dryad_tpu/serve/).

The keystone invariant: a served prediction is BITWISE equal to the
direct ``Booster.predict`` on the same rows, no matter how the serving
layer buckets, pads, chunks, or coalesces the request — predict is
per-row arithmetic end to end, so shape games cannot change a bit.
Everything runs forced-CPU (tests/conftest.py) and stays tier-1 fast.
"""

import threading
import time

import numpy as np
import pytest

import dryad_tpu as dryad
from dryad_tpu.datasets import higgs_like
from dryad_tpu.serve import (MicroBatcher, ModelRegistry, PredictServer,
                             Request, ServeOverloaded, ServeTimeout,
                             bucket_rows, run_bench)


@pytest.fixture(scope="module")
def model():
    X, y = higgs_like(600, seed=7)
    ds = dryad.Dataset(X, y, max_bins=32)
    booster = dryad.train(dict(objective="binary", num_trees=8, num_leaves=7,
                               max_bins=32), ds, backend="cpu")
    return booster, X


@pytest.fixture(scope="module")
def model_multiclass():
    rng = np.random.default_rng(3)
    X = rng.standard_normal((500, 8)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32) + (X[:, 2] > 0.5)
    ds = dryad.Dataset(X, y, max_bins=32)
    booster = dryad.train(dict(objective="multiclass", num_class=3,
                               num_trees=4, num_leaves=7, max_bins=32),
                          ds, backend="cpu")
    return booster, X


def test_bucket_rows():
    assert [bucket_rows(n) for n in (1, 7, 8, 9, 16, 17)] == [8, 8, 8, 16, 16, 32]
    assert bucket_rows(100, 8, 64) == 64           # capped at max bucket
    with pytest.raises(ValueError):
        bucket_rows(0)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_served_predict_bitwise_parity(model, backend):
    """ISSUE satellite: padded/bucketed serve == direct predict, bitwise —
    empty batch, 1-row, bucket boundaries (8|9, 16|17), and a request
    bigger than the largest bucket (33 > 16 → chunked)."""
    booster, X = model
    server = PredictServer(backend=backend, max_batch_rows=16,
                           max_wait_ms=0.5, min_bucket=8)
    server.registry.add(booster)
    with server:
        for n in (0, 1, 7, 8, 9, 15, 16, 17, 33):
            for raw in (False, True):
                direct = booster.predict(X[:n], raw_score=raw)
                served = server.predict(X[:n], raw_score=raw)
                assert served.dtype == direct.dtype
                assert served.shape == direct.shape
                assert np.array_equal(served, direct), (backend, n, raw)
    snap = server.stats()
    assert snap["cache_compiles"] <= 2          # buckets {8, 16} only
    assert snap["cache_hits"] > 0


def test_served_binned_and_multiclass_parity(model_multiclass):
    booster, X = model_multiclass
    Xb = booster.mapper.transform(X)
    server = PredictServer(backend="cpu", max_batch_rows=64, max_wait_ms=0.5)
    server.registry.add(booster)
    with server:
        for n in (1, 9, 33):
            direct = booster.predict_binned(Xb[:n])
            served = server.predict(Xb[:n], binned=True)
            assert direct.shape == (n, 3) and np.array_equal(served, direct)


def test_registry_hot_swap_and_rollback(model, model_multiclass):
    booster_a, X = model
    booster_b, _ = model_multiclass
    reg = ModelRegistry()
    v1 = reg.add(booster_a)                             # v1 active
    v2 = reg.add(booster_b, activate=False)
    assert (reg.active_version, reg.versions()) == (v1, [v1, v2])
    reg.activate(v2)
    assert reg.active_version == v2
    assert reg.rollback() == v1 and reg.active_version == v1
    with pytest.raises(ValueError):
        reg.unload(v1)                                  # active is protected
    reg.unload(v2)
    assert reg.versions() == [v1]
    with pytest.raises(KeyError):
        reg.get(v2)
    with pytest.raises(LookupError):
        ModelRegistry().get()


def test_hot_swap_changes_served_model(model, model_multiclass):
    booster_a, X = model
    booster_b, Xm = model_multiclass
    server = PredictServer(backend="cpu", max_wait_ms=0.2)
    v1 = server.registry.add(booster_a)
    v2 = server.registry.add(booster_b, activate=False)
    with server:
        assert np.array_equal(server.predict(X[:5]), booster_a.predict(X[:5]))
        server.activate(v2)
        assert np.array_equal(server.predict(Xm[:5]), booster_b.predict(Xm[:5]))
        # pinned versions still address the inactive model
        assert np.array_equal(server.predict(X[:5], version=v1),
                              booster_a.predict(X[:5]))
        assert server.rollback() == v1
        assert np.array_equal(server.predict(X[:5]), booster_a.predict(X[:5]))


def test_registry_loads_text_binary_checkpoint(model, tmp_path):
    booster, X = model
    booster.save(str(tmp_path / "m.dryad"))
    booster.save_text(str(tmp_path / "m.txt"))
    from dryad_tpu.checkpoint import Checkpointer

    Checkpointer(str(tmp_path / "ck")).save(booster, 8)
    reg = ModelRegistry()
    v_bin = reg.load(str(tmp_path / "m.dryad"))
    v_txt = reg.load(str(tmp_path / "m.txt"))
    v_ck = reg.load_latest_checkpoint(str(tmp_path / "ck"))
    ref = booster.predict(X[:10])
    for v in (v_bin, v_txt, v_ck):
        got = reg.get(v).booster.predict(X[:10])
        assert np.array_equal(got, ref)
    with pytest.raises(FileNotFoundError):
        reg.load_latest_checkpoint(str(tmp_path / "empty_ck"))


def test_concurrent_requests_coalesce_bitwise(model):
    """Many threads in flight at once: answers stay request-exact, and the
    deadline coalescer folds them into fewer dispatches."""
    booster, X = model
    server = PredictServer(backend="cpu", max_batch_rows=128,
                           max_wait_ms=20.0, queue_size=64)
    server.registry.add(booster)
    sizes = [1, 3, 5, 8, 13]
    outs: dict[int, np.ndarray] = {}
    start = threading.Barrier(len(sizes))

    def worker(i, n):
        start.wait()
        outs[i] = server.predict(X[i:i + n])

    with server:
        threads = [threading.Thread(target=worker, args=(i, n))
                   for i, n in enumerate(sizes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for i, n in enumerate(sizes):
        assert np.array_equal(outs[i], booster.predict(X[i:i + n]))
    snap = server.stats()
    assert snap["requests"] == len(sizes)
    assert snap["batches"] < len(sizes)          # coalescing actually happened
    assert 0 < snap["batch_fill_ratio"] <= 1


def test_batcher_backpressure_and_timeout():
    """Bounded queue rejects excess load; a per-request timeout abandons a
    stuck request instead of hanging the caller."""
    release = threading.Event()

    def slow_dispatch(batch):
        release.wait(5.0)
        return [np.zeros(r.rows.shape[0], np.float32) for r in batch]

    from dryad_tpu.serve import ServeMetrics

    metrics = ServeMetrics()
    batcher = MicroBatcher(slow_dispatch, max_batch_rows=4, max_wait_ms=1.0,
                           queue_size=1, metrics=metrics)
    batcher.start()
    rows = np.zeros((2, 3), np.uint8)
    errs: list[BaseException] = []

    def blocked():
        try:
            batcher.submit(Request(rows), timeout=0.05)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    t = threading.Thread(target=blocked)
    t.start()
    time.sleep(0.2)       # worker is now stuck inside slow_dispatch
    # worker busy: the next submit queues then times out (and stays queued,
    # abandoned), so the one after bounces off the full queue
    with pytest.raises(ServeTimeout):
        batcher.submit(Request(rows), timeout=0.01)
    with pytest.raises(ServeOverloaded):
        batcher.submit(Request(rows), timeout=0.01)
    release.set()
    t.join(5.0)
    assert errs and isinstance(errs[0], ServeTimeout)
    assert metrics.timeouts >= 1 and metrics.rejected >= 1
    batcher.stop()


def test_stop_drains_stranded_requests():
    """A request enqueued behind the stop token must be failed, not left
    waiting forever on a dead worker."""
    from dryad_tpu.serve.batcher import _StopToken

    batcher = MicroBatcher(lambda b: [None] * len(b), queue_size=4)
    stranded = Request(np.zeros((1, 2), np.uint8))
    # stamped with the current generation (start() below leaves it alone —
    # no timed-out stop pending), so the worker honors it as a live stop
    # and drains what's queued behind it
    batcher._q.put(_StopToken(batcher._gen))
    batcher._q.put(stranded)
    batcher.start()
    assert stranded.event.wait(5.0)
    assert isinstance(stranded.error, ServeOverloaded)
    batcher.stop()


def test_unloaded_version_fails_only_its_group(model):
    """A batch mixing a dead pinned version with live requests fails only
    the dead group's requests."""
    booster, X = model
    server = PredictServer(backend="cpu", max_wait_ms=0.2)
    server.registry.add(booster)
    Xb = booster.mapper.transform(X[:4])
    good = Request(Xb, version=server.registry.active_version)
    dead = Request(Xb, version=99)
    results = server._dispatch([good, dead])
    assert isinstance(results[1], KeyError)
    assert np.array_equal(results[0], booster.predict(X[:4]))


def test_dispatch_error_propagates():
    def bad_dispatch(batch):
        raise RuntimeError("boom")

    batcher = MicroBatcher(bad_dispatch, max_wait_ms=0.1, queue_size=4)
    batcher.start()
    with pytest.raises(RuntimeError, match="boom"):
        batcher.submit(Request(np.zeros((1, 2), np.uint8)), timeout=5.0)
    batcher.stop()


def test_pipeline_and_serial_dispatch_agree(model):
    """The overlapped two-deep pipeline returns the same bits as the
    strictly serial loop — pipelining changes WHEN a batch runs, never
    what runs."""
    booster, X = model
    outs = {}
    for depth in (1, 2, 3):
        server = PredictServer(backend="cpu", max_batch_rows=32,
                               max_wait_ms=0.5, pipeline_depth=depth)
        server.registry.add(booster)
        with server:
            outs[depth] = [server.predict(X[:n]) for n in (1, 9, 33)]
        assert server.stats()["pipeline_depth"] == (depth if depth >= 2 else 1)
    for n_i in range(3):
        direct = booster.predict(X[: (1, 9, 33)[n_i]])
        for depth in (1, 2, 3):
            assert np.array_equal(outs[depth][n_i], direct), depth


def test_pipeline_concurrent_bitwise(model):
    """Concurrent load through the pipeline: request-exact answers while
    collector and executor overlap."""
    booster, X = model
    server = PredictServer(backend="cpu", max_batch_rows=64, max_wait_ms=5.0,
                           pipeline_depth=2, queue_size=64)
    server.registry.add(booster)
    sizes = [1, 3, 5, 8, 13, 21]
    outs: dict[int, np.ndarray] = {}
    start = threading.Barrier(len(sizes))

    def worker(i, n):
        start.wait()
        outs[i] = server.predict(X[i:i + n])

    with server:
        threads = [threading.Thread(target=worker, args=(i, n))
                   for i, n in enumerate(sizes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for i, n in enumerate(sizes):
        assert np.array_equal(outs[i], booster.predict(X[i:i + n]))


def test_registry_budget_evicts_lru_not_active(model, model_multiclass):
    """Device-memory budget: staging past the budget evicts the LRU staged
    entry; the active version is pinned; an evicted model transparently
    re-stages on its next request with bitwise-identical output; its
    metrics history survives eviction."""
    booster_a, X = model
    booster_b, Xm = model_multiclass
    reg = ModelRegistry(budget_bytes=1)       # everything non-pinned evicts
    server = PredictServer(reg, backend="tpu", max_wait_ms=0.2)
    vA = reg.add(booster_a)                   # active
    vB = reg.add(booster_b, activate=False, name="challenger")
    with server:
        outB1 = server.predict(Xm[:5], version=vB)
        eA, eB = reg.get(vA), reg.get(vB)
        assert eB.is_staged
        server.predict(X[:5])                 # stages A → B is the LRU victim
        assert not eB.is_staged, "inactive LRU entry must be evicted"
        assert eA.is_staged, "active version is pinned"
        reqs_before = server.stats()["models"][vB]["requests"]
        outB2 = server.predict(Xm[:5], version=vB)   # transparent re-stage
        assert eB.is_staged
        assert np.array_equal(outB1, outB2)
        assert np.array_equal(outB2, booster_b.predict(Xm[:5]))
    snap = server.stats()
    assert snap["evictions"] >= 1 and snap["restages"] >= 1
    mB = snap["models"][vB]
    assert mB["evictions"] >= 1 and mB["restages"] >= 1
    assert mB["requests"] == reqs_before + 1, "stats must survive eviction"
    assert snap["memory"]["budget_bytes"] == 1


def test_unbudgeted_registry_never_evicts(model, model_multiclass):
    booster_a, X = model
    booster_b, Xm = model_multiclass
    server = PredictServer(backend="tpu", max_wait_ms=0.2)
    vA = server.registry.add(booster_a)
    vB = server.registry.add(booster_b, activate=False)
    with server:
        server.predict(Xm[:5], version=vB)
        server.predict(X[:5], version=vA)
    assert server.registry.get(vA).is_staged
    assert server.registry.get(vB).is_staged
    assert server.stats()["evictions"] == 0
    assert server.stats()["memory"]["staged_versions"] == [vA, vB]


def test_named_model_routing(model, model_multiclass):
    """Multi-model co-serving routes by name; re-adding under the same
    name repoints the alias (deploy gesture); unload drops the alias."""
    booster_a, X = model
    booster_b, Xm = model_multiclass
    server = PredictServer(backend="cpu", max_wait_ms=0.2)
    v1 = server.registry.add(booster_a, name="champion")
    v2 = server.registry.add(booster_b, activate=False, name="challenger")
    with server:
        assert np.array_equal(server.predict(X[:5], model="champion"),
                              booster_a.predict(X[:5]))
        assert np.array_equal(server.predict(Xm[:5], model="challenger"),
                              booster_b.predict(Xm[:5]))
        with pytest.raises(KeyError):
            server.predict(X[:2], model="nobody")
        with pytest.raises(ValueError):
            server.predict(X[:2], version=v1, model="champion")
        v3 = server.registry.add(booster_b, activate=False, name="champion")
        assert np.array_equal(server.predict(Xm[:5], model="champion"),
                              booster_b.predict(Xm[:5]))
        assert server.registry.aliases() == {"champion": v3,
                                             "challenger": v2}
        server.registry.unload(v2)
        assert server.registry.aliases() == {"champion": v3}


def test_unload_frees_staged_and_cache_entries(model, model_multiclass):
    """Unloading a co-served model must actually release it: the registry
    drops its staged/device arrays immediately (the budget can never
    reach them again) and server.unload purges the compiled-cache
    closures that would otherwise pin the entry alive."""
    booster_a, X = model
    booster_b, Xm = model_multiclass
    server = PredictServer(backend="tpu", max_wait_ms=0.2)
    vA = server.registry.add(booster_a)
    vB = server.registry.add(booster_b, activate=False, name="retired")
    with server:
        server.predict(Xm[:5], version=vB)
        entry_b = server.registry.get(vB)
        assert entry_b.is_staged
        assert any(k[0] == vB for k in server.cache._fns)
        server.unload(vB)
        assert not entry_b.is_staged, "unload must free the staged arrays"
        assert not any(k[0] == vB for k in server.cache._fns)
        assert not any(k[0] == vB for k in server.cache._warm)
        assert server.registry.aliases() == {}
        # the survivor still serves, bitwise
        assert np.array_equal(server.predict(X[:5]), booster_a.predict(X[:5]))


def test_malformed_request_fails_alone(model):
    """Width validation happens at submit time, in the caller's thread:
    binning is deferred into the coalesced _prepare, so without the check
    one wrong-width request would poison every co-batched request of the
    same version."""
    booster, X = model
    server = PredictServer(backend="cpu", max_batch_rows=64, max_wait_ms=20.0)
    server.registry.add(booster)
    results: dict = {}
    start = threading.Barrier(2)

    def good():
        start.wait()
        results["good"] = server.predict(X[:5])

    def bad():
        start.wait()
        try:
            server.predict(X[:3, :-1])          # one feature short
            results["bad"] = "no error"
        except ValueError as e:
            results["bad"] = e

    with server:
        threads = [threading.Thread(target=good), threading.Thread(target=bad)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert isinstance(results["bad"], ValueError)
        assert np.array_equal(results["good"], booster.predict(X[:5]))
        with pytest.raises(ValueError, match="expected"):
            server.predict(booster.mapper.transform(X[:2])[:, :-1],
                           binned=True)


def test_per_model_stats(model, model_multiclass):
    booster_a, X = model
    booster_b, Xm = model_multiclass
    server = PredictServer(backend="cpu", max_wait_ms=0.2)
    v1 = server.registry.add(booster_a)
    v2 = server.registry.add(booster_b, activate=False)
    with server:
        for _ in range(3):
            server.predict(X[:4], version=v1)
        server.predict(Xm[:7], version=v2)
    snap = server.stats()
    assert snap["models"][v1]["requests"] == 3
    assert snap["models"][v1]["rows"] == 12
    assert snap["models"][v2]["requests"] == 1
    assert snap["models"][v2]["rows"] == 7
    assert snap["models"][v2]["p99_ms"] >= 0.0


def test_bench_compare_pipeline_vs_serial(model):
    """The A/B harness reports both arms + the speedup field and stays
    recompile-free; the ≥1.3× acceptance number itself is recorded by
    scripts/bench_serve.py --compare (timing asserts would be flaky in
    a shared CI container)."""
    from dryad_tpu.serve import run_bench_compare

    booster, X = model
    report = run_bench_compare(booster, backend="cpu", clients=3,
                               duration_s=0.3, sizes=(1, 5, 9),
                               max_batch_rows=32, max_wait_ms=1.0, seed=0,
                               arms=2, feature_pool=X)
    assert report["recompiles_after_warmup"] == 0
    assert report["serial"]["pipeline_depth"] == 1
    assert report["pipeline"]["pipeline_depth"] == 2
    assert report["pipeline_speedup"] > 0
    for arm in ("serial", "pipeline"):
        assert report[arm]["bench_arms"] == 2
        assert "spread_rows_per_s" in report[arm]
        assert isinstance(report[arm]["suspect_capture"], bool)


def test_http_structured_request_logging(model):
    """--log-requests emits one JSON line per request with version, rows,
    latency, and status (including error statuses)."""
    import io
    import json
    import urllib.error
    import urllib.request

    from dryad_tpu.serve.http import make_http_server

    booster, X = model
    server = PredictServer(backend="cpu", max_wait_ms=0.5)
    server.registry.add(booster)
    stream = io.StringIO()
    httpd = make_http_server(server, port=0, log_requests=True,
                             log_stream=stream)
    port = httpd.server_address[1]
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=json.dumps({"rows": X[:3].tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=10).read()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/predict",
                data=json.dumps({"rows": X[:2].tolist(),
                                 "version": 99}).encode(),
                headers={"Content-Type": "application/json"}), timeout=10)
        urllib.request.urlopen(f"http://127.0.0.1:{port}/stats",
                               timeout=10).read()
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.stop()
    lines = [json.loads(l) for l in stream.getvalue().splitlines()]
    assert len(lines) == 3
    # a line is written after its response is sent, on the handler's own
    # thread, so the next request's line can overtake it: match by content
    by_key = {(l["path"], l["status"]): l for l in lines}
    assert set(by_key) == {("/predict", 200), ("/predict", 400),
                           ("/stats", 200)}
    ok = by_key["/predict", 200]
    assert ok["version"] == 1 and ok["rows"] == 3
    assert ok["latency_ms"] >= 0
    assert by_key["/predict", 400]["version"] is None


def test_bench_serve_zero_recompiles_after_warmup(model):
    """Acceptance gate: the closed-loop bench on forced CPU reports zero
    recompiles after warmup — warm traffic only ever hits warm buckets."""
    booster, X = model
    report = run_bench(booster, backend="cpu", clients=3, duration_s=0.5,
                       sizes=(1, 5, 9, 17), max_batch_rows=32,
                       max_wait_ms=1.0, seed=0, feature_pool=X)
    assert report["recompiles_after_warmup"] == 0
    assert report["cache_hits"] > 0
    assert report["bench_requests"] > 0
    assert report["cache_compiles"] == 3         # buckets {8, 16, 32}, once


def test_http_round_trip(model):
    """Loopback smoke of the HTTP front end: /predict parity (through JSON
    — exact, since Python floats widen f32 losslessly), /stats, /models,
    and error mapping for an unknown version."""
    import json
    import urllib.error
    import urllib.request

    from dryad_tpu.serve.http import make_http_server

    booster, X = model
    server = PredictServer(backend="cpu", max_wait_ms=0.5)
    server.registry.add(booster)
    httpd = make_http_server(server, port=0)
    port = httpd.server_address[1]
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()

    def post(path, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(req, timeout=10).read())

    try:
        out = post("/predict", {"rows": X[:5].tolist()})
        assert np.array_equal(np.asarray(out["predictions"], np.float32),
                              booster.predict(X[:5]))
        stats = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stats", timeout=10).read())
        assert stats["requests"] >= 1 and stats["backend"] == "cpu"
        models = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/models", timeout=10).read())
        assert models["active"] in models["versions"]
        assert out["version"] == models["active"]
        # pre-binned rows arrive as JSON ints and must be cast to the
        # model's bin dtype, not float32
        Xb = booster.mapper.transform(X[:3])
        binned_out = post("/predict", {"rows": Xb.tolist(), "binned": True})
        assert np.array_equal(np.asarray(binned_out["predictions"], np.float32),
                              booster.predict_binned(Xb))
        with pytest.raises(urllib.error.HTTPError) as err:
            post("/predict", {"rows": X[:2].tolist(), "version": 99})
        assert err.value.code == 400
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.stop()


def test_stop_timeout_keeps_stuck_worker_handle():
    """The r8-flagged stop() race: when join() times out because the worker
    is stuck in a stalled dispatch, the thread handle must NOT be cleared —
    a cleared handle would let the next start() race a SECOND collector
    onto the same queue.  Once the worker really exits, stop() clears it."""
    release = threading.Event()

    def stuck_dispatch(batch):
        release.wait(30.0)
        return [np.zeros(r.rows.shape[0], np.float32) for r in batch]

    batcher = MicroBatcher(stuck_dispatch, max_batch_rows=4, max_wait_ms=0.5,
                           queue_size=4)
    batcher.start()
    req = Request(np.zeros((1, 3), np.uint8))
    batcher._q.put_nowait(req)
    deadline = time.monotonic() + 5.0
    while not batcher._q.empty() and time.monotonic() < deadline:
        time.sleep(0.005)          # worker has dequeued: now inside dispatch
    worker = batcher._thread
    assert worker is not None and worker.is_alive()

    batcher.stop(timeout=0.05)     # join times out — worker still stuck
    assert batcher._thread is worker, "handle cleared while worker alive"
    batcher.start()                # must NOT spawn a second collector
    assert batcher._thread is worker

    release.set()
    assert req.event.wait(5.0)     # the stuck dispatch completes delivery
    batcher.stop(timeout=5.0)
    assert batcher._thread is None


def test_restart_after_stop_timeout_keeps_serving():
    """start() after a timed-out stop() CANCELS the pending stop: the
    queued stop token goes stale, so when the stuck dispatch finally
    completes the worker ignores it and keeps collecting — without the
    generation stamp it would honor the stale token, exit, and leave the
    queue permanently collector-less (no path re-runs start())."""
    entered = threading.Event()
    release = threading.Event()
    stuck_once = []

    def dispatch(batch):
        if not stuck_once:
            stuck_once.append(1)
            entered.set()
            release.wait(30.0)
        return [np.zeros(r.rows.shape[0], np.float32) for r in batch]

    batcher = MicroBatcher(dispatch, max_batch_rows=4, max_wait_ms=0.5,
                           queue_size=4)
    batcher.start()
    req = Request(np.zeros((1, 3), np.uint8))
    batcher._q.put_nowait(req)
    # synchronize on DISPATCH entry (not _q.empty(), which can observe the
    # worker still inside _collect's coalesce window — a stop token eaten
    # there latches stopping before start() can invalidate it)
    assert entered.wait(5.0)       # worker is inside the stalled dispatch
    worker = batcher._thread

    batcher.stop(timeout=0.05)     # join times out; stop token stays queued
    batcher.start()                # operator restart — must cancel the stop
    release.set()
    assert req.event.wait(5.0)

    # the SAME worker must still be collecting: a fresh request round-trips
    out = batcher.submit(Request(np.zeros((2, 3), np.uint8)), timeout=5.0)
    assert out.shape == (2,)
    assert batcher._thread is worker and worker.is_alive()
    batcher.stop(timeout=5.0)      # un-cancelled stop still works
    assert batcher._thread is None


def test_plain_start_does_not_cancel_pending_stop():
    """PredictServer.predict() auto-calls start() on every request, so a
    start() against a live batcher with NO timed-out stop must not bump
    the stop generation — otherwise any concurrent request would silently
    cancel an operator shutdown and stop() would hang its full join
    timeout with the collector leaked."""
    batcher = MicroBatcher(
        lambda b: [np.zeros(r.rows.shape[0], np.float32) for r in b],
        max_batch_rows=4, max_wait_ms=0.5, queue_size=4)
    batcher.start()
    gen = batcher._gen
    batcher.start()                # per-request auto-start: must be inert
    batcher.start()
    assert batcher._gen == gen
    batcher.stop(timeout=5.0)      # the stop token is still honored
    assert batcher._thread is None


def test_http_bearer_auth_and_metrics_endpoint(model):
    """--auth-token: 401 without/with a wrong bearer on every endpoint,
    200 with the right one; /healthz stays open; /metrics exposes the
    shared registry; the /stats snapshot shape is the pre-obs contract."""
    import json
    import urllib.error
    import urllib.request

    from dryad_tpu.serve.http import make_http_server

    booster, X = model
    server = PredictServer(backend="cpu", max_wait_ms=0.5)
    server.registry.add(booster)
    httpd = make_http_server(server, port=0, auth_token="tok3n")
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{port}"

    def get(path, token=None):
        headers = {"Authorization": f"Bearer {token}"} if token else {}
        return urllib.request.urlopen(
            urllib.request.Request(base + path, headers=headers), timeout=10)

    try:
        assert json.loads(get("/healthz").read()) == {"ok": True}
        for path in ("/stats", "/models", "/metrics"):
            with pytest.raises(urllib.error.HTTPError) as err:
                get(path)
            assert err.value.code == 401
        with pytest.raises(urllib.error.HTTPError) as err:
            get("/stats", token="wrong")
        assert err.value.code == 401
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(urllib.request.Request(
                base + "/predict",
                data=json.dumps({"rows": X[:2].tolist()}).encode(),
                headers={"Content-Type": "application/json"}), timeout=10)
        assert err.value.code == 401

        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps({"rows": X[:2].tolist()}).encode(),
            headers={"Content-Type": "application/json",
                     "Authorization": "Bearer tok3n"})
        out = json.loads(urllib.request.urlopen(req, timeout=10).read())
        assert np.array_equal(np.asarray(out["predictions"], np.float32),
                              booster.predict(X[:2]))
        stats = json.loads(get("/stats", token="tok3n").read())
        assert stats["requests"] >= 1      # unchanged pre-obs snapshot shape
        assert "counters" not in stats
        text = get("/metrics", token="tok3n").read().decode()
        assert "# TYPE dryad_serve_requests_total counter" in text
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.stop()
