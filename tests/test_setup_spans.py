"""Set-up on the program's own clock (PR 36).

``Dataset`` times its sketch and its binning, ``train_device`` its set-up
region (``train.setup`` with children ``upload`` and ``plan``), the compile
boundary's own work (``capture``) and the calibration wait
(``train.calibrate``); ``introspect``'s one ``jax.monitoring`` listener books
what jit did by phase and what the persistent cache did by result under the
thread's program family, and the family is cleared when the job leaves."""

import tracemalloc

import jax
import jax.monitoring
import numpy as np
import pytest

import dryad_tpu as dryad
from dryad_tpu.datasets import higgs_like
from dryad_tpu.engine import introspect
from dryad_tpu.obs import Registry, set_default_registry
from dryad_tpu.obs import spans as S

BASE = dict(objective="binary", num_leaves=7, max_bins=32, seed=3, min_data_in_leaf=5)
JIT = "dryad_prog_jit_seconds_total"
CACHE = "dryad_prog_cache_total"


@pytest.fixture()
def fresh_registry():
    reg = Registry()
    old = set_default_registry(reg)
    yield reg
    set_default_registry(old)
    introspect.attribute(None)


@pytest.fixture(scope="module")
def tables():
    X, y = higgs_like(2400, seed=36)
    return X[:2000], y[:2000], X[2000:], y[2000:]


def counters(reg, name):
    return reg.snapshot()["counters"].get(name, {})


def test_dataset_sketches_once_and_bins_each_table(tables, fresh_registry):
    X, y, Xv, yv = tables
    ds = dryad.Dataset(X, y, max_bins=32)
    snap = S.snapshot(fresh_registry)
    assert snap["data.sketch"]["count"] == 1 and snap["data.bin"]["count"] == 1
    ds.bind(Xv, yv)
    snap = S.snapshot(fresh_registry)
    assert snap["data.sketch"]["count"] == 1 and snap["data.bin"]["count"] == 2
    assert snap["data.sketch"]["total_s"] > 0 and snap["data.bin"]["total_s"] > 0


def test_csr_dataset_sketches_its_own_table_and_not_a_bound_one(fresh_registry):
    rng = np.random.default_rng(36)
    dense = rng.random((300, 6), np.float32) * (rng.random((300, 6)) < 0.3)
    indptr = np.concatenate([[0], np.cumsum((dense != 0).sum(1))]).astype(np.int64)
    rows, cols = np.nonzero(dense)
    csr = (indptr, cols.astype(np.int32), dense[rows, cols], 6)
    ds = dryad.Dataset(csr=csr, y=rng.random(300), max_bins=16)
    mine = S.snapshot(fresh_registry)
    assert mine["data.sketch"]["count"] >= 1 and mine["data.bin"]["count"] >= 1
    dryad.Dataset(csr=csr, y=rng.random(300), mapper=ds.mapper)
    bound = S.snapshot(fresh_registry)
    assert bound["data.sketch"]["count"] == mine["data.sketch"]["count"]
    assert bound["data.bin"]["count"] > mine["data.bin"]["count"]


def test_the_native_build_is_on_the_clock_where_it_happens(monkeypatch, fresh_registry):
    from dryad_tpu import native

    monkeypatch.setattr(native.subprocess, "run",
                        lambda *a, **k: (_ for _ in ()).throw(OSError("no make")))
    with S.span("data.sketch"):
        assert native._build() is False
    assert S.snapshot(fresh_registry)["data.sketch/data.native_build"]["count"] == 1


@pytest.mark.parametrize("chunked", ["1", "0"], ids=["chunked", "per-iteration"])
def test_setup_span_holds_its_children_through_both_loops(monkeypatch, tmp_path, tables,
                                                         fresh_registry, chunked):
    """``train.setup`` fires once and ends before the first dispatch; its
    children ``upload`` and ``plan`` sum to no more than it; the boundary's
    ``capture`` lies under whatever is open at the first dispatch; the chunked
    loop times its two calibration waits and the per-iteration loop has none."""
    monkeypatch.setenv("DRYAD_PROG", "1")
    monkeypatch.setenv("DRYAD_CHUNK", chunked)
    introspect.reset_seen()
    X, y, Xv, yv = tables
    ds = dryad.Dataset(X, y, max_bins=32)
    open_at_dispatch = []
    dryad.train(dict(BASE, num_trees=3), ds, valid_sets=[ds.bind(Xv, yv)], backend="tpu",
                callbacks=[lambda i, info: None],
                chunk_hook=lambda site, it: site == "dispatch" and open_at_dispatch.append(
                    [sp.path for sp in getattr(S._TLS, "stack", [])]),
                checkpoint_dir=str(tmp_path), checkpoint_every=1)
    snap = S.snapshot(fresh_registry)
    setup, upload, plan = (snap[k] for k in ("train.setup", "train.setup/upload",
                                             "train.setup/plan"))
    assert setup["count"] == 1 and upload["count"] >= 2 and plan["count"] >= 2
    assert 0 < upload["total_s"] + plan["total_s"] <= setup["total_s"] + 1e-6
    assert open_at_dispatch and all(stack == [] for stack in open_at_dispatch)
    if chunked == "1":
        assert snap["train.chunk_dispatch/capture"]["count"] == 1
        assert snap["train.calibrate"]["count"] == 2
        assert snap["train.chunk_dispatch"]["count"] == 3
    else:
        assert snap["capture"]["count"] == 1 and "train.calibrate" not in snap
    assert not [name for name in fresh_registry.snapshot()["gauges"] if "capture" in name]


def test_a_disabled_registry_allocates_nothing_on_the_setup_paths(tables):
    reg = Registry(enabled=False)
    old = set_default_registry(reg)
    try:
        def once():
            with S.span("data.sketch"):
                with S.span("data.native_build"):
                    pass
            with S.span("train.setup"):
                with S.span("upload"):
                    pass
            with S.span("train.calibrate"):
                pass
            introspect.attribute("train.setup")
            introspect._on_duration("/jax/core/compile/backend_compile_duration", 1.0)
            introspect._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 1.0)
            introspect._on_event("/jax/compilation_cache/cache_hits")
            introspect.attribute(None)

        for _ in range(64):
            once()

        def leaked():
            tracemalloc.start()
            for _ in range(1000):
                once()
            snap = tracemalloc.take_snapshot()
            tracemalloc.stop()
            return [st for st in snap.statistics("filename")
                    if "dryad_tpu" in st.traceback[0].filename
                    and ("obs" in st.traceback[0].filename
                         or "introspect" in st.traceback[0].filename)]

        for _ in range(3):      # another test's daemon thread may touch obs: re-measure
            left = leaked()
            if not left:
                break
        assert not left, f"disabled path allocated: {left}"
        X, y, _, _ = tables
        dryad.Dataset(X, y, max_bins=32)
        assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    finally:
        set_default_registry(old)


@pytest.mark.parametrize("event, phase", [
    ("/jax/core/compile/jaxpr_trace_duration", "trace"),
    ("/jax/core/compile/jaxpr_to_mlir_module_duration", "lower"),
    ("/jax/core/compile/backend_compile_duration", "backend_compile"),
    ("/jax/compilation_cache/cache_retrieval_time_sec", "cache_read"),
])
def test_the_listener_books_each_duration_under_the_active_family(fresh_registry, event, phase):
    introspect.attribute("train.setup")
    jax.monitoring.record_event_duration_secs(event, 1.5)
    introspect.attribute("train.chunk")
    jax.monitoring.record_event_duration_secs(event, 2.0)
    jax.monitoring.record_event_duration_secs("/jax/compilation_cache/compile_time_saved_sec", 9.0)
    assert counters(fresh_registry, JIT) == {
        f'phase="{phase}",program="train.setup"': 1.5,
        f'phase="{phase}",program="train.chunk"': 2.0}
    compiles = counters(fresh_registry, "dryad_prog_backend_compiles_total")
    seconds = counters(fresh_registry, "dryad_prog_compile_seconds_total")
    if phase == "backend_compile":      # the two series ckpt_compiles reads keep their meaning
        assert compiles == {'program="train.setup"': 1.0, 'program="train.chunk"': 1.0}
        assert seconds == {'program="train.setup"': 1.5, 'program="train.chunk"': 2.0}
    else:
        assert compiles == {} and seconds == {}


@pytest.mark.parametrize("event, result", [("/jax/compilation_cache/cache_hits", "hit"),
                                           ("/jax/compilation_cache/cache_misses", "miss")])
def test_the_listener_books_the_caches_events_under_the_active_family(fresh_registry, event,
                                                                      result):
    introspect.attribute("train.chunk")
    jax.monitoring.record_event(event)
    jax.monitoring.record_event(event)
    jax.monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
    introspect.attribute(None)
    jax.monitoring.record_event(event)
    assert counters(fresh_registry, CACHE) == {
        f'program="train.chunk",result="{result}"': 2.0, f'program="other",result="{result}"': 1.0}


class Stop(Exception):
    pass


@pytest.mark.parametrize("leave", ["return", "raise"])
def test_the_label_ends_with_the_job(monkeypatch, tables, fresh_registry, leave):
    """By return or by an exception a ``chunk_hook`` raises through
    ``train_device`` (the benchmark's ``StopJob``), the sticky label is
    cleared, and what the thread compiles next is counted under ``other``."""
    monkeypatch.setenv("DRYAD_PROG", "1")
    monkeypatch.setenv("DRYAD_CHUNK", "1")
    introspect.reset_seen()
    X, y, _, _ = tables
    ds = dryad.Dataset(X, y, max_bins=32)
    seen = []

    def hook(site, it):
        seen.append((site, introspect._tls.program))
        if leave == "raise" and site == "dispatch" and it >= 1:
            raise Stop()

    kw = dict(backend="tpu", chunk_hook=hook, checkpoint_dir=None)
    if leave == "raise":
        with pytest.raises(Stop):
            dryad.train(dict(BASE, num_trees=4, ch_max=1), ds, **kw)
    else:
        dryad.train(dict(BASE, num_trees=2, ch_max=1), ds, **kw)
    assert seen[0] == ("dispatch", "train.setup") and seen[-1][1] == "train.chunk"
    assert introspect._tls.program is None
    assert getattr(S._TLS, "stack", []) == []
    before = dict(counters(fresh_registry, "dryad_prog_backend_compiles_total"))
    jax.jit(lambda x: x * 36.0 + float(len(leave)))(np.ones((3,), np.float32)).block_until_ready()
    after = counters(fresh_registry, "dryad_prog_backend_compiles_total")
    assert after.get('program="other"', 0) == before.get('program="other"', 0) + 1
    assert {k: v for k, v in after.items() if "other" not in k} \
        == {k: v for k, v in before.items() if "other" not in k}


def test_a_setup_that_raises_leaves_no_span_open(tables, fresh_registry):
    X, y, _, _ = tables
    ds = dryad.Dataset(X, y, max_bins=32)
    ds.y = None                                  # the objective's init score cannot be taken
    with pytest.raises(Exception):
        dryad.train(dict(BASE, num_trees=1), ds, backend="tpu")
    assert getattr(S._TLS, "stack", []) == [] and introspect._tls.program is None
    assert S.snapshot(fresh_registry)["train.setup"]["count"] == 1
