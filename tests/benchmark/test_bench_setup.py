"""The seven readers of set-up (PR 36): the program's spans and jit counters,
read from the process's registry over the whole run, a number in every cell.
``BENCHMARK.json`` lists them under ``per_layer``, moving ``setup_s``."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import manifest as mf  # noqa: E402

SETUP_READERS = ("sketch_s", "bin_s", "upload_s", "capture_s", "trace_lower_s",
                 "cache_read_s", "backend_compile_s")
# what a hand-filled registry (below) must read
FILLED = {"sketch_s": 7.0, "bin_s": 5.0, "upload_s": 3.0, "capture_s": 2.5,
          "trace_lower_s": 1.75, "cache_read_s": 0.5, "backend_compile_s": 11.0}


@pytest.fixture()
def registry():
    from dryad_tpu.obs import Registry, set_default_registry

    reg = Registry()
    old = set_default_registry(reg)
    yield reg
    set_default_registry(old)


def fill(reg):
    walls = reg.counter("dryad_span_seconds_total", "x")
    for path, s in (("data.sketch", 6.0), ("supervise/data.sketch", 1.0),
                    ("data.sketch/data.native_build", 4.0), ("data.bin", 5.0),
                    ("train.setup", 9.0), ("train.setup/upload", 3.0), ("train.setup/plan", 1.0),
                    ("train.chunk_dispatch", 40.0), ("train.chunk_dispatch/capture", 2.0),
                    ("capture", 0.5), ("recapture", 100.0)):
        walls.labels(span=path).inc(s)
    jit = reg.counter("dryad_prog_jit_seconds_total", "x")
    for program, phase, s in (("train.setup", "trace", 0.25), ("train.chunk", "trace", 1.0),
                              ("train.chunk", "lower", 0.5), ("train.chunk", "cache_read", 0.5),
                              ("train.chunk", "backend_compile", 10.0),
                              ("train.setup", "backend_compile", 1.0),
                              ("train.materialize", "trace", 50.0),
                              ("train.materialize", "backend_compile", 50.0),
                              ("other", "lower", 70.0), ("other", "cache_read", 70.0)):
        jit.labels(program=program, phase=phase).inc(s)


@pytest.mark.parametrize("name", SETUP_READERS)
def test_reader_takes_its_series_from_the_programs_registry(name, registry):
    fill(registry)
    assert mf.metric_reader(name).read({}) == pytest.approx(FILLED[name])


@pytest.mark.parametrize("name", SETUP_READERS)
def test_an_absent_series_reads_zero_and_never_nothing(name, registry):
    value = mf.metric_reader(name).read({})
    assert value == 0.0 and isinstance(value, float)


def check_manifest(manifest, root=mf.ROOT):
    """The seven stand in ``per_layer`` once each, entry for entry as below,
    with their readers, and every cell reports all seven."""
    assert mf.problems(manifest, root) == []
    spans = ("sketch_s", "bin_s", "upload_s", "capture_s")
    layers = {"sketch_s": "host data prep", "bin_s": "host data prep",
              "upload_s": "host data prep"}
    entries = [{"name": name, "unit": "s", "better": "lower",
                "source": "program_span" if name in spans else "program_counter",
                "layer": layers.get(name, "compile cache"), "moves": "setup_s"}
               for name in SETUP_READERS]
    for want in entries:
        assert [m for m in manifest["per_layer"] if m["name"] == want["name"]] == [want]
        assert callable(mf.metric_reader(want["name"]).read)
    for cell in (w["name"] for w in manifest["workloads"]):
        assert set(SETUP_READERS) <= {m["name"] for m in mf.Cell(manifest, cell, root).per_layer}


def test_the_manifest_would_take_the_seven_as_the_issue_words_them():
    """The seven are in ``BENCHMARK.json`` entry for entry as
    ``check_manifest`` words them, appended after the entries before them,
    none of which changed."""
    check_manifest(mf.load())


def test_the_sums_the_issue_asks_of_the_readers_hold_on_a_real_job(registry, monkeypatch):
    """A small CPU job through the public API: the sketch and the binning lie
    inside what the benchmark calls data prep, and the three jit readers add
    up to the listener's whole less the families that are not set-up's."""
    import numpy as np

    import dryad_tpu as dryad
    from dryad_tpu.datasets import higgs_like
    from dryad_tpu.engine import introspect

    monkeypatch.setenv("DRYAD_PROG", "1")
    introspect.reset_seen()
    import time

    X, y = higgs_like(2000, seed=36)
    t0 = time.perf_counter()
    ds = dryad.Dataset(X, y, max_bins=32)
    vds = ds.bind(X[:400], y[:400])
    data_prep_s = time.perf_counter() - t0
    dryad.train(dict(objective="binary", num_leaves=7, max_bins=32, num_trees=2), ds,
                valid_sets=[vds], backend="tpu", callbacks=[lambda i, info: None])
    read = {name: mf.metric_reader(name).read({}) for name in SETUP_READERS}
    assert 0 < read["sketch_s"] + read["bin_s"] <= data_prep_s
    assert read["upload_s"] > 0 and read["capture_s"] > 0
    assert read["trace_lower_s"] > 0 and read["backend_compile_s"] > 0
    assert read["cache_read_s"] == 0.0          # the suite runs with the persistent cache off
    jit = registry.snapshot()["counters"]["dryad_prog_jit_seconds_total"]
    whole = sum(v for lbl, v in jit.items()
                if 'program="other"' not in lbl and 'program="train.materialize"' not in lbl)
    assert read["trace_lower_s"] + read["backend_compile_s"] == pytest.approx(whole)
    assert np.isfinite(list(read.values())).all()
