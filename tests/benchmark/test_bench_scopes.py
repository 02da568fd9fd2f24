"""``benchmark/harness/scopes.py``: device time by ``dryad.*`` scope and idle
time by ``train.*`` annotation.  First on a hand-made list of events, then on
a small list recorded on the chip and committed beside this file, then the
readers where there is nothing to read."""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import manifest as mf  # noqa: E402
from benchmark.harness import scopes, trace  # noqa: E402

DEV = "/device:TPU:0"
NEW_READERS = ("grad_score_device_ms", "route_device_ms", "layout_device_ms",
               "hist_glue_device_ms", "split_scan_device_ms", "eval_device_ms",
               "unscoped_device_ms", "gap_unlabelled_ms", "ckpt_materialize_ms",
               "ckpt_save_ms", "ckpt_compiles")


def op(name, start_us, dur_us, plane=DEV):
    return (plane, name, start_us * 1000, dur_us * 1000)


# one chunk program (a level loop inside the iteration loop), a checkpoint's
# small program, a second chunk program, a small program that is all eval
HAND = {
    "modules": [op("jit__chunk_jit(77)", 0, 1000), op("jit_dynamic_slice(5)", 1200, 10),
                op("jit__chunk_jit(77)", 1500, 500), op("jit__apply_valid_jit(3)", 2000, 50)],
    "ops": [
        op("%while.1 = (s32[]) while(...)", 0, 1000),                      # the iteration loop
        op("%while.2 = (s32[]) while(...), body=%level", 100, 600),        # nested: the level loop
        op("%fusion.5 = u32[8,2] fusion(u32[255,2] %fusion.4)", 0, 100),   # route
        op("%_hist_tiles.35 = f32[128,8,8192] custom-call(s32[11461] %copy-done.164)", 100, 300),
        op("%transpose.9 = f32[3,28,256] transpose(f32[128,8,8192] %_hist_tiles.35)", 400, 100),
        op("%permute_records.1 = u8[366560,32,128] custom-call(s32[22910] %copy-done.184)", 500, 150),
        op("%copy.3 = f32[8] copy(f32[8] %x)", 650, 50),                   # in no scope
        op("%fusion.7 = f32[500000] fusion(f32[509] %y)", 700, 300),       # eval, after the loop
        op("%dynamic-slice.1 = s32[15,509] dynamic-slice(s32[500,509] %p)", 1200, 10),
        op("%fusion.5 = u32[8,2] fusion(u32[255,2] %fusion.4)", 1500, 500),
        op("%fusion.46 = f32[100000] fusion(f32[100000] %vs)", 2000, 50),
    ],
    # the gap 1000..1200 is half covered by the checkpoint's fetch, whose
    # child covers a quarter; 1210..1500 lies under the next dispatch but
    # for its first 5 us
    "host": [("train.fetch.checkpoint", 1_100_000, 105_000),
             ("train.fetch.checkpoint/materialize", 1_100_000, 50_000),
             ("train.chunk_dispatch", 1_215_000, 400_000),
             ("train.fetch.eval", 0, 900_000)],                           # no idle under it
}
MAPS = {"jit__chunk_jit": {"fusion.5": "dryad.route", "_hist_tiles.35": "dryad.hist",
                           "transpose.9": "~dryad.hist", "permute_records.1": "dryad.layout",
                           "fusion.7": "dryad.eval"},
        # the same instruction name in another module is another instruction
        "jit_other": {"dynamic-slice.1": "dryad.score", "copy.3": "dryad.grad"},
        # a small program that is one stage whole
        "jit__apply_valid_jit": {"": "dryad.eval"}}


def test_hand_made_trace():
    r = scopes.reduce(HAND, MAPS)
    us = 1e-6
    assert r["devices"] == 1 and r["scoped"] and r["annotated"]
    assert r["scope_s"] == {
        "dryad.eval": pytest.approx(350 * us),            # fusion.7 and all of the small program
        "dryad.hist": pytest.approx(100 * us),           # the kernel's 300 us are apart
        "dryad.route": pytest.approx(600 * us),
        scopes.UNSCOPED: pytest.approx(60 * us),          # copy.3 and the checkpoint's slice
    }
    assert r["inferred_s"] == {"dryad.hist": pytest.approx(100 * us)}
    assert r["kernel_s"] == {"hist": {"dryad.hist": pytest.approx(300 * us)},
                             "perm": {"dryad.layout": pytest.approx(150 * us)}}
    assert dict(r["unscoped_ops"]) == {"jit__chunk_jit/copy.3": pytest.approx(50 * us),
                                       "jit_dynamic_slice/dynamic-slice.1": pytest.approx(10 * us)}
    assert r["gap_s"] == {
        scopes.UNLABELLED: pytest.approx((100 + 5) * us),
        "train.fetch.checkpoint/materialize": pytest.approx(50 * us),
        "train.fetch.checkpoint": pytest.approx(50 * us),
        "train.chunk_dispatch": pytest.approx(285 * us),
    }
    # the seven and the kernels together are the leaves: what trace.reduce
    # calls busy time, here without an overlap
    events = [(p, trace.OPS_LINE, n, s, d) for p, n, s, d in HAND["ops"]] \
        + [(p, trace.MODULES_LINE, n, s, d) for p, n, s, d in HAND["modules"]]
    busy = trace.reduce(events, scopes.KERNELS)["busy_s"]
    assert sum(r["scope_s"].values()) + 450 * us == pytest.approx(busy)
    assert sum(r["gap_s"].values()) == pytest.approx(490 * us)


def test_a_program_without_scopes_or_annotations():
    r = scopes.reduce(dict(HAND, host=[]), {})
    assert not r["scoped"] and not r["annotated"]
    assert set(r["scope_s"]) == {scopes.UNSCOPED}
    assert r["gap_s"] == {scopes.UNLABELLED: pytest.approx(490e-6)}
    assert scopes.reduce({"ops": [], "modules": [], "host": []}, MAPS) == {}


RECORDED = os.path.join(HERE, "data", "trace_scopes_small.json")


def test_recorded_trace():
    """Expected values were worked out another way when the sample was cut
    (a sweep over start and end points, counting what is open), not by
    ``scopes.reduce``."""
    with open(RECORDED) as f:
        rec = json.load(f)
    loaded = {k: [tuple(e) for e in rec[k]] for k in ("ops", "modules", "host")}
    r = scopes.reduce(loaded, rec["scope_maps"])
    want = rec["expected"]
    assert r["scoped"] and r["annotated"]
    for key in ("scope_s", "gap_s"):
        assert set(r[key]) == set(want[key]), key
        for name, v in want[key].items():
            assert r[key][name] == pytest.approx(v, abs=2e-9), (key, name)
    for group, by_scope in want["kernel_s"].items():
        assert r["kernel_s"][group] == pytest.approx(by_scope, abs=2e-9)
    assert r["kernel_s"]["hist"]["dryad.hist"] > 0 and r["kernel_s"]["perm"]["dryad.layout"] > 0
    assert len([s for s in r["scope_s"] if s.startswith("dryad.")]) == 7
    assert r["gap_s"].get("train.fetch.checkpoint/materialize", 0) > 0


def test_finds_only_a_trace_of_this_process(tmp_path):
    assert scopes.find_xplane(str(tmp_path)) is None
    d = tmp_path / "bench_cell_x" / "trace" / "plugins" / "profile" / "2026_09_30"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert scopes.find_xplane(str(tmp_path)) == str(d / "host.xplane.pb")
    os.utime(d / "host.xplane.pb", (1.0, 1.0))           # left by an earlier process
    assert scopes.find_xplane(str(tmp_path)) is None
    assert scopes.process_start_s() > 1e9


@pytest.mark.parametrize("name", NEW_READERS)
def test_nothing_to_read_returns_nothing(name, monkeypatch, tmp_path):
    """No trace (or a CPU rehearsal's, with no TPU plane), no span, no
    counter: every new reader leaves its metric out and does not raise."""
    from dryad_tpu.obs import Registry, set_default_registry

    monkeypatch.setattr(scopes.tempfile, "gettempdir", lambda: str(tmp_path))
    old = set_default_registry(Registry())
    try:
        facts = {"trace": {}, "spans": [("train.fetch.eval", 0.0, 1.0)], "window_iters": 10,
                 "window_s": 1.0, "window_chunks": 2, "peaks": None, "shape": {}}
        assert importlib.import_module("benchmark.layer_metrics." + name).read(facts) is None
    finally:
        set_default_registry(old)


def test_readers_on_a_reduction(monkeypatch):
    from benchmark.layer_metrics import (ckpt_materialize_ms, ckpt_save_ms, gap_unlabelled_ms,
                                         grad_score_device_ms, layout_device_ms)

    r = {"scoped": True, "annotated": True,
         "scope_s": {"dryad.grad": 0.2, "dryad.score": 0.3, "dryad.layout": 1.0},
         "gap_s": {scopes.UNLABELLED: 0.004, "train.callbacks": 1.0}}
    monkeypatch.setattr(scopes, "read", lambda: r)
    facts = {"window_iters": 10, "window_chunks": 2,
             "spans": [("train.fetch.checkpoint", 0.0, 0.30),
                       ("train.fetch.checkpoint/materialize", 0.0, 0.25),
                       ("train.fetch.checkpoint/save", 0.25, 0.04),
                       ("supervise.segment/train.fetch.checkpoint/save", 9.0, 0.06)]}
    assert grad_score_device_ms.read(facts) == pytest.approx(50.0)
    assert layout_device_ms.read(facts) == pytest.approx(100.0)
    assert gap_unlabelled_ms.read(facts) == pytest.approx(2.0)
    assert ckpt_materialize_ms.read(facts) == pytest.approx(250.0)
    assert ckpt_save_ms.read(facts) == pytest.approx(50.0)
    # a program from before the scopes: the trace reduces, the names are absent
    monkeypatch.setattr(scopes, "read", lambda: dict(r, scoped=False, annotated=False))
    assert grad_score_device_ms.read(facts) is None and gap_unlabelled_ms.read(facts) is None


def test_ckpt_compiles_reads_the_programs_counters():
    from benchmark.layer_metrics import ckpt_compiles
    from dryad_tpu.obs import Registry, set_default_registry

    reg = Registry()
    old = set_default_registry(reg)
    try:
        compiles = reg.counter("dryad_prog_backend_compiles_total", "x")
        compiles.labels(program="train.materialize").inc(35)
        compiles.labels(program="train.chunk").inc(2)
        reg.counter("dryad_span_count_total", "x").labels(span="train.fetch.checkpoint").inc(7)
        reg.counter("dryad_span_count_total", "x").labels(
            span="train.fetch.checkpoint/save").inc(7)
        assert ckpt_compiles.read({}) == pytest.approx(5.0)
    finally:
        set_default_registry(old)


def test_manifest_is_sound_and_names_the_new_readers():
    manifest = mf.load()
    assert mf.problems(manifest) == []
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-len(NEW_READERS):] == list(NEW_READERS)
    higgs = {m["name"] for m in mf.Cell(manifest, "higgs10m_d8.job").per_layer}
    epsilon = {m["name"] for m in mf.Cell(manifest, "epsilon400k_d6.job").per_layer}
    assert higgs - epsilon == {"perm_time_share", "layout_device_ms"}
