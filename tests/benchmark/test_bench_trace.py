"""The trace reduction: busy and idle, kernel time by name, gaps and who
gets them.  First on a hand-made list of events, then on a small list
recorded on the chip and committed beside this file."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import trace  # noqa: E402
from benchmark.runners.train_job import idle_by  # noqa: E402

DEV = "/device:TPU:0"
KERNELS = {"hist": ("_hist_tiles", "build_hist_nat"), "perm": ("permute_records",)}


def ev(line, name, start_us, dur_us, plane=DEV):
    return (plane, line, name, start_us * 1000, dur_us * 1000)


HAND = [
    ev(trace.MODULES_LINE, "jit__chunk_jit(1)", 0, 1000),
    ev(trace.OPS_LINE, "%while.1 = while(...)", 0, 1000),          # contains the next three
    ev(trace.OPS_LINE, "%_hist_tiles.35 = f32[128,8,8192]{2,1,0} custom-call(s32[11461] %copy-done.164)", 0, 400),
    ev(trace.OPS_LINE, "%permute_records.1 = u8[366560,32,128] custom-call(s32[22910] %copy-done.184)", 400, 100),
    ev(trace.OPS_LINE, "%fusion.7 = f32[8] fusion(f32[128,8,8192] %_hist_tiles.35)", 600, 400),   # operand, no kernel
    ev(trace.MODULES_LINE, "jit__chunk_jit(1)", 1500, 500),       # 500 us gap
    ev(trace.OPS_LINE, "%build_hist_nat = f32[3,28,256] custom-call(u8[1,2,3] %p)", 1500, 500),
    ev(trace.MODULES_LINE, "jit_other(2)", 5000, 10),             # not a chunk program
    ev("Steps", "step", 0, 99999),                                # another line: ignored
    ev(trace.OPS_LINE, "op", 0, 99999, plane="/host:CPU"),        # not a device plane
]


def test_hand_made_trace():
    events = [e for e in HAND if e[0].startswith(trace.DEVICE_PLANE)
              and e[1] in (trace.OPS_LINE, trace.MODULES_LINE)]
    r = trace.reduce(events, KERNELS)
    assert r["devices"] == 1 and r["programs"] == 3
    assert r["span_s"] == pytest.approx(2000e-6)
    assert r["busy_s"] == pytest.approx(1500e-6)          # the while covers its children
    assert r["programs_s"] == pytest.approx(1510e-6)
    assert r["kernel_s"]["hist"] == pytest.approx(900e-6)
    assert r["kernel_s"]["perm"] == pytest.approx(100e-6)
    names = dict(r["top_ops"])
    assert "while.1" not in names and names["_hist_tiles.35"] == pytest.approx(400e-6)


def test_idle_goes_to_what_the_host_was_doing():
    reduced = {"busy_s": 20.0, "programs_s": 21.0}
    spans = [("train.fetch.eval", 0.0, 11.0), ("train.fetch.checkpoint", 11.1, 0.6),
             ("train.fetch.checkpoint", 23.0, 0.5)]
    got = idle_by(23.0, reduced, spans)
    assert got == {"in_program": pytest.approx(1.0), "train.fetch.checkpoint": pytest.approx(1.1),
                   "fetch_and_dispatch": pytest.approx(0.9)}
    assert sum(got.values()) == pytest.approx(23.0 - 20.0)
    # spans can never claim more than the idle time between programs
    assert idle_by(21.5, reduced, spans)["train.fetch.checkpoint"] == pytest.approx(0.5)


def test_nothing_to_read_returns_nothing():
    assert trace.reduce([], KERNELS) == {}
    from benchmark.layer_metrics import hist_roofline, perm_time_share

    facts = {"trace": {}, "window_iters": 10, "window_s": 1.0, "window_chunks": 2,
             "peaks": None, "shape": {}}
    assert hist_roofline.read(facts) is None and perm_time_share.read(facts) is None


def test_a_trace_longer_than_the_window_shows():
    """Busy time is the trace's, the window the host's: where they disagree
    the share and the gap read negative, not a clamped nought."""
    from benchmark.layer_metrics import chunk_gap_ms, device_idle_share

    facts = {"trace": {"busy_s": 10.5, "programs_s": 10.6}, "window_s": 10.0, "window_chunks": 2}
    assert device_idle_share.read(facts) == pytest.approx(-5.0)
    assert chunk_gap_ms.read(facts) == pytest.approx(-300.0)


RECORDED = os.path.join(HERE, "data", "trace_higgs_small.json")


def test_recorded_trace():
    """Expected values were worked out another way when the sample was cut
    (a sweep over start and end points, counting what is open), not by
    ``reduce``."""
    with open(RECORDED) as f:
        rec = json.load(f)
    r = trace.reduce([tuple(e) for e in rec["events"]], KERNELS)
    want = rec["expected"]
    assert r["programs"] == want["programs"]
    for key in ("busy_s", "span_s", "programs_s"):
        assert r[key] == pytest.approx(want[key], abs=2e-6), key
    for k, v in want["kernel_s"].items():
        assert r["kernel_s"][k] == pytest.approx(v, rel=1e-9)
    assert 0 < r["busy_s"] <= r["span_s"]
    assert r["kernel_s"]["hist"] > 0 and r["kernel_s"]["perm"] > 0
    assert not any(name.startswith("while") for name, _ in r["top_ops"])
