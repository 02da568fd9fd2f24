"""Bench trend ledger (dryad_tpu/obs/trends.py + scripts/bench_trend.py).

Pins: the backfill-tolerant reader over unstamped r1–r7 artifacts AND
stamped r12+ ones, the spread-aware median comparison (a suspect capture
is never a regression verdict), the registry ingest, the artifact stamp,
and the CLI gate over the repo's real committed history."""

import json
import os
import subprocess
import sys

import pytest

from dryad_tpu.obs import Registry
from dryad_tpu.obs.trends import (
    SCHEMA_VERSION,
    artifact_stamp,
    compare,
    ingest,
    load_history,
    stats_provider,
)

ROOT = __file__.rsplit("/tests/", 1)[0]


def _write(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f)


def _history(tmp_path, points, stamp_last=False):
    """points: list of metric dicts, written as driver-wrapper artifacts
    BENCH_r01..; stamp_last adds the r12 stamps to the newest."""
    for i, metrics in enumerate(points, start=1):
        doc = {"n": i, "cmd": "python bench.py", "rc": 0,
               "parsed": dict(metrics)}
        if stamp_last and i == len(points):
            doc["parsed"].update(schema_version=SCHEMA_VERSION,
                                 git_rev="abc1234", device_kind="TPU v4")
        _write(str(tmp_path / f"BENCH_r{i:02d}.json"), doc)
    return str(tmp_path)


# ---- reader -----------------------------------------------------------------

def test_load_history_backfill_tolerant(tmp_path):
    # r1: driver wrapper, unstamped; r2: flat bench.py line saved raw;
    # r3: stamped wrapper; plus junk that must be skipped, not fatal
    _write(str(tmp_path / "BENCH_r01.json"),
           {"n": 1, "rc": 0, "parsed": {"metric": "m", "value": 3.0}})
    _write(str(tmp_path / "BENCH_r02.json"),
           {"metric": "m", "value": 3.5, "rows": 200000})
    _write(str(tmp_path / "BENCH_r03.json"),
           {"n": 3, "parsed": {"metric": "m", "value": 4.0,
                               "schema_version": 1, "git_rev": "deadbee",
                               "device_kind": "cpu"}})
    with open(str(tmp_path / "BENCH_r04.json"), "w") as f:
        f.write("{ not json")
    _write(str(tmp_path / "BENCH_r05.json"), {"n": 5, "tail": "no metrics"})
    hist = load_history(str(tmp_path))
    assert [p["round"] for p in hist] == [1, 2, 3]
    assert hist[0]["git_rev"] is None            # backfill: unstamped
    assert hist[1]["metrics"]["value"] == 3.5    # flat artifact accepted
    assert hist[2]["git_rev"] == "deadbee"
    assert hist[2]["device_kind"] == "cpu"
    assert hist[2]["schema_version"] == 1
    # non-numeric fields never become metrics
    assert "metric" not in hist[0]["metrics"]


def test_load_history_real_committed_files():
    # the r1-r5 artifacts recorded another installation and left the tree
    # (PR 21); whatever history is committed must parse, and the ci.sh
    # gate must stay green on an empty one
    hist = load_history(ROOT)
    assert all("value" in p["metrics"] for p in hist)
    assert compare(hist)["ok"]


# ---- comparison -------------------------------------------------------------

BASE = {"value": 10.0, "marginal_s_per_iter_10m": 2.5,
        "spread_2tree_10m": 0.01, "spread_8tree_10m": 0.01}


def test_compare_ok_and_improved(tmp_path):
    root = _history(tmp_path, [BASE, BASE,
                               dict(BASE, value=14.0,
                                    marginal_s_per_iter_10m=2.4)])
    report = compare(load_history(root))
    assert report["ok"] and report["newest"] == "BENCH_r03.json"
    assert report["metrics"]["value"]["verdict"] == "improved"
    assert report["metrics"]["marginal_s_per_iter_10m"]["verdict"] == "ok"


def test_compare_flags_regression_against_median(tmp_path):
    # median of (2.4, 2.5, 2.6) = 2.5; newest 5.0 is 2x worse
    root = _history(tmp_path, [
        dict(BASE, marginal_s_per_iter_10m=2.4),
        dict(BASE, marginal_s_per_iter_10m=2.6),
        dict(BASE, marginal_s_per_iter_10m=2.5),
        dict(BASE, marginal_s_per_iter_10m=5.0)])
    report = compare(load_history(root))
    entry = report["metrics"]["marginal_s_per_iter_10m"]
    assert not report["ok"] and entry["verdict"] == "regression"
    assert entry["median"] == 2.5 and entry["n_history"] == 3


def test_compare_spread_vetoes_regression(tmp_path):
    """Suspect capture, never a regression verdict (CLAUDE.md): the same
    2x-worse point under a >5% per-arm spread downgrades to suspect."""
    bad = dict(BASE, marginal_s_per_iter_10m=5.0, spread_8tree_10m=0.2)
    root = _history(tmp_path, [BASE, BASE, bad])
    report = compare(load_history(root))
    assert report["ok"]
    assert report["metrics"]["marginal_s_per_iter_10m"][
        "verdict"] == "suspect"


def test_compare_new_metric_and_single_point(tmp_path):
    root = _history(tmp_path, [BASE, dict(BASE, obs_overhead_ms=1.5)])
    report = compare(load_history(root))
    assert report["metrics"]["obs_overhead_ms"]["verdict"] == "new"
    solo = compare(load_history(root)[:1])
    assert solo["ok"] and solo["metrics"]["value"]["verdict"] == "new"


def test_compare_higher_better_direction(tmp_path):
    root = _history(tmp_path, [BASE, BASE, dict(BASE, value=5.0)])
    report = compare(load_history(root))
    assert report["metrics"]["value"]["verdict"] == "regression"
    assert not report["ok"]


# ---- ingest + provider ------------------------------------------------------

def test_ingest_registry_series(tmp_path):
    root = _history(tmp_path, [BASE, dict(BASE, value=12.0)],
                    stamp_last=True)
    reg = Registry()
    n = ingest(load_history(root), reg)
    assert n > 0
    fam = reg.gauge("dryad_bench_value")
    assert fam.labels(metric="value", round=1).value() == 10.0
    assert fam.labels(metric="value", round=2).value() == 12.0
    assert reg.gauge("dryad_bench_rounds").value() == 2
    # spreads/rows are context, not tracked series
    assert not any("spread" in lbl for lbl in fam.series())
    disabled = Registry(enabled=False)
    assert ingest(load_history(root), disabled) == 0


def test_stats_provider_shape(tmp_path):
    root = _history(tmp_path, [BASE, BASE, BASE])
    provide = stats_provider(root)
    out = provide()
    assert out["bench_trends"]["ok"] and out["bench_trends"]["n_points"] == 3
    assert provide() is not None        # cached second call


# ---- PROFILE_r*.json ingestion (r13 stage profiler) ------------------------

PROF = {"profile_schema": 1, "stage_ms_hist_segmented": 136.0,
        "stage_spread_hist_segmented": 0.01,
        "stage_ms_route_gather": 30.0, "stage_spread_route_gather": 0.02,
        "stage_rows_hist_segmented": 10_000_000}


def _profile_history(tmp_path, points):
    for i, metrics in enumerate(points, start=1):
        _write(str(tmp_path / f"PROFILE_r{i:02d}.json"), dict(metrics))
    return str(tmp_path)


def test_profile_history_loads_and_tracks_stage_metrics(tmp_path):
    from dryad_tpu.obs.trends import PROFILE_PATTERN

    root = _profile_history(tmp_path, [PROF, dict(PROF,
                                                  stage_ms_route_gather=28.0)])
    hist = load_history(root, pattern=PROFILE_PATTERN)
    assert [p["round"] for p in hist] == [1, 2]
    report = compare(hist)
    assert report["ok"]
    assert report["metrics"]["stage_ms_route_gather"]["verdict"] == "ok"
    # context fields (rows) are never tracked metrics
    assert "stage_rows_hist_segmented" not in report["metrics"]


def test_profile_regression_flagged_and_spread_vetoed(tmp_path):
    """A 2x-slower stage regresses vs the median; the SAME point with a
    seeded noisy spread downgrades to suspect (the CLAUDE.md veto)."""
    from dryad_tpu.obs.trends import PROFILE_PATTERN

    bad = dict(PROF, stage_ms_hist_segmented=270.0)
    root = _profile_history(tmp_path, [PROF, PROF, PROF, bad])
    report = compare(load_history(root, pattern=PROFILE_PATTERN))
    entry = report["metrics"]["stage_ms_hist_segmented"]
    assert not report["ok"] and entry["verdict"] == "regression"

    noisy = dict(bad, stage_spread_hist_segmented=0.2)
    _write(str(tmp_path / "PROFILE_r04.json"), noisy)
    report = compare(load_history(root, pattern=PROFILE_PATTERN))
    entry = report["metrics"]["stage_ms_hist_segmented"]
    assert report["ok"] and entry["verdict"] == "suspect"


def test_profile_history_backfill_tolerant(tmp_path):
    """An unstamped artifact (no schema_version — the stamp is
    best-effort) still loads via its profile_schema marker; junk files
    skip, never fatal."""
    from dryad_tpu.obs.trends import PROFILE_PATTERN

    unstamped = {k: v for k, v in PROF.items()}     # no schema_version
    _write(str(tmp_path / "PROFILE_r01.json"), unstamped)
    _write(str(tmp_path / "PROFILE_r02.json"),
           dict(PROF, schema_version=1, git_rev="abc", device_kind="cpu"))
    with open(str(tmp_path / "PROFILE_r03.json"), "w") as f:
        f.write("{ torn")
    hist = load_history(str(tmp_path), pattern=PROFILE_PATTERN)
    assert [p["round"] for p in hist] == [1, 2]
    assert hist[0]["git_rev"] is None and hist[1]["git_rev"] == "abc"


def test_stats_provider_mounts_profile_trends(tmp_path):
    root = _history(tmp_path, [BASE, BASE])
    out = stats_provider(root)()
    assert "profile_trends" not in out          # no PROFILE files
    _profile_history(tmp_path, [PROF, PROF])
    out = stats_provider(root)()
    assert out["profile_trends"]["ok"]
    assert out["profile_trends"]["n_points"] == 2


def test_profile_ingest_registry_series(tmp_path):
    from dryad_tpu.obs.trends import PROFILE_PATTERN

    root = _profile_history(tmp_path, [PROF])
    reg = Registry()
    n = ingest(load_history(root, pattern=PROFILE_PATTERN), reg)
    assert n == 2        # two stage_ms_* metrics, spreads/rows untracked
    fam = reg.gauge("dryad_bench_value")
    assert fam.labels(metric="stage_ms_route_gather", round=1).value() == 30.0


# ---- artifact stamp ---------------------------------------------------------

def test_artifact_stamp_in_repo_and_outside(tmp_path):
    stamp = artifact_stamp(device_kind="cpu", root=ROOT)
    assert stamp["schema_version"] == SCHEMA_VERSION
    assert stamp["device_kind"] == "cpu"
    assert stamp["git_rev"]          # this repo IS a git checkout
    lost = artifact_stamp(device_kind=None, root=str(tmp_path))  # no git here
    assert lost["git_rev"] is None and lost["device_kind"] is None
    # r23: the default resolves through the ONE derivation
    from dryad_tpu.policy.device import current_device_kind
    auto = artifact_stamp(root=str(tmp_path))
    assert auto["device_kind"] == current_device_kind()


# ---- the CLI gate -----------------------------------------------------------

@pytest.mark.parametrize("args,rc", [(["--check"], 0), (["--selftest"], 0)])
def test_bench_trend_cli_on_committed_history(args, rc):
    env = dict(os.environ, PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "bench_trend.py"),
         "--root", ROOT] + args,
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == rc, proc.stdout + proc.stderr


def test_bench_trend_cli_check_fails_on_seeded_regression(tmp_path):
    _history(tmp_path, [BASE, BASE, BASE,
                        dict(BASE, marginal_s_per_iter_10m=6.0)])
    env = dict(os.environ, PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "bench_trend.py"),
         "--root", str(tmp_path), "--check"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 1
    assert "TREND REGRESSION" in proc.stderr
