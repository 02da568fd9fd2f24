"""The missing-value cell (``bosch1m_leaf255.job_missing``): its data family
(the empty share, whole stations, the positive rate), the reference's
two-plane scan against a brute force over every (column, threshold,
direction) with the tie and the exclusion, the reader ``missing_device_ms``
and the runner's counter, the manifest's new entries, and the whole cell at
rehearsal size through ``benchmark/run.py``.  All on the CPU at a few
thousand rows.  The faults planted underneath the real path are in
``test_bench_missing_faults.py``, so that they run beside these."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import test_bench_setup  # noqa: E402
from bench_fixtures import ROOT, run_cli  # noqa: E402
from test_bench_append import grown, readers_of  # noqa: E402,F401  (fixtures)

from benchmark.datagen import bosch  # noqa: E402
from benchmark.harness import manifest as mf  # noqa: E402
from benchmark.reference import gbdt  # noqa: E402
from benchmark.reference.gbdt_bestfirst_missing import BestFirstMissing, Rows  # noqa: E402

CELL = "bosch1m_leaf255.job_missing"
SEED = 3800000011


# ---- data ---------------------------------------------------------------------------


def test_the_family_empties_whole_stations_at_the_sources_share():
    """81 % of the cells empty (0.81 +- 0.02 on every seed: the paths are
    evened out to it), a station's columns present or empty together, about
    0.58 % of the rows positive, the same rows whatever the thread count, and
    stream 1 not stream 0."""
    q, y = bosch.make(SEED, 60_000)
    again, y_again = bosch.make(SEED, 60_000, threads=3)
    assert q.dtype == np.uint8 and q.shape == (60_000, 968)
    assert np.array_equal(q, again) and np.array_equal(y, y_again)
    assert abs(float(np.mean(q == 0)) - 0.81) <= 0.02
    assert q.max() == 63 and np.unique(q[q > 0]).size == 63
    sizes = [c.size for c in bosch.STATION_COLUMNS]
    assert len(sizes) == 52 and sum(sizes) == 968
    for cols in bosch.STATION_COLUMNS:
        empty = q[:, cols] == 0
        assert (empty.all(axis=1) | ~empty.any(axis=1)).all()
    assert 0.004 < y.mean() < 0.008
    other, _ = bosch.make(SEED, 1000, stream=1)
    assert not np.array_equal(other, q[:1000])
    for seed in (1, 2, 4100000017, 2**31 + 5):
        assert abs(bosch._Tables(seed).missing_share() - 0.81) <= 0.02
    x = bosch.as_float(q[:100])
    assert np.array_equal(np.isnan(x), q[:100] == 0) and np.array_equal(x[q[:100] > 0],
                                                                         q[:100][q[:100] > 0])


# ---- the reference's two-plane scan ---------------------------------------------------


PARAMS = dict(objective="binary", num_leaves=3, lambda_l2=1.0, min_child_weight=1.0,
              min_data_in_leaf=1, min_split_gain=0.0, learning_rate=0.1, max_bins=64)


def brute_force(qb, g, h, params, bins=64):
    """Every (column, threshold, direction) of one node scored by summing the
    rows each side holds, written out here: left holds the rows whose bin is
    1..t, and the empty cells (bin 0) where missing goes left.  A split whose
    right child would hold empty cells alone is not a candidate when missing
    goes right.  The first of the largest gain in the order direction (left
    first), column, threshold."""
    G, H, C = g.sum(), h.sum(), g.size
    l2, floor = params["lambda_l2"], params["min_child_weight"]
    best = (-np.inf, None)
    for plane in (0, 1):
        for f in range(qb.shape[1]):
            x = qb[:, f]
            for t in range(bins):
                left = (x >= 1) & (x <= t)
                if plane == 0:
                    left |= x == 0
                right = ~left
                if plane == 1 and not (right & (x > 0)).any():
                    continue
                gl, hl, cl = g[left].sum(), h[left].sum(), left.sum()
                if cl < 1 or C - cl < 1 or hl < floor or H - hl < floor:
                    continue
                gain = 0.5 * (gl * gl / (hl + l2) + (G - gl) ** 2 / (H - hl + l2)
                              - G * G / (H + l2))
                if gain > best[0]:
                    best = (gain, (plane, f, t))
    return best


def root_grads(ref, y):
    s0 = gbdt.init_score(y, "binary")
    g, h = gbdt._grad_hess(ref.train.start(s0), ref.train.y, objective="binary")
    return g, h, (np.asarray(ref.train.host(a), np.float64) for a in (g, h))


def test_the_two_plane_scan_finds_the_brute_forces_split():
    """The reference's first split of a table with empty cells against the
    brute force over every (column, threshold, direction), on columns of four
    stations of the family; and its tree's first node carries the
    direction."""
    q, y = bosch.make(SEED, 3000)
    cols = np.concatenate([bosch.STATION_COLUMNS[s][:2] for s in (0, 7, 24, 40)])
    qb = np.ascontiguousarray(q[:, cols])
    ref = BestFirstMissing(PARAMS, Rows(qb, y), None, 4)
    g, h, (gh, hh) = root_grads(ref, y)
    gain, (plane, f, t) = brute_force(qb, gh, hh, PARAMS)
    tree, _ = ref.grow_tree(g, h)
    assert (int(tree.feature[0]), int(np.floor(tree.threshold[0]))) == (f, t)
    assert bool(tree.default_left[0]) is (plane == 0)
    # every node of that grown tree is the brute force's best over its own rows
    hist = ref.level_hist(ref.train.real.astype(np.int32) - 1, g, h, 1, False)
    best, *_ = ref.best_of(ref.plane_gains(hist, PARAMS["min_child_weight"]))
    assert best[0] == pytest.approx(gain, rel=1e-6)


def test_the_tie_goes_left_and_the_mirror_split_is_excluded():
    """Written out by hand: a column whose empty rows carry the signal.
    Plane 0 at t = 0 (the empty rows left, every value right) and plane 1 at
    the top threshold (every value left, the empty rows right) are one
    partition; the second is excluded, so the split learns "left".  On a
    column with no empty cell both planes give the same gains and plane 0
    wins the tie."""
    n = 64
    qb = np.zeros((n, 2), np.uint8)
    qb[:, 0] = np.where(np.arange(n) < 16, 0, 1 + np.arange(n) % 8)     # 16 empty rows
    qb[:, 1] = 1 + np.arange(n) % 8                                      # none empty
    y = (np.arange(n) < 12).astype(np.float32)        # the empty rows hold the positives
    ref = BestFirstMissing(PARAMS, Rows(qb, y), None, 4)
    g, h, (gh, hh) = root_grads(ref, y)
    hist = ref.level_hist(ref.train.real.astype(np.int32) - 1, g, h, 1, False)
    gains = ref.plane_gains(hist, PARAMS["min_child_weight"])[0]         # [plane, F, B]
    assert np.isfinite(gains[0, 0, 0]) and gains[1, 0, 8] == -np.inf      # the mirror
    assert np.array_equal(gains[0, 1], gains[1, 1], equal_nan=True)       # no empty cell
    best, plane, f, t = (v[0] for v in ref.best_of(gains[None]))
    assert (int(plane), int(f), int(t)) == (0, 0, 0)
    assert best == pytest.approx(brute_force(qb, gh, hh, PARAMS, bins=64)[0], rel=1e-9)
    # on the column with no empty cell alone, the tie goes to plane 0
    only = np.where(np.arange(2)[None, :, None] == 1, gains, -np.inf)
    assert int(ref.best_of(only[None])[1][0]) == 0


def test_routing_sends_empty_cells_by_the_nodes_direction():
    from benchmark.reference.gbdt_bestfirst_missing import MissTree

    qb = np.array([[0], [3], [5], [0]], np.uint8)
    rows = Rows(qb, np.zeros(4, np.float32))
    ref = BestFirstMissing(PARAMS, rows, None, 2)
    for dleft, want in ((True, [1, 1, 2, 1]), (False, [2, 1, 2, 2])):
        tree = MissTree(np.array([0, -1, -1], np.int32), np.array([4.5, 0, 0], np.float32),
                        np.array([1, 0, 0], np.int32), np.array([2, 0, 0], np.int32),
                        np.zeros(3), np.zeros(3), np.array([dleft, True, True]))
        assert rows.host(ref.route(rows, tree, 1)).tolist() == want


# ---- the reader and the runner's counter ---------------------------------------------


def test_missing_device_ms_reads_the_scope_and_nothing_where_it_is_absent(monkeypatch):
    from benchmark.harness import scopes
    from benchmark.layer_metrics import missing_device_ms

    facts = {"window_iters": 4}
    filled = {"scoped": True, "scope_s": {"dryad.missing": 0.2, "dryad.route": 2.0}}
    monkeypatch.setattr(scopes, "read", lambda: filled)
    assert missing_device_ms.read(facts) == pytest.approx(50.0)
    monkeypatch.setattr(scopes, "read", lambda: {"scoped": True, "scope_s": {"dryad.route": 2.0}})
    assert missing_device_ms.read(facts) is None          # a program with no such scope
    monkeypatch.setattr(scopes, "read", lambda: {})
    assert missing_device_ms.read(facts) is None          # no trace at all


def test_the_runner_reads_the_counter_where_the_program_keeps_it():
    from benchmark.runners import train_job_missing as runner
    from dryad_tpu.obs import Registry, set_default_registry

    reg = Registry()
    old = set_default_registry(reg)
    try:
        assert runner.missing_counters() == {}                   # an empty registry
        dirs = reg.counter(runner.DEFAULT_DIRS, "x")
        dirs.labels(dir="left").inc(30)
        dirs.labels(dir="right").inc(10)
        assert runner.missing_counters() == {"left": 30.0, "right": 10.0}
    finally:
        set_default_registry(old)
    q = np.zeros((4, 2), np.uint8)
    q[0, 0] = 5
    facts = runner._facts(q, {"left": 1.0}, {"left": 4.0, "right": 1.0})
    assert facts == {"empty_share": 7 / 8, "split_dirs": {"left": 3.0, "right": 1.0},
                     "learned_right_share": 0.25}
    assert runner._facts(q, {}, {})["learned_right_share"] is None


# ---- the manifest ---------------------------------------------------------------------


def check_cell_resolves(manifest, root=mf.ROOT):
    assert mf.problems(manifest, root) == []
    cell = mf.Cell(manifest, CELL, root)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "bosch1m_leaf255",
                                                                 "job_missing")
    runner = cell.runner()
    assert runner.__name__.endswith("train_job_missing") and "order_gain_gap" in runner.NUMBERS
    assert set(cell.limits) == {"job_died", "checkpoint_iters_gap", *runner.NUMBERS}
    config = cell.config
    assert (config["data"]["train_rows"], config["data"]["valid_rows"],
            config["data"]["features"]) == (1_000_000, 183_747, 968)
    assert config["data"]["lines"] == {"columns": list(bosch.LINE_COLUMNS),
                                       "stations": list(bosch.LINE_STATIONS)}
    assert config["params"]["max_bins"] == 64 and config["params"]["max_depth"] == -1
    entry = next(c for c in manifest["configs"] if c["name"] == "bosch1m_leaf255")
    assert entry["reduced"] == ["trees_grown"] == list(config["reduced_why"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    with open(mf.traffic_path("job_bestfirst", root)) as f:
        bestfirst = json.load(f)
    assert cell.traffic == {**bestfirst, "runner": "train_job_missing",
                            "what": cell.traffic["what"]}
    return cell


def test_the_manifest_resolves_the_cell_and_the_policy_gives_its_cap():
    cell = check_cell_resolves(mf.load())
    runner, config = cell.runner(), cell.config
    params, cap = runner.job_params(config, rehearsal=False)
    assert (params, cap) == (config["params"], 10)
    from dryad_tpu.config import effective_depth_params, make_params
    assert effective_depth_params(make_params(params), 968, 64, 1_000_000).max_depth == cap
    small, small_cap = runner.job_params(config, rehearsal=True)
    assert effective_depth_params(make_params(small), 968, 64, 6000).max_depth == small_cap == 8


def check_accepted_metrics(manifest, root=mf.ROOT):
    from test_bench_scopes import accepted_of

    cell = mf.Cell(manifest, CELL, root)
    ours = accepted_of(manifest, CELL, root)
    # what the plan-path leaf-wise cells report: no layout, no categorical column
    assert ours == accepted_of(manifest, "epsilon400k_d6.job", root)
    assert not ours & {"perm_time_share", "layout_device_ms", "cat_device_ms"}
    assert "missing_device_ms" in {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"iters_per_s", "setup_s"}
    listed = set()
    for entry in manifest["per_layer"]:
        lists = entry.get("workloads")
        if lists is None or CELL not in lists:
            continue
        assert entry["moves"] == "iters_per_s"
        listed.add(entry["name"])
    assert len(listed) >= 15 and {"hist_roofline", "missing_device_ms"} <= listed
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == "missing_device_ms"]
    assert entry["workloads"] == [CELL] and entry["source"] == "device_trace"
    assert entry["layer"] == "growers, split scan, partition"
    assert os.path.exists(mf.metric_path("missing_device_ms", root))


def check_manifest(manifest, root=mf.ROOT):
    """This cell's part of the manifest, for the accepted one and for one
    that later entries have grown (below)."""
    check_cell_resolves(manifest, root)
    check_accepted_metrics(manifest, root)


def test_the_cell_reports_what_the_plan_path_cells_report_and_its_own():
    check_accepted_metrics(mf.load())


def test_the_cells_manifest_checks_hold_after_appends(grown, readers_of):
    """The accepted cells' test of a manifest grown by appending, for this
    cell: one more cell appended to ``workloads`` and to every list that names
    the headline cell, and metrics appended to ``per_layer``."""
    manifest, root = grown
    readers_of(root)
    assert CELL in [w["name"] for w in manifest["workloads"]]
    theirs = {m["name"] for m in mf.Cell(manifest, CELL, root).per_layer}
    assert {"sham_every", "sham_listed"} <= theirs
    check_manifest(manifest, root)
    test_bench_setup.check_manifest(manifest, root)


# ---- the whole cell ---------------------------------------------------------------------


def test_rehearsal_of_the_cell_is_correct_and_prints_no_metric():
    done = run_cli(ROOT, "--workload", CELL, "--seed", str(SEED), "--seconds", "1",
                   "--trace", "0", "--rehearse-cpu", timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert set(line["compared"]) == set(mf.Cell(mf.load(), CELL).limits)
    facts = json.loads(next(ln for ln in done.stderr.splitlines()
                            if ln.startswith("facts: "))[len("facts: "):])
    missing = facts["missing"]
    assert abs(missing["empty_share"] - 0.81) <= 0.02
    assert 0.1 < missing["learned_right_share"] < 0.9
    assert sum(missing["split_dirs"].values()) == facts["leafwise"][
        "dryad_leafwise_selected_splits_total"]
    assert missing["dryad_leafwise_depth_cap"] == facts["leafwise"]["depth_cap"] == 8
    assert facts["policy"]["hist_backend"] and facts["flips"]["strict_flip_share"] == 0.0
