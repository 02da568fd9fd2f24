"""The best-first cell (``higgs10m_leaf255.job_bestfirst``): its reference
against the program's leaf-wise job and against a sequential best-first
written here, what ``order_gain_gap`` flags, the count of least work, the
files the manifest resolves, and a rehearsal of the command.  All on the CPU
at a few thousand rows x 8 features and 15 leaves; the longer versions (the
runner with faults planted underneath, the readings tool) are ``slow``."""

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from bench_fixtures import ROOT, run_cli  # noqa: E402

from benchmark.counts import gbdt_bestfirst as counts_bf  # noqa: E402
from benchmark.counts import gbdt_iteration as counts  # noqa: E402
from benchmark.datagen import higgs  # noqa: E402
from benchmark.harness import manifest as mf  # noqa: E402
from benchmark.harness.result import judge  # noqa: E402
from benchmark.reference import gbdt  # noqa: E402
from benchmark.reference.gbdt_bestfirst import BestFirst, Rows, depths_of, split_order  # noqa: E402

CELL = "higgs10m_leaf255.job_bestfirst"
PARAMS = dict(objective="binary", metric="auc", growth="leafwise", num_leaves=15, max_depth=-1,
              max_bins=256, learning_rate=0.1, num_trees=4, lambda_l2=1.0, min_child_weight=20,
              min_data_in_leaf=1, min_split_gain=0.0, hist_precision="exact", seed=11)
CAP = 8            # the program's policy for 15 leaves: ceil(log2 15) + 4
# every number of a sound job against the reference, with what sets it: sums of
# 4000 float32 terms agree to 1e-6; a flipped split or a lost step reads 0.07 and more
TOLERANCE = {"init_score_gap": 1e-6, "split_flip_share": 0.0, "order_gain_gap": 1e-9,
             "leaf_value_gap": 1e-5, "valid_metric_gap": 1e-6, "window_cover_gap": 0.0,
             "window_leaf_value_gap": 1e-5, "window_root_gain_gap": 1e-9}


@pytest.fixture(scope="module")
def data():
    q, y = higgs.make(7, 4000, 8, stream=0)
    qv, yv = higgs.make(7, 1000, 8, stream=1)
    return q, y, qv, yv


@pytest.fixture(scope="module")
def ref(data):
    q, y, qv, yv = data
    return BestFirst(PARAMS, Rows(q, y), Rows(qv, yv), CAP)


@pytest.fixture(scope="module")
def grown(ref):
    """Three trees of the reference itself, best-first."""
    return ref.grow(3)


def test_the_systems_leafwise_job_agrees_with_the_reference(data, ref):
    import dryad_tpu as dryad
    from benchmark.runners.train_job import trees_of

    q, y, qv, yv = data
    ds = dryad.Dataset(q.astype(np.float32), y, max_bins=256)
    vds = ds.bind(qv.astype(np.float32), yv)
    evals = {}

    def note(it, info):
        evals[it] = next(v for k, v in info.items() if k.startswith("valid"))

    booster = dryad.train(PARAMS, ds, valid_sets=[vds], backend="tpu", callbacks=[note])
    assert booster.params.max_depth == CAP          # asked for -1: the policy's cap, on record
    job = {"trees": trees_of(booster), "init_score": float(booster.init_score[0]), "evals": evals}
    numbers = {**ref.follow(job, 3), **ref.follow_window(job, 2)}
    ok, compared = judge(numbers, TOLERANCE)
    assert ok, compared
    assert numbers["cap_stopped_steps"] == [0, 0, 0] and max(numbers["tree_depths"]) <= CAP
    assert [row["leaves"] for row in numbers["per_tree"]] == [15, 15, 15]


def sequential_best_first(q, g, h, p, cap, leaves, skip_at=None):
    """Best-first as the textbook has it, one leaf at a time, float64
    histograms by ``bincount``; ``skip_at``: at that step take the second
    best leaf (a selection that skipped one).  Returns the splits in order:
    (node, feature, bin, left id, right id)."""
    def best_split(rows):
        G, H = g[rows].sum(), h[rows].sum()
        top = (-np.inf, 0, 0)
        for f in range(q.shape[1]):
            gl = np.cumsum(np.bincount(q[rows, f], g[rows], 256))
            hl = np.cumsum(np.bincount(q[rows, f], h[rows], 256))
            cl = np.cumsum(np.bincount(q[rows, f], None, 256))
            ok = ((cl >= p["min_data_in_leaf"]) & (len(rows) - cl >= p["min_data_in_leaf"])
                  & (hl >= p["min_child_weight"]) & (H - hl >= p["min_child_weight"]))
            gain = 0.5 * (gl ** 2 / (hl + p["lambda_l2"]) + (G - gl) ** 2 / (H - hl + p["lambda_l2"])
                          - G ** 2 / (H + p["lambda_l2"]))
            gain = np.where(ok, gain, -np.inf)
            if gain.max() > top[0]:
                top = (float(gain.max()), f, int(gain.argmax()))
        return top
    frontier = {0: (np.arange(len(q)), 0)}          # node -> (rows, depth)
    found = {0: best_split(frontier[0][0])}
    splits, n_nodes = [], 1
    for step in range(leaves - 1):
        open_ = sorted((n for n, (rows, d) in frontier.items() if d < cap and found[n][0] > 0),
                       key=lambda n: -found[n][0])
        if not open_:
            break
        n = open_[1] if step == skip_at and len(open_) > 1 else open_[0]
        rows, d = frontier.pop(n)
        _, f, b = found[n]
        left = q[rows, f] <= b
        for child, part in ((n_nodes, rows[left]), (n_nodes + 1, rows[~left])):
            frontier[child] = (part, d + 1)
            found[child] = best_split(part)
        splits.append((n, f, b, n_nodes, n_nodes + 1))
        n_nodes += 2
    return splits


def first_gradients(ref):
    s0 = gbdt.init_score(ref.train.y_host, "binary")
    return gbdt._grad_hess(ref.train.start(s0), ref.train.y, objective="binary")


def tree_of(splits, size):
    tree = gbdt.Tree(np.full(size, -1, np.int32), np.zeros(size, np.float32),
                     np.zeros(size, np.int32), np.zeros(size, np.int32), np.zeros(size))
    for n, f, b, left, right in splits:
        tree.feature[n], tree.threshold[n], tree.left[n], tree.right[n] = f, b + 0.5, left, right
    return tree


def test_the_references_grow_is_sequential_best_first(data, ref, grown):
    q = data[0]
    g, h = first_gradients(ref)
    splits = sequential_best_first(q, np.asarray(ref.train.host(g), np.float64),
                                   np.asarray(ref.train.host(h), np.float64), PARAMS, CAP, 15)
    tree = grown["trees"][0]
    assert len(splits) == 14
    for n, f, b, left, right in splits:
        assert (tree.feature[n], tree.threshold[n], tree.left[n], tree.right[n]) \
            == (f, b + 0.5, left, right)
    assert split_order(tree) == [s[0] for s in splits]
    assert int((tree.feature >= 0).sum()) == 14 and depths_of(tree).max() <= CAP
    # numbered otherwise (children swapped into other ids), the order comes from a
    # greedy replay over the tree's own splits by gain
    new_id = np.arange(len(tree.feature))
    new_id[1:29] = new_id[1:29][::-1]
    moved = gbdt.Tree(*(np.zeros_like(a) for a in (tree.feature, tree.threshold, tree.left,
                                                   tree.right, tree.value)))
    moved.feature[:] = -1
    for n in range(29):
        m = new_id[n]
        moved.feature[m], moved.threshold[m] = tree.feature[n], tree.threshold[n]
        if tree.feature[n] >= 0:
            moved.left[m], moved.right[m] = new_id[tree.left[n]], new_id[tree.right[n]]
    gain_of = np.zeros(len(tree.feature))
    gain_of[[new_id[s[0]] for s in splits]] = np.arange(14, 0, -1)     # falling, as best-first gives
    assert split_order(moved, gain_of) == [int(new_id[s[0]]) for s in splits]


@pytest.mark.parametrize("fault", ["grown_level_by_level", "skipped_a_leaf"])
def test_order_gain_gap_flags(data, ref, grown, fault):
    g, h = first_gradients(ref)
    sound = ref.one_tree(g, h, grown["trees"][0])[1]
    assert sound["order_gain_gap"] == 0.0 and sound["flips"] == [(0, 14)]
    if fault == "grown_level_by_level":
        by_level = gbdt.Reference({**PARAMS, "max_depth": 4}, ref.train, ref.valid).grow(1)
        tree = by_level["trees"][0]
        assert int((tree.feature >= 0).sum()) == 14      # as many leaves, every split its node's best
    else:
        splits = sequential_best_first(data[0], np.asarray(ref.train.host(g), np.float64),
                                       np.asarray(ref.train.host(h), np.float64), PARAMS, CAP, 15,
                                       skip_at=5)
        tree = tree_of(splits, 31)
    facts = ref.one_tree(g, h, tree)[1]
    assert facts["flips"][0][0] == 0, "each split is still the best of its own node"
    assert facts["order_gain_gap"] > 0.05, facts["order_gaps"]


def test_an_empty_tree_and_a_split_past_the_cap_read_one(ref, grown):
    g, h = first_gradients(ref)
    tree = grown["trees"][0]
    empty = dataclasses.replace(tree, feature=np.full_like(tree.feature, -1))
    assert ref.one_tree(g, h, empty)[1]["order_gain_gap"] == 1.0
    shallow = BestFirst(PARAMS, ref.train, ref.valid, int(depths_of(tree).max()) - 1)
    assert shallow.one_tree(g, h, tree)[1]["order_gain_gap"] == 1.0


def test_level_passes_bounds_the_rows_a_tree_needs(grown):
    assert [counts_bf.level_passes(n) for n in (1, 2, 15, 16, 17, 255, 256)] \
        == [1, 2, 5, 5, 6, 9, 9]
    for tree in grown["trees"]:
        need = counts_bf.rows_needed(tree.left, tree.right, tree.cover)
        assert 4000 < need <= 4000 * (1 + (counts_bf.level_passes(15) - 1) / 2)
    shape = {"rows": 10_000_000, "features": 28, "bins": 256, "bin_bytes": 1, "trees": 1,
             "depth": counts_bf.level_passes(255)}
    work = counts.of_shape(shape)
    assert work["ops"] == 2 * 28 * 5 * 10_000_000 and work["bytes"] > 5 * 10_000_000 * 36


def check_cell_resolves(manifest, root=mf.ROOT):
    assert mf.problems(manifest, root) == []
    cell = mf.Cell(manifest, CELL, root)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "higgs10m_leaf255", "job_bestfirst")
    runner = cell.runner()
    assert runner.__name__.endswith("train_job_bestfirst") and "order_gain_gap" in runner.NUMBERS
    assert set(cell.limits) == {"job_died", "checkpoint_iters_gap", *runner.NUMBERS}
    assert cell.config["depth_cap"] == 12 and cell.config["params"]["max_depth"] == -1
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu", "hist_roofline", "split_scan_device_ms", "other_device_ms"} <= names
    assert {"perm_time_share", "layout_device_ms"} <= names          # the wired layout runs
    return cell


def check_accepted_metrics(manifest, root=mf.ROOT):
    from test_bench_scopes import accepted_of, check_readers_listed

    check_readers_listed(manifest)
    ours = accepted_of(manifest, CELL, root)
    assert ours == accepted_of(manifest, "higgs10m_d8.job", root)
    for entry in manifest["per_layer"]:
        assert mf.metric_reader(entry["name"]) is not None
        if entry["name"] in ours and "workloads" in entry:
            assert CELL in entry["workloads"] and entry["moves"] == "iters_per_s"


def check_manifest(manifest, root=mf.ROOT):
    """This cell's part of the manifest, for the accepted one and for one
    that later entries have grown (``test_bench_append.py``)."""
    check_cell_resolves(manifest, root)
    check_accepted_metrics(manifest, root)


def test_the_manifest_resolves_the_cell_and_its_files():
    cell = check_cell_resolves(mf.load())
    runner = cell.runner()
    params, cap = runner.job_params(cell.config, rehearsal=False)
    assert (params, cap) == (cell.config["params"], 12)
    # the program's own policy gives the cap the file states
    from dryad_tpu.config import effective_depth_params, make_params
    assert effective_depth_params(make_params(params), 28, 256, 10_000_000).max_depth == cap
    small, small_cap = runner.job_params(cell.config, rehearsal=True)
    assert effective_depth_params(make_params(small), 28, 256, 3000).max_depth == small_cap


def test_the_cell_reads_the_accepted_metrics_and_adds_none():
    """The cell reports every accepted per-layer metric of the depth-wise
    Higgs cell, the wired layout's two among them (it runs the layout),
    through the accepted readers, and no metric of its own: ``dryad.select``
    and the two counters are read from the traced run's printout and
    ``facts`` (PERF.md section 7)."""
    check_accepted_metrics(mf.load())


def test_the_runner_takes_the_leafwise_counters_where_the_program_keeps_them():
    from benchmark.runners import train_job_bestfirst as runner
    from dryad_tpu.obs.registry import default_registry

    before = runner.leafwise_counters()
    assert set(before) <= {*runner.COUNTERS, "depth_cap"}      # {} from a program that keeps none
    reg = default_registry()
    was = reg.enabled
    reg.enabled = True
    try:
        reg.counter(runner.COUNTERS[0], "expanded").inc(400)
        reg.counter(runner.COUNTERS[1], "selected").inc(100)
        reg.gauge("dryad_leafwise_depth_cap", "cap").set(8)
        after = runner.leafwise_counters()
    finally:
        reg.enabled = was
    assert after[runner.COUNTERS[0]] - before.get(runner.COUNTERS[0], 0.0) == 400.0
    assert after[runner.COUNTERS[1]] - before.get(runner.COUNTERS[1], 0.0) == 100.0
    assert after["depth_cap"] >= 8.0


def test_rehearsal_of_the_cell_prints_no_metric():
    done = run_cli(ROOT, "--workload", CELL, "--seed", "2147483999", "--seconds", "1",
                   "--trace", "0", "--rehearse-cpu", timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] >= 5
    assert set(line["compared"]) == set(mf.Cell(mf.load(), CELL).limits)
    for name, (value, limit) in line["compared"].items():
        if limit == 0:
            assert value == 0, name
    facts = json.loads(next(ln for ln in done.stderr.splitlines()
                            if ln.startswith("facts: "))[len("facts: "):])
    assert facts["level_passes"] == 5 and 0 < facts["rows_needed_share"] <= 1
    assert facts["leafwise"]["dryad_leafwise_selected_splits_total"] == 14 * facts["window_iters"]
    assert facts["leafwise"]["depth_cap"] == 8


# ---- the longer versions ----------------------------------------------------


@pytest.fixture(scope="module")
def cell():
    return mf.Cell(mf.load(), CELL)


def drive(cell, trace=0):
    from benchmark.runners import train_job_bestfirst

    args = argparse.Namespace(seed=11, seconds=0.5, trace=trace, rehearse_cpu=True)
    return train_job_bestfirst.run(cell, args, time.perf_counter())


@pytest.mark.slow
def test_a_sound_rehearsal_is_correct_and_a_traced_one_runs_the_readers(cell):
    out = drive(cell, trace=1)
    assert out["correct"] is True, out["compared"]
    assert out["metrics"] == {} and "split_scan_device_ms" in out["facts"]["layer_metrics_read"]


@pytest.mark.slow
def test_a_levelwise_tree_underneath_the_real_path_is_not_correct(cell, monkeypatch):
    """The fault this cell exists to catch, planted where the job's answer is
    made: the trainer is handed ``growth="depthwise"`` at the depth that holds
    the same leaves, and everything else of the run is the command's."""
    from benchmark.runners import train_job

    real = train_job.train_entry

    def by_level(params, ds, **kw):
        leaves = int(params["num_leaves"])
        return real({**params, "growth": "depthwise",
                     "max_depth": max(leaves - 1, 1).bit_length()}, ds, **kw)

    monkeypatch.setattr(train_job, "train_entry", by_level)
    out = drive(cell)
    assert out["correct"] is False
    over = [k for k, (v, lim) in ((k, (c["value"], c["limit"])) for k, c in out["compared"].items())
            if not v <= lim]
    assert over == ["order_gain_gap"], out["compared"]


@pytest.mark.slow
def test_the_readings_tool_finds_the_control_and_every_fault_not_correct(tmp_path):
    import subprocess

    out = tmp_path / "rows.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                                        "readings_bestfirst.py"),
                           "--workload", CELL, "--seeds", "0", "--grown", "1", "--rehearse-cpu",
                           "--out", str(out)], cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    rows = {r["kind"]: r for r in json.loads(out.read_text())}
    assert rows.pop("reference_float32")["correct"] is True
    assert set(rows) == {"control_bfloat16", "fault_state_unchanged", "fault_answer_altered",
                         "fault_grown_level_by_level", "fault_eval_on_half", "fault_half_batch"}
    # the limits are the 10M-row cell's: at 6000 rows the control's rounding is
    # as large, every fault larger
    assert not any(r["correct"] for r in rows.values()), rows
    assert rows["fault_grown_level_by_level"]["over"] == ["order_gain_gap"]
