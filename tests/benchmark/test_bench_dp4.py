"""The data-parallel cell (``criteo12m_leaf255.job_dp4``): its data family, the
files the manifest resolves, the runner's refusal of a program whose growth
policy counts the global rows on one device, the reference with its histogram
passes spread over devices against the plain one, ``judge`` under the cell's
limits, a rehearsal of the command on a four-device CPU mesh, and this cell's
own fault, one shard's part left out of every histogram sum, planted underneath
the real path.  The readings tool's run is ``slow``."""

import argparse
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from bench_fixtures import ROOT, run_cli  # noqa: E402

from benchmark.datagen import criteo  # noqa: E402
from benchmark.harness import manifest as mf  # noqa: E402
from benchmark.harness.result import judge  # noqa: E402

CELL = "criteo12m_leaf255.job_dp4"
SOUND = {"job_died": 0.0, "checkpoint_iters_gap": 0.0, "init_score_gap": 1e-7,
         "split_flip_share": 0.0, "order_gain_gap": 1e-5, "leaf_value_gap": 1e-5,
         "valid_metric_gap": 1e-7, "window_cover_gap": 0.0, "window_leaf_value_gap": 1e-5,
         "window_root_gain_gap": 0.0}


def over(compared: dict) -> list:
    return [k for k, c in compared.items() if c["value"] is None or not c["value"] <= c["limit"]]


# ---- the data family -----------------------------------------------------------


def test_the_family_draws_67_bin_columns_and_a_rare_click_from_the_seed():
    q, y = criteo.make(2_147_483_999, 200_000, 67, stream=0)
    assert q.shape == (200_000, 67) and q.dtype == np.uint8 and int(q.max()) == 254
    assert y.dtype == np.float32 and set(np.unique(y)) == {0.0, 1.0}
    assert abs(float(y.mean()) - criteo.POSITIVE_RATE) < 0.004
    q2, y2 = criteo.make(2_147_483_999, 200_000, 67, stream=0)
    assert np.array_equal(q, q2) and np.array_equal(y, y2)
    qv, yv = criteo.make(2_147_483_999, 50_000, 67, stream=1)
    assert not np.array_equal(q[:50_000], qv)
    assert abs(float(yv.mean()) - criteo.POSITIVE_RATE) < 0.006
    # the label follows the columns: a click's linear score is higher
    w = criteo.block_rng(2_147_483_999, 99, 0).standard_normal(67).astype(np.float32)
    s = criteo.Z_OF_BIN[q] @ w
    assert s[y == 1].mean() > s[y == 0].mean() + 1.0


# ---- the manifest's entries and files -------------------------------------------


def check_cell_resolves(manifest, root=mf.ROOT):
    assert mf.problems(manifest, root) == []
    cell = mf.Cell(manifest, CELL, root)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (4, "criteo12m_leaf255", "job_dp4")
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= max(1, len(manifest["workloads"]) // 4)
    runner = cell.runner()
    assert runner.__name__.endswith("train_job_dp4") and "order_gain_gap" in runner.NUMBERS
    assert set(cell.limits) == {"job_died", "checkpoint_iters_gap", *runner.NUMBERS}
    assert cell.limits["window_cover_gap"] == 0 and cell.limits["job_died"] == 0
    config = cell.config
    assert config["data"] == {"family": "criteo", "train_rows": 12_000_000,
                              "valid_rows": 600_000, "features": 67}
    assert config["depth_cap"] == 12 and config["params"]["max_depth"] == -1
    assert config["params"]["num_leaves"] == 255 and config["params"]["min_data_in_leaf"] == 20
    entry = next(c for c in manifest["configs"] if c["name"] == "criteo12m_leaf255")
    assert entry["reduced"] == ["train_rows", "trees_grown"] == list(config["reduced_why"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    return cell


def check_accepted_metrics(manifest, root=mf.ROOT):
    from test_bench_scopes import NEW_READERS, accepted_of, check_readers_listed

    check_readers_listed(manifest)
    ours = accepted_of(manifest, CELL, root)
    assert ours == accepted_of(manifest, "higgs10m_d8.job", root)
    listed = set()
    for entry in manifest["per_layer"]:
        lists = entry.get("workloads")
        if lists is None or entry["name"] not in ours:
            continue
        assert CELL in lists and entry["moves"] == "iters_per_s"
        listed.add(entry["name"])
    sixteen = {"hist_roofline", "hist_time_share", "perm_time_share", "other_device_ms",
               "ckpt_stall_ms", *NEW_READERS}
    assert len(sixteen) == 16 and sixteen <= listed


def check_manifest(manifest, root=mf.ROOT):
    """This cell's part of the manifest, for the accepted one and for one
    that later entries have grown (``test_bench_append.py``)."""
    check_cell_resolves(manifest, root)
    check_accepted_metrics(manifest, root)


def test_the_manifest_resolves_the_cell_and_its_files():
    cell = check_cell_resolves(mf.load())
    runner, config = cell.runner(), cell.config
    params, cap = runner.job_params(config, rehearsal=False)
    assert (params, cap) == (config["params"], 12)
    # the program's own policy gives the cap the file states on four shards, and
    # refuses the shape on one: the cell needs its chips
    from dryad_tpu.config import effective_depth_params, make_params
    assert effective_depth_params(make_params(params), 67, 256, 12_000_000, 4).max_depth == cap
    assert effective_depth_params(make_params(params), 67, 256, 12_000_000).max_depth == -1
    small, small_cap = runner.job_params(config, rehearsal=True)
    assert effective_depth_params(make_params(small), 67, 256, 3000, 4).max_depth == small_cap
    runner.check_policy(params, cap, 67, 12_000_000, 4)


def test_the_cell_reads_the_accepted_metrics_and_adds_none():
    """Every accepted per-layer metric of the depth-wise Higgs cell, the wired
    layout's two among them, through the accepted readers: the cell is named
    in each of the sixteen of them that list their cells, wherever it stands
    there."""
    check_accepted_metrics(mf.load())


def test_the_runner_refuses_a_program_whose_policy_counts_the_global_rows(monkeypatch):
    import dryad_tpu.config as config
    from benchmark.harness.device import NoChip
    from benchmark.runners import train_job_dp4 as runner

    cell = mf.Cell(mf.load(), CELL)
    params = cell.config["params"]
    real = config.effective_depth_params
    # the parent's signature: no shard count
    monkeypatch.setattr(config, "effective_depth_params",
                        lambda p, features, bins, rows=None: real(p, features, bins, rows))
    with pytest.raises(runner.NotThisConfiguration, match="no shard count"):
        runner.check_policy(params, 12, 67, 12_000_000, 4)
    assert issubclass(runner.NotThisConfiguration, NoChip)      # run.py exits 2 on it
    # a policy that gives the shape another cap
    monkeypatch.setattr(config, "effective_depth_params", real)
    with pytest.raises(runner.NotThisConfiguration, match="max_depth -1"):
        runner.check_policy(params, 12, 67, 12_000_000, 1)


def test_the_runner_reads_the_exchanges_gauges_where_the_program_keeps_them():
    from benchmark.runners import train_job_dp4 as runner
    from dryad_tpu.obs import Registry, set_default_registry
    from dryad_tpu.obs.comm import export_comm_stats

    # a registry of its own: the process's holds the jobs of every test before this one
    old = set_default_registry(Registry())
    try:
        assert runner.comm_gauges() == {}                      # a job with no mesh
        export_comm_stats({"hist_reduce": "fused", "n_shards": 4, "psum_bytes_per_iter": 8.0e8,
                           "collective_calls_per_iter": 13, "collective_bytes_per_iter": 8.0e8,
                           "reduce_scatter_bytes_per_iter": 0, "all_gather_bytes_per_iter": 0},
                          growth="leafwise")
        comm = runner.comm_gauges()
    finally:
        set_default_registry(old)
    assert comm["arm"] == "fused" and comm["shards"] == "4" and comm["growth"] == "leafwise"
    assert comm["dryad_comm_psum_bytes_per_iter"] == 8.0e8
    assert comm["dryad_comm_collective_calls_per_iter"] == 13.0
    assert isinstance(runner.policy_choices(), dict)


# ---- judge under the cell's limits ------------------------------------------------


def test_judge_under_the_cells_limits():
    limits = mf.Cell(mf.load(), CELL).limits
    ok, compared = judge(SOUND, limits)
    assert ok and over(compared) == []
    # one shard's part left out: a quarter of the rows missing from every count,
    # and splits chosen on the rest
    ok, compared = judge({**SOUND, "window_cover_gap": 0.25, "split_flip_share": 0.3}, limits)
    assert not ok and over(compared) == ["split_flip_share", "window_cover_gap"]
    for name in limits:
        ok, compared = judge({**SOUND, name: float(limits[name]) * 1.5 + 1e-9}, limits)
        assert not ok and over(compared) == [name]
    ok, compared = judge({k: v for k, v in SOUND.items() if k != "order_gain_gap"}, limits)
    assert not ok and over(compared) == ["order_gain_gap"]     # a number left out fails


# ---- the reference, spread over devices ---------------------------------------------


def test_the_spread_reference_makes_the_plain_references_histograms():
    import jax
    import jax.numpy as jnp

    from benchmark.reference.gbdt import _grad_hess
    from benchmark.reference.gbdt_bestfirst import BestFirst, Rows
    from benchmark.reference.gbdt_bestfirst_dp import BestFirstSpread

    assert len(jax.devices()) >= 4, "conftest must provide the virtual devices"
    # 100,000 rows: four blocks of 32,768, so three devices get a block of rows
    # each, one the rest and no device sits idle; five devices: a padded block
    q, y = criteo.make(5, 100_000, 67)
    params = dict(objective="binary", metric="auc", num_leaves=15, max_depth=-1,
                  learning_rate=0.1, lambda_l2=1.0, min_child_weight=0.001, min_data_in_leaf=20,
                  min_split_gain=0.0)
    plain = BestFirst(params, Rows(q, y), None, 8)
    g, h = _grad_hess(plain.train.start(-3.3), plain.train.y, objective="binary")
    node = jnp.asarray(np.where(np.arange(plain.train.padded) < 100_000,
                                np.arange(plain.train.padded) % 3, -1).astype(np.int32)
                       ).reshape(plain.train.real.shape)
    want = plain.level_hist(node, g, h, 3, False)
    assert want.shape == (3, 3, 67, 256) and want[2].sum() == 67 * 100_000
    for n in (4, 5, 1):
        spread = BestFirstSpread(params, Rows(q, y), None, 8, jax.devices()[:n])
        assert (spread.mesh is None) == (n == 1)
        got = spread.level_hist(node, g, h, 3, False)
        assert np.array_equal(got[2], want[2])                        # counts: exact
        np.testing.assert_allclose(got[:2], want[:2], rtol=1e-6, atol=1e-6)
    rounded = BestFirstSpread(params, Rows(q, y), None, 8, jax.devices()[:4])
    np.testing.assert_allclose(rounded.level_hist(node, g, h, 3, True),
                               plain.level_hist(node, g, h, 3, True), rtol=1e-6, atol=1e-6)


# ---- the command, on the CPU mesh -------------------------------------------------


def test_rehearsal_of_the_cell_on_four_host_devices_prints_no_metric():
    done = run_cli(ROOT, "--workload", CELL, "--seed", "2147483999", "--seconds", "1",
                   "--trace", "0", "--rehearse-cpu", timeout=900,
                   env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert line["failed"] == 0 and line["attempted"] >= 1      # what a 1 s window held
    assert set(line["compared"]) == set(mf.Cell(mf.load(), CELL).limits)
    for name, (value, limit) in line["compared"].items():
        if limit == 0:
            assert value == 0, name
    facts = json.loads(next(ln for ln in done.stderr.splitlines()
                            if ln.startswith("facts: "))[len("facts: "):])
    assert facts["shard_rows"] == 750 and facts["level_passes"] == 5
    assert facts["leafwise"]["depth_cap"] == 8
    assert facts["leafwise"]["dryad_leafwise_selected_splits_total"] == 14 * facts["window_iters"]
    assert facts["comm"]["arm"] == "fused" and facts["comm"]["shards"] == "4"
    assert facts["comm"]["dryad_comm_psum_bytes_per_iter"] > 0
    assert facts["policy"]["hist_reduce"] == "fused"


def drive(cell, trace=0):
    from benchmark.runners import train_job_dp4

    args = argparse.Namespace(seed=11, seconds=0.5, trace=trace, rehearse_cpu=True)
    return train_job_dp4.run(cell, args, time.perf_counter())


def test_one_shards_part_left_out_of_the_sum_is_not_correct(monkeypatch):
    """This cell's own fault, planted where the exchange is made: the last
    shard's histograms are dropped before the all-reduce, so every sum lacks
    a quarter of the rows; everything else of the run is the command's."""
    import jax

    from dryad_tpu.engine import distributed

    real = distributed.reduce_hist

    def lossy(hist, axis_name, hist_reduce="fused"):
        if axis_name is not None:
            last = distributed.axis_shards(axis_name) - 1
            hist = hist * (jax.lax.axis_index(axis_name) != last).astype(hist.dtype)
        return real(hist, axis_name, hist_reduce)

    jax.clear_caches()                     # a program traced before the fault would hide it
    monkeypatch.setattr(distributed, "reduce_hist", lossy)
    try:
        out = drive(mf.Cell(mf.load(), CELL))
    finally:
        monkeypatch.undo()
        jax.clear_caches()                 # and the faulty one must not outlive the test
    assert out["correct"] is False
    wrong = over(out["compared"])
    assert {"split_flip_share", "window_cover_gap"} <= set(wrong), out["compared"]
    assert out["numbers"]["window_cover_gap"] > 0.1 and out["numbers"]["job_died"] == 0.0


@pytest.mark.slow
def test_a_sound_traced_rehearsal_is_correct_and_runs_the_readers():
    out = drive(mf.Cell(mf.load(), CELL), trace=1)
    assert out["correct"] is True, out["compared"]
    assert out["metrics"] == {} and "ckpt_stall_ms" in out["facts"]["layer_metrics_read"]
    assert out["facts"]["comm"]["shards"] == "4"


@pytest.mark.slow
def test_the_readings_tool_finds_the_control_and_every_fault_not_correct(tmp_path):
    import subprocess

    out = tmp_path / "rows.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                                        "readings_dp4.py"),
                           "--workload", CELL, "--seeds", "0", "--grown", "1", "--rehearse-cpu",
                           "--out", str(out)], cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=1500)
    assert done.returncode == 0, done.stderr[-3000:]
    rows = {r["kind"]: r for r in json.loads(out.read_text())}
    assert rows.pop("reference_float32")["correct"] is True
    from benchmark.tools.readings_dp4 import FAULTS
    assert set(rows) == {"control_bfloat16", *FAULTS}
    faults = {k: r for k, r in rows.items() if k != "control_bfloat16"}
    assert not any(r["correct"] for r in faults.values()), faults
    assert {"split_flip_share", "window_cover_gap"} <= set(rows["fault_quarter_left_out"]["over"])
    assert rows["fault_grown_level_by_level"]["over"] == ["order_gain_gap"]
