"""The ranking cell (``mslr2m_leaf255.job_rank``): its reference's λ-gradients
and NDCG against the program's host oracle, the reference against the
program's LambdaMART job, what the control and the four faults of this
mechanism read through ``judge``, the data family, the files the manifest
resolves, and a rehearsal of the command.  All on the CPU at a few thousand
rows and 15 leaves; the longer versions (the runner with faults planted
underneath the real path, the readings tool) are ``slow``."""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from bench_fixtures import ROOT, run_cli  # noqa: E402

from benchmark.datagen import mslr  # noqa: E402
from benchmark.harness import manifest as mf  # noqa: E402
from benchmark.harness.result import judge  # noqa: E402
from benchmark.reference.gbdt_rank import (RankBestFirst, RankRows, lambda_grad_hess,  # noqa: E402
                                           ndcg_at)
from benchmark.runners import train_job  # noqa: E402

CELL = "mslr2m_leaf255.job_rank"
PARAMS = dict(objective="lambdarank", metric="ndcg", ndcg_at=10, sigmoid=1.0,
              lambdarank_truncation=30, growth="leafwise", num_leaves=15, max_depth=-1,
              max_bins=256, learning_rate=0.1, num_trees=4, lambda_l2=1.0, min_child_weight=1.0,
              min_data_in_leaf=1, min_split_gain=0.0, hist_precision="exact", seed=11)
CAP = 8            # the program's policy for 15 leaves: ceil(log2 15) + 4
LENGTHS = {"min": 1, "max": 160, "median": 20, "sigma": 0.6}
# every number of a sound job against the reference, with what sets it: the
# program's float32 λ-sums against float64 ones agree to 1e-6; a flipped split
# or a lost step reads 0.07 and more
TOLERANCE = {"init_score_gap": 0.0, "split_flip_share": 0.0, "order_gain_gap": 1e-9,
             "leaf_value_gap": 1e-5, "valid_metric_gap": 1e-6, "window_cover_gap": 0.0,
             "window_leaf_value_gap": 1e-5, "window_root_gain_gap": 1e-9}


# ---- the reference's own pieces against the program's host oracle -------------


def ragged(seed: int, ties: bool):
    """Queries of 1, 2 and 31 documents, one far longer than the rest, one
    with a single grade, and scores with or without ties."""
    rng = np.random.default_rng(seed)
    lengths = np.array([1, 2, 31, 5, 400, 30, 29, 64, 7, 1, 100])
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    rel = rng.integers(0, 5, offsets[-1]).astype(np.float32)
    rel[offsets[3]:offsets[4]] = 2.0
    score = (rng.integers(0, 6, offsets[-1]) * 0.25 if ties
             else rng.standard_normal(offsets[-1])).astype(np.float32)
    return lengths, offsets, rel, score


@pytest.mark.parametrize("truncation,ties", [(30, True), (30, False), (10, True), (5, False)])
def test_lambda_gradients_agree_with_the_programs_host_oracle(truncation, ties):
    from dryad_tpu.objectives import LambdaRank

    lengths, offsets, rel, score = ragged(3, ties)
    want_g, want_h = LambdaRank(1.0, truncation).grad_hess_np(score, rel, None,
                                                              query_offsets=offsets)
    g, h = lambda_grad_hess(score, rel, lengths, 1.0, truncation)
    assert np.abs(g - want_g).max() <= 1e-6 * np.abs(want_g).max()
    assert np.abs(h - want_h).max() <= 1e-6 * np.abs(want_h).max()
    assert not g[offsets[3]:offsets[4]].any() and not g[0] and not h[0]   # one grade, one document
    every_g, _ = lambda_grad_hess(score, rel, lengths, 1.0, None)
    all_g, _ = LambdaRank(1.0, 10 ** 6).grad_hess_np(score, rel, None, query_offsets=offsets)
    assert np.abs(every_g - all_g).max() <= 1e-6 * np.abs(all_g).max()


@pytest.mark.parametrize("k,ties", [(10, True), (10, False), (3, True)])
def test_ndcg_agrees_with_the_programs_host_metric(k, ties):
    from dryad_tpu.metrics import ndcg_at_k

    lengths, offsets, rel, score = ragged(4, ties)
    assert ndcg_at(rel, score, lengths, k) == pytest.approx(ndcg_at_k(rel, score, offsets, k),
                                                            abs=1e-12)
    none = np.zeros_like(rel)
    assert ndcg_at(none, score, lengths, k) == 1.0      # no relevant document: counted as 1


def test_the_data_family_draws_ragged_queries_from_the_seed():
    q, y, lengths = mslr.make(5, 30_000, 12, stream=0, query_length=LENGTHS)
    again = mslr.make(5, 30_000, 12, stream=0, query_length=LENGTHS)
    assert all(np.array_equal(a, b) for a, b in zip((q, y, lengths), again))
    assert lengths.sum() == 30_000 and lengths.max() == 160 and lengths.min() >= 1
    assert 15 <= np.median(lengths) <= 25 and q.shape == (30_000, 12) and q.dtype == np.uint8
    shares = np.bincount(y.astype(int), minlength=5) / y.size
    assert np.abs(shares - np.array(mslr.SHARES)).max() < 0.02
    other = mslr.make(5, 30_000, 12, stream=1, query_length=LENGTHS)
    assert not np.array_equal(other[2][:50], lengths[:50])
    full = mslr.query_lengths(7, 0, 2_270_296)
    assert full.max() == 1251 and full.sum() == 2_270_296 and 18_000 < full.size < 20_000


# ---- the reference against the program's job, and in the job's place -----------


@pytest.fixture(scope="module")
def data():
    return (mslr.make(7, 3000, 8, stream=0, query_length=LENGTHS),
            mslr.make(7, 1000, 8, stream=1, query_length=LENGTHS))


def reference(data, fault=None):
    (q, y, lengths), (qv, yv, lengths_v) = data
    return RankBestFirst(PARAMS, RankRows(q, y, lengths), RankRows(qv, yv, lengths_v), CAP, fault)


@pytest.fixture(scope="module")
def ref(data):
    return reference(data)


def test_the_systems_ranking_job_agrees_with_the_reference(data, ref):
    import dryad_tpu as dryad
    from benchmark.runners.train_job import trees_of

    (q, y, lengths), (qv, yv, lengths_v) = data
    ds = dryad.Dataset(q.astype(np.float32), y, group=lengths, max_bins=256)
    vds = ds.bind(qv.astype(np.float32), yv, group=lengths_v)
    evals = {}

    def note(it, info):
        evals[it] = next(v for k, v in info.items() if k.startswith("valid"))

    booster = dryad.train(PARAMS, ds, valid_sets=[vds], backend="tpu", callbacks=[note])
    assert booster.params.max_depth == CAP
    job = {"trees": trees_of(booster), "init_score": float(booster.init_score[0]), "evals": evals}
    numbers = {**ref.follow(job, 3), **ref.follow_window(job, 2)}
    ok, compared = judge(numbers, TOLERANCE)
    assert ok, compared


STAND_INS = ("reference_float32", "control_bfloat16", "no_delta_ndcg", "all_pairs",
             "shifted_boundaries", "ndcg_one_query")


@pytest.mark.parametrize("kind", STAND_INS)
def test_the_control_and_each_fault_of_the_mechanism_are_not_correct(data, ref, kind):
    """The reference put in the job's place, through the harness's ``judge``
    with the cell's own limits: sound in float32, not correct with gradients
    rounded to bfloat16, nor with any of the four faults of a ranking job."""
    limits = mf.Cell(mf.load(), CELL).limits
    if kind in ("reference_float32", "control_bfloat16"):
        job = ref.grow(3, bf16=kind == "control_bfloat16")
    else:
        job = reference(data, kind).grow(3)
    numbers = {"job_died": 0.0, "checkpoint_iters_gap": 0.0,
               **ref.follow(job, 3), **ref.follow_window(job, 2)}
    ok, compared = judge(numbers, limits)
    over = {k for k, c in compared.items() if not c["value"] <= c["limit"]}
    if kind == "reference_float32":
        assert ok, compared
    elif kind == "ndcg_one_query":
        assert over == {"valid_metric_gap"}, compared
    else:
        assert not ok and over & {"leaf_value_gap", "split_flip_share"}, compared


def test_the_floor_band_holds_the_rounding_of_the_chips_lambda_pass():
    """A child whose hessian sum lies 1.2e-5 under ``min_child_weight`` (the
    job's own split of a depth-9 node on a TPU v5e, where the job's λ-pass on
    the same scores summed 99.99831) counts as either in the ranking reference
    and as refused where both sides take the same hessians; a child past the
    band is refused by both, and the reference's own best keeps the floor."""
    import types

    from benchmark.reference.gbdt_bestfirst import FLOOR_ROUNDING, BestFirst
    from benchmark.reference.gbdt_rank import FLOOR_ROUNDING_RANK, RankBestFirst

    params = {"lambda_l2": 1.0, "min_data_in_leaf": 1, "min_child_weight": 100.0}

    def gains(cls, right_hessian):
        hist = np.zeros((3, 1, 1, 256))
        hist[:, 0, 0, 0] = (116.76, 144.898, 2822.0)
        hist[:, 0, 0, 1] = (-8.32, right_hessian, 1446.0)
        stub = types.SimpleNamespace(p=params, floor_rounding=cls.floor_rounding)
        sure, maybe = cls.gains_either(stub, hist)[:2]
        return float(sure[0, 0, 0]), float(maybe[0, 0, 0])

    assert BestFirst.floor_rounding == FLOOR_ROUNDING
    assert RankBestFirst.floor_rounding == FLOOR_ROUNDING_RANK > 4 * 2.2e-5
    seen = 99.9987957989797
    assert gains(RankBestFirst, seen)[0] == -np.inf and gains(RankBestFirst, seen)[1] > 0
    assert gains(BestFirst, seen) == (-np.inf, -np.inf)
    past = 100.0 * (1 - 2 * FLOOR_ROUNDING_RANK)
    assert gains(RankBestFirst, past) == (-np.inf, -np.inf)
    assert gains(RankBestFirst, 101.0)[0] > 0


def test_the_hessian_look_reads_the_band_and_names_an_operation_planted_low(ref, monkeypatch):
    """``readings_rank.py --hessian-look`` on the CPU: float32 keeps the
    program's child hessian sums within 1e-6 of the reference's and the
    bfloat16 control's lie over 1e-5 from them; then with ``exp`` planted
    2**-8 low under the program's λ-pass and the look alike, the program's
    shortfall shows, the look reads ``exp`` low and nothing else, and the
    hessians rebuilt with the device's ``exp`` alone give the shortfall."""
    import jax

    from benchmark.tools.readings_rank import hessian_look

    job = ref.grow(2)
    looks = hessian_look(ref, job, 2, 7, 40)
    band = [r for r in looks if r["kind"] == "band"]
    assert len(band) == 2 and all(r["children"] > 0 for r in band)
    assert max(r["program"]["largest"] for r in band) < 1e-6
    assert min(r["control"]["largest"] for r in band) > 1e-5
    for row in (r for r in looks if r["kind"] == "ops"):
        assert row["pairs"] > 0
        assert max(abs(v) for v in row["hessian_sum_gap"].values()) < 1e-6, row

    real = jax.numpy.exp
    monkeypatch.setattr(jax.numpy, "exp", lambda x: real(x) * (1 - 2.0 ** -8))
    jax.clear_caches()
    try:
        planted = hessian_look(ref, job, 2, 7, 40)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    (row,) = [r for r in planted if r["kind"] == "ops" and r["iteration"] == 1]
    ops, gap = row["device_against_float64"], row["hessian_sum_gap"]
    assert ops["exp"]["mean"] == pytest.approx(-(2.0 ** -8), rel=1e-3)
    assert max(abs(ops[k]["mean"]) for k in ("power", "log2", "discount", "inverse")) < 1e-6
    assert abs(gap["program"]) > 1e-5
    assert gap["exp"] == pytest.approx(gap["program"], rel=0.05)
    assert max(abs(gap[k]) for k in ("power", "log2", "discount", "inverse")) < 1e-6


# ---- the cell's files and the command -----------------------------------------


def check_manifest(manifest, root=mf.ROOT):
    """This cell's part of the manifest, for the accepted one and for one
    that later entries have grown (``test_bench_append.py``)."""
    assert mf.problems(manifest, root) == []
    cell = mf.Cell(manifest, CELL, root)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "mslr2m_leaf255", "job_rank")
    assert CELL in [w["name"] for w in manifest["workloads"]]
    runner = cell.runner()
    assert runner.__name__.endswith("train_job_rank") and "order_gain_gap" in runner.NUMBERS
    assert set(cell.limits) == {"job_died", "checkpoint_iters_gap", *runner.NUMBERS}
    config = cell.config
    assert config["depth_cap"] == 12 and config["params"]["max_depth"] == -1
    assert (config["data"]["train_rows"], config["data"]["valid_rows"],
            config["data"]["features"]) == (2_270_296, 753_611, 136)
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell.config_name]
    assert entry["reduced"] == ["trees_grown"] and set(config["reduced_why"]) == {"trees_grown"}
    # the cell reports what reads its mechanism (the λ-pass in dryad.grad, NDCG in
    # dryad.eval), the histogram kernels' share and the shares of the whole step,
    # and neither of the wired layout's two: no layout runs
    ours = {m["name"] for m in cell.per_layer}
    assert {"grad_score_device_ms", "eval_device_ms", "hist_roofline", "hist_glue_device_ms",
            "split_scan_device_ms", "route_device_ms", "step_mfu", "hbm_held_share",
            "device_idle_share"} <= ours
    assert not ours & {"perm_time_share", "layout_device_ms"}
    assert all(mf.metric_reader(name) is not None for name in ours)
    return cell


def test_the_manifest_resolves_the_cell_and_its_files():
    cell = check_manifest(mf.load())
    runner, config = cell.runner(), cell.config
    # the program's own policy gives the cap the file states, at the cell's size
    # and at the rehearsal's
    for rehearsal in (False, True):
        params, cap = runner.job_params(config, rehearsal)
        sizes = runner.data_sizes(config, rehearsal)
        runner.check_policy(params, cap, sizes["features"], sizes["train_rows"])


def test_the_runner_refuses_a_program_whose_policy_gives_another_cap(monkeypatch):
    """A program that would send the shape to the sequential grower (as before
    PR 32) is refused at once, with exit code 2 and no result line."""
    import dryad_tpu.config as config_mod
    from benchmark.runners import train_job_rank as runner

    cell = mf.Cell(mf.load(), CELL)
    params, cap = runner.job_params(cell.config, rehearsal=False)
    monkeypatch.setattr(config_mod, "effective_depth_params", lambda p, *a, **k: p)
    with pytest.raises(runner.NotThisConfiguration, match="max_depth -1"):
        runner.check_policy(params, cap, 136, 2_270_296)
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run as cli

    assert cli.main(["--workload", CELL, "--seed", "1", "--rehearse-cpu"]) == 2


def test_the_runner_takes_the_rank_gauges_where_the_program_keeps_them():
    from benchmark.runners import train_job_rank as runner
    from dryad_tpu.obs.registry import default_registry

    assert set(runner.rank_gauges()) <= {"dryad_rank_queries", "dryad_rank_plan_width",
                                         "dryad_rank_pair_cells.padded",
                                         "dryad_rank_pair_cells.own", "dryad_rank_pair_cells.kept"}
    reg = default_registry()
    was = reg.enabled
    reg.enabled = True
    try:
        reg.gauge("dryad_rank_queries", "q").set(3)
        reg.gauge("dryad_rank_pair_cells", "c").labels(kind="kept").set(17)
        after = runner.rank_gauges()
    finally:
        reg.enabled = was
    assert after["dryad_rank_queries"] == 3.0 and after["dryad_rank_pair_cells.kept"] == 17.0


def test_rehearsal_of_the_cell_is_correct_and_prints_no_metric():
    done = run_cli(ROOT, "--workload", CELL, "--seed", "2147483999", "--seconds", "1",
                   "--trace", "0", "--rehearse-cpu", timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] >= 5
    assert set(line["compared"]) == set(mf.Cell(mf.load(), CELL).limits)
    facts = json.loads(next(ln for ln in done.stderr.splitlines()
                            if ln.startswith("facts: "))[len("facts: "):])
    assert facts["level_passes"] == 5 and facts["leafwise"]["depth_cap"] == 8
    rank = facts["rank"]
    assert rank["dryad_rank_plan_width"] == 160 and rank["dryad_rank_queries"] > 100
    assert (rank["dryad_rank_pair_cells.kept"] <= rank["dryad_rank_pair_cells.own"]
            <= rank["dryad_rank_pair_cells.padded"])


# ---- the longer versions ----------------------------------------------------


@pytest.fixture(scope="module")
def cell():
    return mf.Cell(mf.load(), CELL)


def drive(cell, trace=0):
    from benchmark.runners import train_job_rank

    args = argparse.Namespace(seed=11, seconds=0.5, trace=trace, rehearse_cpu=True)
    return train_job_rank.run(cell, args, time.perf_counter())


def with_entry(monkeypatch, edit):
    """Plant a fault underneath the real path: ``edit(params, ds, kw)`` gives
    what the trainer is really handed."""
    real = train_job.train_entry

    def entry(params, ds, **kw):
        params, ds, kw = edit(dict(params), ds, kw)
        return real(params, ds, **kw)

    monkeypatch.setattr(train_job, "train_entry", entry)


def regroup(ds, lengths, rows=None):
    import dryad_tpu as dryad

    rows = ds.num_rows if rows is None else rows
    return dryad.Dataset.from_binned(ds.X_binned[:rows], ds.mapper, ds.y[:rows], group=lengths)


def shifted(params, ds, kw):
    lengths = ds.group.copy()
    lengths[0] += 1
    lengths[-1] -= 1
    return params, regroup(ds, lengths[lengths > 0]), kw


def one_query(params, ds, kw):
    vds = kw["valid_sets"][0]
    return params, ds, {**kw, "valid_sets": [regroup(vds, np.array([vds.num_rows]))]}


def half_batch(params, ds, kw):
    keep = ds.group[: ds.group.size // 2]
    return params, regroup(ds, keep, int(keep.sum())), kw


def eval_on_half(params, ds, kw):
    vds = kw["valid_sets"][0]
    keep = vds.group[: vds.group.size // 2]
    return params, ds, {**kw, "valid_sets": [regroup(vds, keep, int(keep.sum()))]}


def by_level(params, ds, kw):
    leaves = int(params["num_leaves"])
    return {**params, "growth": "depthwise", "max_depth": max(leaves - 1, 1).bit_length()}, ds, kw


def ranknet(monkeypatch):
    """|dNDCG| left out where the gradients are made: the program's λ-pass is
    replaced by RankNet's over the same pairs."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.engine import lambdarank

    def no_delta(obj, score, y, weight, query_offsets, use_device=True, plan=None):
        shape = jax.ShapeDtypeStruct(score.shape, jnp.float32)
        return jax.pure_callback(
            lambda s, r, offsets: lambda_grad_hess(s, r, np.diff(offsets), obj.sigma,
                                                   obj.truncation, delta_ndcg=False),
            (shape, shape), score, y, query_offsets)

    jax.clear_caches()          # the trainer's jit is module-level: trace it anew
    monkeypatch.setattr(lambdarank, "grad_hess_ranking", no_delta)


def faults():
    from test_bench_correct import answer_altered, break_checkpoints, state_unchanged

    return {
        "state_unchanged": lambda mp: break_checkpoints(mp, state_unchanged),
        "answer_altered": lambda mp: break_checkpoints(mp, answer_altered),
        "half_batch": lambda mp: with_entry(mp, half_batch),
        "eval_on_half": lambda mp: with_entry(mp, eval_on_half),
        "grown_level_by_level": lambda mp: with_entry(mp, by_level),
        "no_delta_ndcg": ranknet,
        "all_pairs": lambda mp: with_entry(
            mp, lambda p, ds, kw: ({**p, "lambdarank_truncation": 10 ** 6}, ds, kw)),
        "shifted_boundaries": lambda mp: with_entry(mp, shifted),
        "ndcg_one_query": lambda mp: with_entry(mp, one_query),
    }


@pytest.mark.slow
def test_a_sound_rehearsal_is_correct_and_a_traced_one_runs_the_readers(cell):
    out = drive(cell, trace=1)
    assert out["correct"] is True, out["compared"]
    assert out["metrics"] == {} and "data_prep_s" in out["facts"]["layer_metrics_read"]
    assert "split_scan_device_ms" not in out["facts"]["layer_metrics_read"]


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered", "half_batch", "eval_on_half",
                                   "grown_level_by_level", "no_delta_ndcg", "all_pairs",
                                   "shifted_boundaries", "ndcg_one_query"])
def test_a_fault_underneath_the_real_path_is_not_correct(cell, monkeypatch, fault):
    import jax

    faults()[fault](monkeypatch)
    try:
        out = drive(cell)
    finally:
        jax.clear_caches()      # no later test may meet a program traced under the fault
    assert out["correct"] is False, (fault, out["compared"])
    over = [k for k, c in out["compared"].items()
            if c["value"] is None or not c["value"] <= c["limit"]]
    assert over and "job_died" not in over, out["compared"]     # a number caught it, not a crash


@pytest.mark.slow
def test_the_readings_tool_finds_the_control_and_every_fault_not_correct(tmp_path):
    out = tmp_path / "rows.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                                        "readings_rank.py"),
                           "--workload", CELL, "--seeds", "0", "--grown", "1", "--rehearse-cpu",
                           "--out", str(out)], cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=1800)
    assert done.returncode == 0, done.stderr[-3000:]
    rows = {r["kind"]: r for r in json.loads(out.read_text())}
    assert rows.pop("reference_float32")["correct"] is True
    assert set(rows) == {"control_bfloat16", "fault_state_unchanged", "fault_answer_altered",
                         "fault_grown_level_by_level", "fault_eval_on_half", "fault_half_batch",
                         "fault_no_delta_ndcg", "fault_all_pairs", "fault_shifted_boundaries",
                         "fault_ndcg_one_query"}
    assert not any(r["correct"] for r in rows.values()), rows
