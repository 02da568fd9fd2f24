"""How ``correct`` is decided, shown to fail: the control (the reference in
bfloat16, put in the job's place) and a run of the real path with each fault
a training cell can have planted underneath, all on the CPU at a small size.
The harness's look for a chip is skipped (``rehearse_cpu``); the rest of a
run is driven as the command drives it.

The tests that train or grow trees are marked ``slow``: together they take
two minutes of CPU, and the load was seen to tip a timing test of the
program's fleet over in a whole tier-1 run.  Run them by hand after a change
to the benchmark: ``pytest tests/benchmark -m slow``."""

import argparse
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from bench_fixtures import TINY_CONFIG, TINY_LIMITS, copy_with_third_cell  # noqa: E402

from benchmark.harness import manifest as mf  # noqa: E402
from benchmark.harness.result import judge  # noqa: E402
from benchmark.runners import train_job  # noqa: E402


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = copy_with_third_cell(str(tmp_path_factory.mktemp("bench")))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return mf.Cell(manifest, "tiny_d3.job2", root)


def drive(cell, seed=11):
    args = argparse.Namespace(seed=seed, seconds=0.5, trace=0, rehearse_cpu=True)
    return train_job.run(cell, args, time.perf_counter())


@pytest.mark.slow
def test_sound_run_is_correct(cell):
    out = drive(cell)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] >= 2


def break_checkpoints(monkeypatch, edit):
    """Plant a fault where the job's answer is produced: every booster the
    trainer materialises for a checkpoint passes through ``edit``."""
    from dryad_tpu import checkpoint

    real = checkpoint.Checkpointer.save

    def save(self, booster, iteration):
        edit(booster)
        return real(self, booster, iteration)

    monkeypatch.setattr(checkpoint.Checkpointer, "save", save)


def state_unchanged(booster):
    """The second step returns its state unchanged: no tree was added."""
    if booster.num_total_trees > 1:
        booster.feature, booster.value = booster.feature.copy(), booster.value.copy()
        booster.feature[1, :] = -1
        booster.value[1, :] = 0.0


def last_state_unchanged(booster):
    """The same inside the window: the newest tree of every checkpoint is
    empty, so the last one read back is."""
    booster.feature, booster.value = booster.feature.copy(), booster.value.copy()
    booster.feature[-1, :] = -1
    booster.value[-1, :] = 0.0


def last_rows_dropped(booster):
    """The newest tree saw half of the rows: every node's count is halved."""
    booster.cover = booster.cover.copy()
    booster.cover[-1, :] = np.floor(booster.cover[-1, :] / 2)


def answer_altered(booster):
    """One answer altered where it is produced: the first tree's root
    threshold moves forty bins."""
    booster.threshold = booster.threshold.copy()
    booster.threshold[0, 0] = (int(booster.threshold[0, 0]) + 40) % 250 + 1


def half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    import dryad_tpu as dryad

    real = train_job.train_entry

    def entry(params, ds, **kw):
        n = ds.num_rows // 2
        half = dryad.Dataset.from_binned(ds.X_binned[:n], ds.mapper, ds.y[:n])
        return real(params, half, **kw)

    monkeypatch.setattr(train_job, "train_entry", entry)


def no_evals(monkeypatch):
    """The valid metric the job reports is not the model's: the device eval
    scores half of the valid rows."""
    import dryad_tpu as dryad

    real = train_job.train_entry

    def entry(params, ds, **kw):
        vds = kw["valid_sets"][0]
        n = vds.num_rows // 2
        kw["valid_sets"] = [dryad.Dataset.from_binned(vds.X_binned[:n], vds.mapper, vds.y[:n])]
        return real(params, ds, **kw)

    monkeypatch.setattr(train_job, "train_entry", entry)


FAULTS = {
    "state_unchanged": lambda mp: break_checkpoints(mp, state_unchanged),
    "last_state_unchanged": lambda mp: break_checkpoints(mp, last_state_unchanged),
    "last_rows_dropped": lambda mp: break_checkpoints(mp, last_rows_dropped),
    "answer_altered": lambda mp: break_checkpoints(mp, answer_altered),
    "half_batch": half_batch,
    "eval_on_half": no_evals,
}


@pytest.mark.slow
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_underneath_is_not_correct(cell, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    out = drive(cell)
    assert out["correct"] is False, (fault, out["compared"])
    over = [k for k, v in out["compared"].items()
            if v["value"] is None or not v["value"] <= v["limit"]]
    assert over, out["compared"]


@pytest.mark.slow
def test_job_that_dies_fails_its_window(cell, monkeypatch):
    def entry(*a, **kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(train_job, "train_entry", entry)
    out = drive(cell)
    assert out["correct"] is False and out["failed"] >= 1


@pytest.fixture(scope="module")
def reference():
    from benchmark.reference.gbdt import Reference, Rows
    from benchmark.runners.train_job import make_data

    q, y, qv, yv = make_data(TINY_CONFIG, 23, rehearsal=False)
    return Reference(dict(TINY_CONFIG["params"]), Rows(q, y), Rows(qv, yv))


@pytest.mark.slow
def test_reference_in_the_jobs_place_is_correct(reference):
    job = reference.grow(2)
    numbers = {**reference.follow(job, 2), **reference.follow_window(job, 2)}
    numbers.update(job_died=0.0, checkpoint_iters_gap=0.0)
    ok, compared = judge(numbers, TINY_LIMITS)
    assert ok, compared
    assert numbers["level_gain_gap"] < 1e-9 and numbers["leaf_value_gap"] < 1e-9
    assert numbers["split_flip_share"] == 0.0


@pytest.mark.slow
def test_a_node_that_did_not_take_the_best_split_is_counted(reference):
    job = reference.grow(1)
    tree = job["trees"][0]
    tree.threshold[0] = (int(tree.threshold[0]) + 40) % 250 + 0.5
    numbers = reference.follow(job, 1)
    assert numbers["split_flip_share"] >= 1 / int((tree.feature >= 0).sum())
    assert numbers["level_gain_gap"] > 1e-3


@pytest.mark.slow
def test_control_in_bfloat16_is_not_correct(reference):
    """The nearest precision below float32: gradients rounded to bfloat16
    before they are summed.  It has to fail at least one number."""
    job = reference.grow(2, bf16=True)
    numbers = {**reference.follow(job, 2), **reference.follow_window(job, 2)}
    numbers.update(job_died=0.0, checkpoint_iters_gap=0.0)
    ok, compared = judge(numbers, TINY_LIMITS)
    assert not ok, compared
    assert numbers["leaf_value_gap"] > TINY_LIMITS["leaf_value_gap"]


@pytest.mark.slow
def test_window_stand_ins_read_as_the_tool_reads_them(reference):
    """The last trees' leaves restated by the reference: in float32 it passes,
    from bfloat16 gradients or from half of the rows it does not."""
    from benchmark.reference.gbdt import Reference, Rows
    from benchmark.runners.train_job import make_data

    job = reference.grow(3)
    sound = reference.follow_window(reference.restate(job, 2), 2)
    assert sound["window_cover_gap"] == 0.0 and sound["window_root_gain_gap"] < 1e-9
    assert sound["window_leaf_value_gap"] < 1e-9
    assert [t["iteration"] for t in sound["window_trees"]] == [1, 2]
    control = reference.follow_window(reference.restate(job, 2, bf16=True), 2)
    assert control["window_leaf_value_gap"] > TINY_LIMITS["window_leaf_value_gap"]
    q, y, _, _ = make_data(TINY_CONFIG, 23, rehearsal=False)
    n = q.shape[0] // 2
    half = Reference(dict(TINY_CONFIG["params"]), Rows(q[:n], y[:n]), None)
    fault = reference.follow_window(half.restate(job, 2), 2)
    assert fault["window_cover_gap"] > 0.4
    assert fault["window_leaf_value_gap"] > TINY_LIMITS["window_leaf_value_gap"]


def test_judge_needs_every_number_and_a_limit():
    assert judge({"a": 0.0}, {"a": 0})[0]
    assert not judge({"a": 1e-9}, {"a": 0})[0]
    assert not judge({}, {"a": 1.0})[0]
    assert not judge({"a": float("nan")}, {"a": 1.0})[0]
    assert not judge({"a": 0.0}, {})[0]


def test_limb_sums_are_float32_exact():
    import jax.numpy as jnp

    from benchmark.reference.gbdt import _limbs

    x = jnp.asarray(np.random.default_rng(0).standard_normal(4096).astype(np.float32) * 1e3)
    back = sum(np.asarray(limb, np.float64) for limb in _limbs(x))
    assert np.max(np.abs(back - np.asarray(x, np.float64)) / np.abs(np.asarray(x))) < 2 ** -22
