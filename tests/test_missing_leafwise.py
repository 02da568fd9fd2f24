"""Missing values on the batched leaf-wise path: a job over a table whose
empty cells come in whole stations (``benchmark/datagen/bosch.py``) grown
tree for tree against the plain best-first reference with both planes
(``benchmark/reference/gbdt_bestfirst_missing.py``), ``default_left`` on
every node; the counter of split nodes by the side their empty cells take;
the ``dryad.missing`` scope, innermost in the split scan and the route of a
program with missing values and absent from a numeric one; and the numeric
programs' digests, which the scope and the counter must leave as
committed."""

import os
import re
import sys

import jax
import numpy as np
import pytest

import dryad_tpu as dryad
from dryad_tpu.obs import Registry, set_default_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.datagen import bosch  # noqa: E402
from benchmark.harness.result import judge  # noqa: E402
from benchmark.reference.gbdt_bestfirst_missing import BestFirstMissing, Rows  # noqa: E402
from benchmark.runners.train_job_missing import trees_of_missing  # noqa: E402

PARAMS = dict(objective="binary", metric="auc", growth="leafwise", num_leaves=15, max_depth=-1,
              max_bins=64, learning_rate=0.1, num_trees=4, lambda_l2=1.0, min_child_weight=1.0,
              min_data_in_leaf=1, min_split_gain=0.0, hist_precision="exact", seed=11)
CAP = 8            # the program's policy for 15 leaves: ceil(log2 15) + 4
SEED = 4100000017
# a sound job against the reference: sums of 6000 float32 terms agree to 1e-6;
# a flipped split, a wrong direction or a lost step reads 0.05 and more
TOLERANCE = {"init_score_gap": 1e-6, "split_flip_share": 0.0, "order_gain_gap": 1e-9,
             "leaf_value_gap": 1e-5, "valid_metric_gap": 1e-6, "window_cover_gap": 0.0,
             "window_leaf_value_gap": 1e-5, "window_root_gain_gap": 1e-9}


@pytest.fixture(scope="module")
def job():
    q, y = bosch.make(SEED, 6000, stream=0)
    qv, yv = bosch.make(SEED, 1000, stream=1)
    reg = Registry()
    old = set_default_registry(reg)
    try:
        ds = dryad.Dataset(bosch.as_float(q), y, max_bins=64)
        vds = ds.bind(bosch.as_float(qv), yv)
        evals = {}

        def note(it, info):
            evals[it] = next(v for k, v in info.items() if k.startswith("valid"))

        booster = dryad.train(PARAMS, ds, valid_sets=[vds], backend="tpu", callbacks=[note])
        snap = reg.snapshot()
    finally:
        set_default_registry(old)
    return dict(q=q, y=y, qv=qv, yv=yv, ds=ds, booster=booster, evals=evals, snap=snap)


def test_the_program_finds_the_empty_cells_and_bins_values_as_the_reference(job):
    ds = job["ds"]
    assert ds.has_missing and ds.mapper.total_bins == 64
    # where a column's train rows hold all 63 values, its bin is the value
    full = [f for f in range(968) if np.unique(job["q"][:, f]).size == 64]
    assert len(full) > 400
    assert np.array_equal(ds.X_binned[:, full], job["q"][:, full])


def test_the_job_agrees_with_the_reference_tree_for_tree(job):
    """Every split of the first four trees is the reference's best on the
    plane its ``default_left`` names (a split whose direction is wrong loses
    the other plane's gain and counts as flipped), the leaves' values and
    rows agree, and the job's model sends the valid rows where the reference
    does.  The reference follows the job's trees rather than growing its
    own: in a first tree every row's gradient is one of two values, so two
    different partitions with as many positives on each side tie exactly,
    and float32 sums in different orders break such a tie by rounding."""
    b = job["booster"]
    assert b.params.max_depth == CAP
    ref = BestFirstMissing(PARAMS, Rows(job["q"], job["y"]), Rows(job["qv"], job["yv"]), CAP)
    trees = trees_of_missing(b)
    run = {"trees": trees, "init_score": float(b.init_score[0]), "evals": job["evals"]}
    numbers = {**ref.follow(run, 4), **ref.follow_window(run, 4)}
    ok, compared = judge(numbers, TOLERANCE)
    assert ok, compared
    assert numbers["strict_flip_share"] == 0.0
    split = np.concatenate([t.feature[:-1] >= 0 for t in trees])
    dleft = np.concatenate([t.default_left[:-1] for t in trees])
    assert split.sum() == numbers["split_nodes"] and 0 < (split & ~dleft).sum() < split.sum()
    scores = ref.valid.start(float(b.init_score[0]))
    for tree in trees:
        scores = ref.add_tree(ref.valid, scores, tree, tree.value)
    raw = np.asarray(dryad.predict(b, bosch.as_float(job["qv"]), raw_score=True,
                                   num_iteration=len(trees)))
    np.testing.assert_allclose(ref.valid.host(scores), raw.reshape(-1), rtol=0, atol=1e-5)


def test_the_counter_counts_split_nodes_by_direction(job):
    dirs = {re.search(r'dir="(\w+)"', str(k)).group(1): v
            for k, v in job["snap"]["counters"]["dryad_split_default_total"].items()}
    b = job["booster"]
    split = b.feature >= 0
    assert dirs == {"left": float((split & b.default_left).sum()),
                    "right": float((split & ~b.default_left).sum())}
    assert dirs["left"] > 0 and dirs["right"] > 0


def test_the_per_iteration_path_counts_at_each_eval_fetch(monkeypatch):
    """Where the trainer dispatches each iteration on its own (as it does at
    the Bosch cell's shape on the chip), the grown trees' statistics ride
    each eval fetch, so the counter holds every tree a callback has seen and
    a job stopped early has been counted up to there."""
    monkeypatch.setenv("DRYAD_CHUNK", "0")
    q, y = bosch.make(SEED, 3000, stream=0)
    qv, yv = bosch.make(SEED, 500, stream=1)
    reg = Registry()
    old = set_default_registry(reg)
    seen = []
    try:
        ds = dryad.Dataset(bosch.as_float(q), y, max_bins=64)
        vds = ds.bind(bosch.as_float(qv), yv)

        def note(it, info):
            dirs = reg.snapshot()["counters"].get("dryad_split_default_total") or {}
            seen.append(sum(dirs.values()))

        b = dryad.train({**PARAMS, "num_trees": 3}, ds, valid_sets=[vds], backend="tpu",
                        callbacks=[note])
    finally:
        set_default_registry(old)
    splits = np.cumsum([(np.asarray(b.feature[t]) >= 0).sum() for t in range(3)])
    assert seen == splits.astype(float).tolist() and seen[0] > 0


def test_a_job_with_no_missing_value_keeps_no_direction_counter():
    q, y = bosch.make(5, 2000, stream=0)
    filled = np.where(q == 0, 32, q).astype(np.float32)
    reg = Registry()
    old = set_default_registry(reg)
    try:
        ds = dryad.Dataset(filled, y, max_bins=64)
        assert not ds.has_missing
        dryad.train({**PARAMS, "num_trees": 2}, ds, backend="tpu")
        snap = reg.snapshot()
    finally:
        set_default_registry(old)
    assert not snap["counters"].get("dryad_split_default_total")
    assert snap["counters"].get("dryad_leafwise_selected_splits_total")


def _scope_stacks(closed) -> list:
    from dryad_tpu.analysis.digests import iter_sub_jaxprs

    found = []

    def walk(jaxpr, stack):
        for eqn in jaxpr.eqns:
            here = stack + "/" + str(eqn.source_info.name_stack)
            found.append(here)
            for _, sub, _ in iter_sub_jaxprs(eqn):
                walk(sub, here)

    walk(closed.jaxpr, "")
    return found


@pytest.mark.parametrize("learn_missing", [False, True])
def test_only_a_program_with_missing_values_carries_the_scope(learn_missing):
    """The scope is traced where the table has missing values alone, and
    there it sits innermost under the split scan (the plane, the argmax, the
    left sums) and under the route (the learned direction's look-up)."""
    from dryad_tpu.config import make_params
    from dryad_tpu.engine.train import audit_iteration_args, audit_iteration_fn

    p = make_params(dict(objective="binary", num_trees=1, num_leaves=15, max_depth=4,
                         growth="leafwise", max_bins=64, hist_backend="pallas"))
    fn = audit_iteration_fn(p, 64, False, None, "tpu", 2048, learn_missing=learn_missing)
    stacks = _scope_stacks(jax.make_jaxpr(fn)(*audit_iteration_args(p, 2048, 8)))
    scoped = [s for s in stacks if "dryad.missing" in s]
    assert bool(scoped) is learn_missing
    if learn_missing:
        outer = {re.findall(r"dryad\.[a-z_]+", s)[-2] for s in scoped
                 if len(re.findall(r"dryad\.[a-z_]+", s)) > 1}
        assert {"dryad.split_scan", "dryad.route"} <= outer
        assert all(re.findall(r"dryad\.[a-z_]+", s)[-1] == "dryad.missing" for s in scoped)


@pytest.mark.parametrize("arm", ["leafwise_wired", "train_eval"])
def test_the_numeric_programs_digest_as_committed(arm):
    """The scope and the counter are traced where the table has missing
    values alone: the numeric programs the goldens pin are unchanged."""
    from dryad_tpu.analysis import jaxpr_audit
    from dryad_tpu.analysis.digests import load_goldens

    fn, args, _, _ = jaxpr_audit.ARMS[arm].build()
    closed = jax.make_jaxpr(fn)(*args)
    assert not any("dryad.missing" in s for s in _scope_stacks(closed))
    assert jaxpr_audit.canonical_digest(closed) == load_goldens()["arms"][arm]["digest"]
