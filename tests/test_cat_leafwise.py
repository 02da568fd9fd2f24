"""Categorical columns on the batched leaf-wise path: a job with six
categorical columns (one of them past 254 values, so its rarest codes pool
into the overflow bin) grown tree for tree against the plain best-first
reference with subset splits (``benchmark/reference/gbdt_bestfirst_cat.py``),
the split-node counter and the sketch's gauge, the ``dryad.cat`` scope, the
subset scan's one sort and no gather, and the numeric programs' digests,
which the scope must leave as committed."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dryad_tpu as dryad
from dryad_tpu.obs import Registry, set_default_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.datagen import expo  # noqa: E402
from benchmark.harness.result import judge  # noqa: E402
from benchmark.reference.gbdt_bestfirst_cat import BestFirstCat, Categories, Rows  # noqa: E402
from benchmark.runners.train_job_cat import trees_of_cat  # noqa: E402

COLUMNS = list(expo.CATEGORICAL)
CODES = max(expo.CARDINALITY.values())
PARAMS = dict(objective="binary", metric="auc", growth="leafwise", num_leaves=15, max_depth=-1,
              max_bins=256, learning_rate=0.1, num_trees=4, lambda_l2=1.0, min_child_weight=20,
              min_data_in_leaf=1, min_split_gain=0.0, hist_precision="exact", seed=11,
              categorical_features=COLUMNS)
CAP = 8            # the program's policy for 15 leaves: ceil(log2 15) + 4
# a sound job against the reference: sums of 8000 float32 terms agree to 1e-6;
# a flipped split or a lost step reads 0.07 and more
TOLERANCE = {"init_score_gap": 1e-6, "split_flip_share": 0.0, "order_gain_gap": 1e-9,
             "leaf_value_gap": 1e-5, "valid_metric_gap": 1e-6, "window_cover_gap": 0.0,
             "window_leaf_value_gap": 1e-5, "window_root_gain_gap": 1e-9}


@pytest.fixture(scope="module")
def job():
    q, y = expo.make(5, 8000, stream=0)
    qv, yv = expo.make(5, 1500, stream=1)
    reg = Registry()
    old = set_default_registry(reg)
    try:
        ds = dryad.Dataset(q.astype(np.float32), y, max_bins=256, categorical_features=COLUMNS)
        vds = ds.bind(qv.astype(np.float32), yv)
        evals = {}

        def note(it, info):
            evals[it] = next(v for k, v in info.items() if k.startswith("valid"))

        booster = dryad.train(PARAMS, ds, valid_sets=[vds], backend="tpu", callbacks=[note])
        snap = reg.snapshot()
    finally:
        set_default_registry(old)
    return dict(q=q, y=y, qv=qv, yv=yv, ds=ds, booster=booster, evals=evals, snap=snap)


def test_the_data_pools_an_airport_column_past_254_codes(job):
    seen = np.unique(job["q"][:, 5]).size
    assert seen > 254
    fb = job["ds"].mapper.features[5]
    assert fb.is_categorical and fb.n_bins == 256
    # the reference's own rule bins every categorical column as the sketch does
    cats = Categories(job["q"], COLUMNS, CODES)
    assert np.array_equal(cats.bins(job["q"])[:, COLUMNS], job["ds"].X_binned[:, COLUMNS])
    assert cats.pooled_share(job["q"])[5] > 0


def test_the_job_agrees_with_the_reference_tree_for_tree(job):
    b = job["booster"]
    assert b.params.max_depth == CAP and b.is_cat.any()
    cats = Categories(job["q"], COLUMNS, CODES)
    ref = BestFirstCat(PARAMS, Rows(cats.bins(job["q"]), job["y"]),
                       Rows(cats.bins(job["qv"]), job["yv"]), CAP, cats)
    run = {"trees": trees_of_cat(b, CODES), "init_score": float(b.init_score[0]),
           "evals": job["evals"]}
    numbers = {**ref.follow(run, 4), **ref.follow_window(run, 4)}
    ok, compared = judge(numbers, TOLERANCE)
    assert ok, compared
    # at this size the job loses no split's gain past 1e-9 either: the float32
    # band of split_flip_share widens nothing here
    assert numbers["strict_flip_share"] == 0.0 and numbers["split_nodes"] == 14 * 4
    assert [row["leaves"] for row in numbers["per_tree"]] == [15] * 4
    # the job's model and the raw-space trees send the valid rows to the same leaves
    scores = ref.valid.start(float(b.init_score[0]))
    for tree in run["trees"]:
        scores = ref.add_tree(ref.valid, scores, tree, tree.value)
    raw = np.asarray(dryad.predict(b, job["qv"].astype(np.float32), raw_score=True))
    np.testing.assert_allclose(ref.valid.host(scores), raw.reshape(-1), rtol=0, atol=1e-5)


def test_the_counter_counts_split_nodes_by_kind_and_the_gauge_the_levels(job):
    snap = job["snap"]
    nodes = {re.search(r'kind="(\w+)"', str(k)).group(1): v
             for k, v in snap["counters"]["dryad_split_nodes_total"].items()}
    b = job["booster"]
    split = b.feature >= 0
    assert nodes == {"categorical": float((split & b.is_cat).sum()),
                     "numeric": float((split & ~b.is_cat).sum())}
    assert nodes["categorical"] + nodes["numeric"] == 14 * 4
    assert nodes["categorical"] / (14 * 4) >= 0.5
    levels = {tuple(re.findall(r'="([^"]*)"', str(k))): v
              for k, v in snap["gauges"]["dryad_cat_levels"].items()}
    seen = np.unique(job["q"][:, 5]).size
    assert levels[("5", "kept")] == 254 and levels[("5", "pooled")] == seen - 254
    assert levels[("0", "kept")] == 12 and levels[("0", "pooled")] == 0
    assert not any(f == "3" for f, _ in levels)         # DepTime is numeric


def test_a_numeric_job_keeps_no_categorical_counter():
    reg = Registry()
    old = set_default_registry(reg)
    try:
        q, y = expo.make(5, 2000, stream=0)
        ds = dryad.Dataset(q.astype(np.float32), y, max_bins=256)
        dryad.train({**PARAMS, "categorical_features": [], "num_trees": 2}, ds, backend="tpu")
        snap = reg.snapshot()
    finally:
        set_default_registry(old)
    assert not snap["counters"].get("dryad_split_nodes_total")
    assert not snap["gauges"].get("dryad_cat_levels")


def _scopes_of(closed) -> set:
    from dryad_tpu.analysis.digests import iter_sub_jaxprs

    found = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            found.update(re.findall(r"dryad\.[a-z_]+", str(eqn.source_info.name_stack)))
            for _, sub, _ in iter_sub_jaxprs(eqn):
                walk(sub)

    walk(closed.jaxpr)
    return found


@pytest.mark.parametrize("has_cat", [False, True])
def test_only_a_categorical_program_carries_the_scope(has_cat):
    from dryad_tpu.config import make_params
    from dryad_tpu.engine.train import audit_iteration_args, audit_iteration_fn

    p = make_params(dict(objective="binary", num_trees=1, num_leaves=15, max_depth=4,
                         growth="leafwise", max_bins=32, hist_backend="pallas"))
    fn = audit_iteration_fn(p, 32, has_cat, None, "tpu", 2048)
    scopes = _scopes_of(jax.make_jaxpr(fn)(*audit_iteration_args(p, 2048, 8)))
    assert ("dryad.cat" in scopes) is has_cat
    assert {"dryad.split_scan", "dryad.route", "dryad.layout"} <= scopes


def _subset_scan_census(monkeypatch):
    """Trace the categorical program and return (scan call sites, the
    equations inside both ``dryad.split_scan`` and ``dryad.cat``): the subset
    scan's order and membership row, the root's and the level's vmapped one."""
    from dryad_tpu.analysis.digests import iter_sub_jaxprs
    from dryad_tpu.config import make_params
    from dryad_tpu.engine import split
    from dryad_tpu.engine.train import audit_iteration_args, audit_iteration_fn

    sites = []
    order_of = split._cat_order
    monkeypatch.setattr(split, "_cat_order", lambda *a: (sites.append(1), order_of(*a))[1])
    p = make_params(dict(objective="binary", num_trees=1, num_leaves=15, max_depth=4,
                         growth="leafwise", max_bins=32, hist_backend="pallas"))
    fn = audit_iteration_fn(p, 32, True, None, "tpu", 2048)
    closed = jax.make_jaxpr(fn)(*audit_iteration_args(p, 2048, 8))
    eqns = []

    def walk(jaxpr, stack):
        for eqn in jaxpr.eqns:
            here = stack + "/" + str(eqn.source_info.name_stack)
            if "dryad.split_scan" in here and "dryad.cat" in here:
                eqns.append(eqn)
            for _, sub, _ in iter_sub_jaxprs(eqn):
                walk(sub, here)

    walk(closed.jaxpr, "")
    return len(sites), eqns


def _sorts_and_gathers(eqns):
    sorts = [(len(e.invars), e.params["num_keys"]) for e in eqns if e.primitive.name == "sort"]
    return sorts, sum(e.primitive.name == "gather" for e in eqns)


def test_the_subset_scan_sorts_once_and_gathers_nothing(monkeypatch):
    """The static form of "the multi-operand sort engaged": at each scan
    call site one stable sort of (key, G, H, C, bin) with one key, and no
    gather in the categorical part of the scan (the ordered planes come out
    of the sort, the winner's order row by a masked reduce)."""
    sites, eqns = _subset_scan_census(monkeypatch)
    assert sites == 2                       # the root's scan and the level's
    assert _sorts_and_gathers(eqns) == ([(5, 1)] * sites, 0)


def test_the_argsort_and_gathers_are_caught(monkeypatch):
    """Mutation direction: the scan's order as it stood before, an
    ``argsort`` and three ``take_along_axis``, reads a two-operand sort and
    three gathers a call site in the same census."""
    from dryad_tpu.engine import split

    def gathered(hg, hh, hc, is_cat_feat):
        iota = jnp.arange(hg.shape[1], dtype=jnp.int32)
        ratio = jnp.where(hc > 0, hg / (hh + split.CAT_SMOOTH), jnp.inf)
        order = jnp.where(is_cat_feat[:, None], jnp.argsort(ratio, axis=1, stable=True),
                          iota[None, :]).astype(jnp.int32)
        return (*(jnp.take_along_axis(x, order, axis=1) for x in (hg, hh, hc)), order)

    monkeypatch.setattr(split, "_cat_order", gathered)
    sites, eqns = _subset_scan_census(monkeypatch)
    assert _sorts_and_gathers(eqns) == ([(2, 1)] * sites, 3 * sites)


@pytest.mark.parametrize("arm", ["leafwise_wired", "train_eval"])
def test_the_numeric_programs_digest_as_committed(arm):
    """The scope and the counter are traced where a model has categorical
    features alone: the numeric programs the goldens pin are unchanged."""
    from dryad_tpu.analysis import jaxpr_audit
    from dryad_tpu.analysis.digests import load_goldens

    fn, args, _, _ = jaxpr_audit.ARMS[arm].build()
    closed = jax.make_jaxpr(fn)(*args)
    assert "dryad.cat" not in _scopes_of(closed)
    assert jaxpr_audit.canonical_digest(closed) == load_goldens()["arms"][arm]["digest"]
