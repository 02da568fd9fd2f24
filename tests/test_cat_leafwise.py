"""Categorical columns on the batched leaf-wise path: a job with six
categorical columns (one of them past 254 values, so its rarest codes pool
into the overflow bin) grown tree for tree against the plain best-first
reference with subset splits (``benchmark/reference/gbdt_bestfirst_cat.py``),
the split-node counter and the sketch's gauge, the ``dryad.cat`` scope, the
subset scan's one sort and no gather, route's membership read off the
widened node record with no gather, and the numeric programs' digests,
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


def _categorical_program():
    """The categorical iteration's jaxpr at the audit's small shape (N = 2048
    rows x 8 columns, cap 4, so HN = 32 heap slots)."""
    from dryad_tpu.config import make_params
    from dryad_tpu.engine.train import audit_iteration_args, audit_iteration_fn

    p = make_params(dict(objective="binary", num_trees=1, num_leaves=15, max_depth=4,
                         growth="leafwise", max_bins=32, hist_backend="pallas"))
    fn = audit_iteration_fn(p, 32, True, None, "tpu", 2048)
    return jax.make_jaxpr(fn)(*audit_iteration_args(p, 2048, 8))


def _eqns_within(closed, *scopes):
    """Every equation, nested ones too, whose name stack holds all of ``scopes``."""
    from dryad_tpu.analysis.digests import iter_sub_jaxprs

    eqns = []

    def walk(jaxpr, stack):
        for eqn in jaxpr.eqns:
            here = stack + "/" + str(eqn.source_info.name_stack)
            if all(s in here for s in scopes):
                eqns.append(eqn)
            for _, sub, _ in iter_sub_jaxprs(eqn):
                walk(sub, here)

    walk(closed.jaxpr, "")
    return eqns


def _subset_scan_census(monkeypatch):
    """Trace the categorical program and return (scan call sites, the
    equations inside both ``dryad.split_scan`` and ``dryad.cat``): the subset
    scan's order and membership row, the root's and the level's vmapped one."""
    from dryad_tpu.engine import split

    sites = []
    order_of = split._cat_order
    monkeypatch.setattr(split, "_cat_order", lambda *a: (sites.append(1), order_of(*a))[1])
    eqns = _eqns_within(_categorical_program(), "dryad.split_scan", "dryad.cat")
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


def _route_census(monkeypatch):
    """Trace the categorical program and return (route's membership reads,
    the gathers inside ``dryad.route`` and ``dryad.cat``, the operand shapes
    of route's row-sized gathers outside ``dryad.cat``)."""
    from dryad_tpu.engine import leafwise_fast

    sites = []
    bit_of = leafwise_fast._member_bit
    monkeypatch.setattr(leafwise_fast, "_member_bit",
                        lambda *a: (sites.append(1), bit_of(*a))[1])
    closed = _categorical_program()
    route = [e for e in _eqns_within(closed, "dryad.route") if e.primitive.name == "gather"]
    in_cat = {id(e) for e in _eqns_within(closed, "dryad.route", "dryad.cat")}
    cat = [e for e in route if id(e) in in_cat]
    rows = [tuple(e.invars[0].aval.shape) for e in route
            if id(e) not in in_cat and e.outvars[0].aval.shape[0] == 2048]
    return len(sites), len(cat), rows


def test_route_reads_membership_off_the_widened_record(monkeypatch):
    """The static form of "the membership rides the record": route's one
    row-sized gather reads the (HN, 2 + CAT_WORDS) node table, and the bit is
    read by a masked reduce, so ``dryad.cat`` inside ``dryad.route`` gathers
    nothing."""
    from dryad_tpu.booster import CAT_WORDS

    sites, cat_gathers, rows = _route_census(monkeypatch)
    assert sites >= 1
    assert cat_gathers == 0
    assert rows == [(32, 2 + CAT_WORDS)] * sites


def test_an_element_gather_in_route_is_caught(monkeypatch):
    """Mutation direction: the bit read as an element gather, one word a row
    out of the 2-D gathered records, reads one gather in ``dryad.cat`` a route
    site in the same census."""
    from dryad_tpu.engine import leafwise_fast

    def gathered(rec, bins):
        word = rec[jnp.arange(rec.shape[0]), 2 + (bins >> 5)]
        return ((word >> (bins & 31).astype(jnp.uint32)) & 1).astype(bool)

    monkeypatch.setattr(leafwise_fast, "_member_bit", gathered)
    sites, cat_gathers, _ = _route_census(monkeypatch)
    assert sites >= 1 and cat_gathers == sites


@pytest.mark.parametrize("n_bins", [256, 32])
def test_the_bit_read_equals_the_membership_table(n_bins):
    """Route's read, the widened table gathered at each node and the bit read
    off its words, equals ``nd_catmask[min(node, HN - 1), min(bin, Bc - 1)]``
    for every (node, bin): nodes past the clamp, bins at and past ``Bc - 1``,
    and at 32 bins words that are zero-padded.  The words are the checkpoint's
    (``pack_cat_bitset``'s), bit for bit."""
    from dryad_tpu.booster import CAT_WORDS
    from dryad_tpu.engine import leafwise_fast
    from dryad_tpu.engine.grower import pack_cat_bitset

    HN = 64
    rng = np.random.default_rng(n_bins)
    mask = rng.random((HN, n_bins)) < 0.5
    rec = rng.integers(0, 1 << 32, (HN, 2), dtype=np.uint64).astype(np.uint32)
    table = np.asarray(leafwise_fast._route_table(jnp.asarray(rec), jnp.asarray(mask)))
    assert table.shape == (HN, 2 + CAT_WORDS)
    assert np.array_equal(table[:, :2], rec)
    assert np.array_equal(table[:, 2:], np.asarray(pack_cat_bitset(jnp.asarray(mask), HN)))
    assert not table[:, 2 + -(-n_bins // 32):].any()
    nodes, bins = (a.ravel() for a in np.meshgrid(np.arange(HN + 3), np.arange(300),
                                                  indexing="ij"))
    bc = np.minimum(bins, n_bins - 1)
    rr = jnp.asarray(table)[jnp.asarray(nodes)]
    got = np.asarray(leafwise_fast._member_bit(rr, jnp.asarray(bc, jnp.int32)))
    assert np.array_equal(got, mask[np.minimum(nodes, HN - 1), bc])


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
