"""The categorical cell (``expo11m_leaf255.job_cat``): its data family, the
reference's category rule, subset scan and float32 band of its flips (a
float32-scan witness under it, the bfloat16 control past it), the reader
``cat_device_ms`` and the runner's counters, the manifest's new entries, the
whole cell at rehearsal size through the real runner, and three faults
planted underneath the real path (categorical columns scanned in bin order,
one category moved where rows are routed, half of the batch left out).  All
on the CPU at a few thousand rows and 15 leaves."""

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

from benchmark.datagen import expo  # noqa: E402
from benchmark.harness import manifest as mf  # noqa: E402
from benchmark.reference import gbdt  # noqa: E402
from benchmark.reference.gbdt_bestfirst_cat import (BestFirstCat, Categories,  # noqa: E402
                                                    Rows)

CELL = "expo11m_leaf255.job_cat"
SEED = 3800000011          # a rehearsal seed whose first trees hold no tie of leaf values


@pytest.fixture(scope="module")
def cell():
    return mf.Cell(mf.load(), CELL)


# ---- data and the reference -------------------------------------------------------


def test_the_family_draws_the_sources_columns_at_their_cardinalities():
    q, y = expo.make(SEED, 60_000)
    again, _ = expo.make(SEED, 60_000, threads=3)
    assert q.dtype == np.uint16 and q.shape == (60_000, 8) and np.array_equal(q, again)
    for f, k in expo.CARDINALITY.items():
        assert q[:, f].max() < k and np.unique(q[:, f]).size > 0.8 * k
    assert q[:, [3, 7]].max() <= 254
    assert 0.15 < y.mean() < 0.23
    # hub-skewed airports: the 59 rarest codes of 313 hold about 3.3 % of the rows
    counts = np.sort(np.bincount(q[:, 5], minlength=313))[::-1]
    assert 0.025 < counts[254:].sum() / q.shape[0] < 0.045
    # a code's size says nothing of its number
    assert np.argmax(np.bincount(q[:, 5])) != 0 or np.argmax(np.bincount(q[:, 6])) != 0


def test_the_references_rule_is_the_documented_one():
    raw = np.array([[5, 0], [5, 1], [2, 2], [2, 3], [7, 4], [9, 5], [9, 6], [9, 7]], np.uint16)
    cats = Categories(raw, [0], codes=10, max_bins=4)      # two categories kept
    # counts: 9 three times, 2 and 5 twice each (2 first by value), 7 once
    assert cats.bin_of[0][[9, 2, 5, 7, 0]].tolist() == [1, 2, 3, 3, 3]
    assert cats.bin_of[0][10] == 0 and cats.overflow[0] == 3
    assert cats.pooled[0][:2].tolist() == [5, 7]
    assert cats.bins(raw)[:, 1].tolist() == raw[:, 1].tolist()      # numeric as is
    left = np.zeros(11, bool)
    left[[9, 5]] = True
    assert cats.to_bins(0, left)[:4].tolist() == [False, True, False, True]
    assert cats.to_codes(0, cats.to_bins(0, left))[[9, 2, 5, 7]].tolist() == [True, False, True, True]


def test_the_subset_scan_finds_the_best_prefix_of_the_ratio_order():
    """The reference's first split against a brute force over every prefix
    of every categorical column's order, written out here."""
    q, y = expo.make(SEED, 5000)
    cats = Categories(q, expo.CATEGORICAL, 313)
    qb = cats.bins(q)
    params = dict(objective="binary", num_leaves=3, lambda_l2=1.0, min_child_weight=20,
                  min_data_in_leaf=1, min_split_gain=0.0, learning_rate=0.1)
    ref = BestFirstCat(params, Rows(qb, y), None, 4, cats)
    s0 = gbdt.init_score(y, "binary")
    g, h = (np.asarray(ref.train.host(a), np.float64)
            for a in gbdt._grad_hess(ref.train.start(s0), ref.train.y, objective="binary"))
    G, H = g.sum(), h.sum()
    top = (-np.inf, None)
    for f in range(8):
        gb, hb, cb = (np.bincount(qb[:, f], w, 256) for w in (g, h, None))
        order = (np.argsort(np.where(cb > 0, gb / (hb + 10.0), np.inf), kind="stable")
                 if f in expo.CATEGORICAL else np.arange(256))
        gl, hl, cl = (np.cumsum(a[order]) for a in (gb, hb, cb))
        ok = (cl >= 1) & (cl <= len(y) - 1) & (hl >= 20) & (H - hl >= 20)
        gain = np.where(ok, 0.5 * (gl ** 2 / (hl + 1) + (G - gl) ** 2 / (H - hl + 1)
                                   - G ** 2 / (H + 1)), -np.inf)
        t = int(np.argmax(gain))
        if gain[t] > top[0]:
            top = (float(gain[t]), f, set(order[: t + 1].tolist()))
    tree, _ = ref.grow_tree(*gbdt._grad_hess(ref.train.start(s0), ref.train.y,
                                             objective="binary"))
    f = int(tree.feature[0])
    assert f == top[1] and f in expo.CATEGORICAL
    assert set(np.flatnonzero(cats.to_bins(f, tree.cat_left[0])).tolist()) == top[2]


def test_the_flip_band_passes_the_float32_witness_and_fails_the_control(cell):
    """At rehearsal size: the reference grown with its scan's order and gains
    in float32 loses no split's gain past 1e-9, nor past the band; the
    bfloat16 control loses one past the band, and the band's count is the
    strict count's part above ``FLIP_ROUNDING``."""
    from benchmark.reference.gbdt_bestfirst_cat import FLIP_ROUNDING
    from benchmark.runners import train_job_cat as runner

    seed = 3800104729
    params, cap = runner.job_params(cell.config, rehearsal=True)
    q, y, qv, yv = runner.make_data(cell.config, seed, True)
    cats = Categories(q, expo.CATEGORICAL, runner.codes_of(cell.config))
    train, valid = Rows(cats.bins(q), y), Rows(cats.bins(qv), yv)
    ref = BestFirstCat(params, train, valid, cap, cats)
    witness = ref.follow(BestFirstCat(params, train, valid, cap, cats,
                                      float32_scan=True).grow(3), 3)
    assert witness["split_flip_share"] == witness["strict_flip_share"] == 0.0
    assert witness["split_nodes"] == 42 and witness["flip_rel"] == []
    control = ref.follow(ref.grow(3, bf16=True), 3)
    assert control["split_flip_share"] > cell.limits["split_flip_share"]
    rel = np.asarray(control["flip_rel"])
    assert control["split_flip_share"] == (rel > FLIP_ROUNDING).sum() / 42
    assert control["strict_flip_share"] == rel.size / 42 and list(rel) == sorted(rel)[::-1]


# ---- the reader and the runner's counters ----------------------------------------


def test_cat_device_ms_reads_the_scope_and_zero_where_it_is_absent(monkeypatch):
    from benchmark.harness import scopes
    from benchmark.layer_metrics import cat_device_ms

    facts = {"window_iters": 4}
    filled = {"scoped": True, "scope_s": {"dryad.cat": 0.5, "dryad.route": 2.0}}
    monkeypatch.setattr(scopes, "read", lambda: filled)
    assert cat_device_ms.read(facts) == pytest.approx(125.0)
    monkeypatch.setattr(scopes, "read", lambda: {"scoped": True, "scope_s": {"dryad.route": 2.0}})
    assert cat_device_ms.read(facts) == 0.0               # a program with no such scope
    monkeypatch.setattr(scopes, "read", lambda: {})
    assert cat_device_ms.read(facts) is None              # no trace at all


def test_the_runner_reads_the_counter_and_the_gauge_where_the_program_keeps_them():
    from benchmark.runners import train_job_cat as runner
    from dryad_tpu.obs import Registry, set_default_registry

    reg = Registry()
    old = set_default_registry(reg)
    try:
        assert runner.cat_counters() == {} and runner.cat_levels() == {}     # an empty registry
        nodes = reg.counter(runner.SPLIT_NODES, "x")
        nodes.labels(kind="categorical").inc(30)
        nodes.labels(kind="numeric").inc(10)
        levels = reg.gauge(runner.LEVELS, "x")
        levels.labels(feature="5", kind="kept").set(254)
        levels.labels(feature="5", kind="pooled").set(59)
        assert runner.cat_counters() == {"categorical": 30.0, "numeric": 10.0}
        assert runner.cat_levels() == {"5": {"kept": 254.0, "pooled": 59.0}}
    finally:
        set_default_registry(old)


# ---- the manifest -------------------------------------------------------------------


def check_cell_resolves(manifest, root=mf.ROOT):
    assert mf.problems(manifest, root) == []
    cell = mf.Cell(manifest, CELL, root)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "expo11m_leaf255", "job_cat")
    runner = cell.runner()
    assert runner.__name__.endswith("train_job_cat") and "order_gain_gap" in runner.NUMBERS
    assert set(cell.limits) == {"job_died", "checkpoint_iters_gap", *runner.NUMBERS}
    config = cell.config
    assert (config["data"]["train_rows"], config["data"]["valid_rows"],
            config["data"]["features"]) == (11_000_000, 100_000, 8)
    assert config["data"]["categorical_features"] == config["params"]["categorical_features"] \
        == list(expo.CATEGORICAL)
    assert sorted(config["data"]["cardinality"].values()) == sorted(expo.CARDINALITY.values())
    assert sum(expo.CARDINALITY.values()) + 2 == 700       # the source's one-hot width
    entry = next(c for c in manifest["configs"] if c["name"] == "expo11m_leaf255")
    assert entry["reduced"] == ["trees_grown"] == list(config["reduced_why"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    with open(mf.traffic_path("job_bestfirst", root)) as f:
        bestfirst = json.load(f)
    assert cell.traffic == {**bestfirst, "runner": "train_job_cat", "what": cell.traffic["what"]}
    return cell


def test_the_manifest_resolves_the_cell_and_its_files():
    cell = check_cell_resolves(mf.load())
    runner, config = cell.runner(), cell.config
    params, cap = runner.job_params(config, rehearsal=False)
    assert (params, cap) == (config["params"], 12) and params["max_depth"] == -1
    # the program's own policy gives the cap the file states, at the cell's size
    # and at the rehearsal's
    from dryad_tpu.config import effective_depth_params, make_params
    assert effective_depth_params(make_params(params), 8, 256, 11_000_000).max_depth == cap
    small, small_cap = runner.job_params(config, rehearsal=True)
    assert effective_depth_params(make_params(small), 8, 256, 6000).max_depth == small_cap


def check_accepted_metrics(manifest, root=mf.ROOT):
    from test_bench_scopes import accepted_of

    cell = mf.Cell(manifest, CELL, root)
    ours = accepted_of(manifest, CELL, root)
    assert ours == accepted_of(manifest, "higgs10m_d8.job", root) | {"cat_device_ms"}
    assert {m["name"] for m in cell.end_to_end} == {"iters_per_s", "setup_s"}
    listed = set()
    for entry in manifest["per_layer"]:
        lists = entry.get("workloads")
        if lists is None or entry["name"] not in ours:
            continue
        assert CELL in lists and entry["moves"] == "iters_per_s"
        listed.add(entry["name"])
    assert len(listed) >= 17 and "cat_device_ms" in listed
    assert CELL in [w["name"] for w in manifest["workloads"]]
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == "cat_device_ms"]
    assert entry["workloads"] == [CELL] and entry["source"] == "device_trace"
    assert entry["layer"] == "categorical split scan and routing"
    assert os.path.exists(mf.metric_path("cat_device_ms", root))


def check_manifest(manifest, root=mf.ROOT):
    """This cell's part of the manifest, for the accepted one and for one
    that later entries have grown (``test_bench_append.py``)."""
    check_cell_resolves(manifest, root)
    check_accepted_metrics(manifest, root)


def test_the_cell_reports_every_accepted_metric_of_a_wired_cell():
    """The cell reports what the depth-wise Higgs cell reports, the wired
    layout's two among them, and ``cat_device_ms``, which lists this cell
    alone: its name is in the seventeen lists that name it, wherever it
    stands there."""
    check_accepted_metrics(mf.load())


# ---- the whole cell, and faults underneath it ---------------------------------------


def drive(cell, seed=SEED):
    from benchmark.runners import train_job_cat

    args = argparse.Namespace(seed=seed, seconds=0.5, trace=0, rehearse_cpu=True)
    return train_job_cat.run(cell, args, time.perf_counter())


def over_limits(out):
    return sorted(k for k, c in out["compared"].items()
                  if c["value"] is None or not c["value"] <= c["limit"])


def test_rehearsal_of_the_cell_is_correct_and_prints_no_metric():
    done = run_cli(ROOT, "--workload", CELL, "--seed", str(SEED), "--seconds", "1",
                   "--trace", "0", "--rehearse-cpu", timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert set(line["compared"]) == set(mf.Cell(mf.load(), CELL).limits)
    facts = json.loads(next(ln for ln in done.stderr.splitlines()
                            if ln.startswith("facts: "))[len("facts: "):])
    cat = facts["cat"]
    assert cat["categorical_share"] >= 0.5
    assert sum(cat["split_nodes"].values()) == 14 * facts["window_iters"]
    assert cat["levels"]["5"]["kept"] == 254 and cat["levels"]["5"]["pooled"] > 0
    assert 0 < cat["pooled_rows_share"]["5"] < 0.06 and cat["pooled_rows_share"]["0"] == 0
    assert facts["leafwise"]["depth_cap"] == 8


def _clear():
    import jax

    jax.clear_caches()


@pytest.fixture()
def fresh_programs():
    """A planted fault changes what the grower traces: no program compiled
    before it may serve it, and none compiled with it may outlive it."""
    _clear()
    yield
    _clear()


def scanned_as_numeric(monkeypatch):
    """The subset scan skipped: a categorical column's bins prefix-scanned in
    bin order, the left set the bins up to the threshold."""
    import jax.numpy as jnp

    from dryad_tpu.engine import leafwise_fast

    real = leafwise_fast.find_best_split

    def scan(hist, G, H, C, *, is_cat_feat, **kw):
        res = real(hist, G, H, C, is_cat_feat=jnp.zeros_like(is_cat_feat), **kw)
        f = jnp.maximum(res.feature, 0)
        mask = ((jnp.arange(hist.shape[-1]) <= res.threshold) & is_cat_feat[f]
                & (res.feature >= 0))
        return res._replace(cat_mask=mask)

    monkeypatch.setattr(leafwise_fast, "find_best_split", scan)


def one_category_moved(monkeypatch):
    """Rows routed with bin 1 (the column's most frequent category) on the
    other side of every categorical split, the tree keeping the set chosen."""
    from dryad_tpu.engine import leafwise_fast

    real_scan, real_pack = leafwise_fast.find_best_split, leafwise_fast.pack_cat_bitset

    def flip(mask):
        live = mask.any(axis=-1, keepdims=True)
        return mask.at[..., 1].set(mask[..., 1] ^ live[..., 0])

    def scan(*a, **kw):
        res = real_scan(*a, **kw)
        return res._replace(cat_mask=flip(res.cat_mask))

    monkeypatch.setattr(leafwise_fast, "find_best_split", scan)
    monkeypatch.setattr(leafwise_fast, "pack_cat_bitset", lambda m, M: real_pack(flip(m), M))


def half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    import dryad_tpu as dryad
    from benchmark.runners import train_job

    real = train_job.train_entry

    def entry(params, ds, **kw):
        n = ds.num_rows // 2
        half = dryad.Dataset.from_binned(ds.X_binned[:n], ds.mapper, ds.y[:n])
        return real(params, half, **kw)

    monkeypatch.setattr(train_job, "train_entry", entry)


FAULTS = {"scanned_as_numeric": (scanned_as_numeric, {"split_flip_share", "order_gain_gap"}),
          "one_category_moved": (one_category_moved, {"window_cover_gap"}),
          "half_batch": (half_batch, {"init_score_gap", "window_cover_gap"})}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_underneath_the_real_path_is_not_correct(cell, monkeypatch, fresh_programs,
                                                         fault):
    plant, must = FAULTS[fault]
    plant(monkeypatch)
    out = drive(cell)
    assert out["correct"] is False, out["compared"]
    assert must <= set(over_limits(out)), out["compared"]
