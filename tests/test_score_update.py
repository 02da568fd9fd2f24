"""The score update's one look-up a row (engine/train._row_records).

A grower hands the train step each row's partition key (its leaf slot, or
the heap node the batched leaf-wise expansion routed it to) and a key ->
leaf table; the step composes that table with the leaf values into one
``(keys, 2)`` u32 record table and gathers once a row.  These tests hold that
gather to the formulation it replaces, ``value[key_leaf[row_key]]`` as two
look-ups in sequence, bit for bit: the scores after three trees and the trees
themselves through ``_grow_iteration`` for every grower, K = 3, GOSS with
out-of-bag rows, L1 leaf renewal and a four-device mesh, and the model bytes
and callback values of whole ``dryad.train`` jobs (DART among them, whose
``value_scale`` rides the per-iteration step).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dryad_tpu as dryad
from dryad_tpu.booster import CAT_WORDS
from dryad_tpu.config import make_params
from dryad_tpu.datasets import covertype_like, higgs_like
from dryad_tpu.engine import leafwise_fast
from dryad_tpu.engine import train as engine_train
from dryad_tpu.objectives import renew_alpha

pytestmark = pytest.mark.distributed

ROWS, BINS, TREES = 2000, 32, 3


def _two_lookups(key_leaf, value, row_key):
    """Each row's value and leaf as the step read them before: the leaf from
    the key -> leaf table, then the value from the leaf."""
    leaves = key_leaf[jnp.clip(row_key, 0, key_leaf.shape[0] - 1)]
    return value[leaves], leaves


def _against_two_lookups(monkeypatch, run):
    """``run()`` on the record gather and on the two look-ups in sequence;
    the patched trace is dropped so no later test meets it."""
    jax.clear_caches()
    change = run()
    monkeypatch.setattr(engine_train, "_row_records", _two_lookups)
    jax.clear_caches()
    try:
        return change, run()
    finally:
        jax.clear_caches()


BASE = dict(num_trees=TREES, max_bins=BINS, min_data_in_leaf=5)
# name -> (params, mesh shards); every case holds rows out of the bag
CASES = {
    "levelwise": (dict(objective="binary", growth="depthwise", num_leaves=15,
                       max_depth=4), 0),
    # the batched expansion's heap has 2^(cap+1) nodes: eight leaves under a
    # cap of 6 leave the deep nodes' keys unused, under a cap of 3 they fill
    # the last level and the last heap node, where the key's clip stands
    "leafwise_under_cap": (dict(objective="binary", growth="leafwise",
                                num_leaves=8, max_depth=6), 0),
    "leafwise_at_clip": (dict(objective="binary", growth="leafwise",
                              num_leaves=8, max_depth=3), 0),
    "sequential": (dict(objective="binary", growth="leafwise", num_leaves=8,
                        max_depth=4, hist_subtraction=False), 0),
    "multiclass_k3": (dict(objective="multiclass", num_class=3,
                           growth="depthwise", num_leaves=15, max_depth=4), 0),
    "goss_out_of_bag": (dict(objective="binary", growth="depthwise",
                             num_leaves=15, max_depth=4, boosting="goss",
                             goss_top_rate=0.3, goss_other_rate=0.2), 0),
    "l1_renewal": (dict(objective="l1", growth="leafwise", num_leaves=8,
                        max_depth=4), 0),
    "mesh4_levelwise": (dict(objective="binary", growth="depthwise",
                             num_leaves=15, max_depth=4), 4),
    "mesh4_leafwise": (dict(objective="binary", growth="leafwise",
                            num_leaves=8, max_depth=4), 4),
}


def _table(params, rows=ROWS):
    if params["objective"] == "multiclass":
        X, y = covertype_like(rows + 400, seed=29, num_class=3)
    else:
        X, y = higgs_like(rows + 400, seed=29, num_features=10)
        if params["objective"] == "l1":
            y = X[:, 0] * 2.0 + np.where(y > 0, 1.5, -0.5)
    ds = dryad.Dataset(X[:rows], y[:rows].astype(np.float32), max_bins=BINS)
    return ds, ds.bind(X[rows:], y[rows:].astype(np.float32))


def _mesh(shards):
    if not shards:
        return None
    from dryad_tpu.engine.distributed import make_mesh

    assert len(jax.devices()) >= shards, "conftest provides the devices"
    return make_mesh(jax.devices()[:shards])


def _three_iterations(name):
    """Scores and output tables after three boosting iterations through the
    trainer's own ``_grads_body`` and ``_grow_iteration``, a third of the
    rows out of the bag (routed and scored all the same)."""
    params, shards = CASES[name]
    p = make_params(dict(BASE, **params)).validate()
    ds, _ = _table(params)
    K = p.num_class if p.objective == "multiclass" else 1
    Xb, y = jnp.asarray(ds.X_binned), jnp.asarray(ds.y)
    N, F = Xb.shape
    mesh = _mesh(shards)
    renew = renew_alpha(p, weighted=False)
    bag = jnp.asarray(np.random.default_rng(3).random(N) > 1 / 3)
    out = engine_train._empty_out_device(
        TREES * K, p.max_nodes, CAT_WORDS,
        grow_stats=(p.growth == "leafwise"
                    and leafwise_fast.supports(p, F, BINS, N, shards or 1)))

    @jax.jit
    def iteration(out, score, it):
        g_all, h_all = engine_train._grads_body(
            p, N, K, 0, score, y, None, None, None, None, 0, 0)
        return engine_train._grow_iteration(
            p, BINS, False, mesh, "cpu", False, out, score, Xb, y, g_all,
            h_all, bag, jnp.ones((F,), bool), jnp.zeros((F,), bool), it, K,
            n_rows=N, renew_alpha=renew)

    score = jnp.zeros((N, K), jnp.float32)
    for it in range(TREES):
        out, score = iteration(out, score, jnp.int32(it))
    return np.asarray(score), {k: np.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("name", list(CASES))
def test_scores_after_three_trees_are_the_two_lookups(name, monkeypatch):
    (score, out), (score0, out0) = _against_two_lookups(
        monkeypatch, lambda: _three_iterations(name))
    assert np.abs(score).max() > 0 and (out["feature"] >= 0).any()
    np.testing.assert_array_equal(score.view(np.uint32),
                                  score0.view(np.uint32))
    assert out.keys() == out0.keys()
    for key in out:
        np.testing.assert_array_equal(out[key], out0[key], err_msg=key)


def test_the_clip_case_reaches_the_last_heap_node():
    """``leafwise_at_clip`` is the case its name says: some row's key is the
    heap's last node, and the sequential case runs the sequential grower."""
    params, _ = CASES["leafwise_at_clip"]
    p = make_params(dict(BASE, **params)).validate()
    ds, _ = _table(params)
    Xb = jnp.asarray(ds.X_binned)
    N, F = Xb.shape
    g = jnp.asarray(np.where(ds.y > 0, -0.5, 0.5).astype(np.float32))
    tree = leafwise_fast.grow_tree_leafwise_batched(
        p, BINS, Xb, g, jnp.full((N,), 0.25, jnp.float32),
        jnp.ones((N,), bool), jnp.ones((F,), bool), jnp.zeros((F,), bool),
        platform="cpu")
    heap = tree["key_leaf"].shape[0]
    assert heap == 1 << (p.max_depth + 1)
    assert int(tree["row_key"].max()) == heap - 1
    seq = make_params(dict(BASE, **CASES["sequential"][0])).validate()
    assert not leafwise_fast.supports(seq, F, BINS, N)


JOBS = dict(CASES, dart=(dict(objective="binary", growth="depthwise",
                              num_leaves=15, max_depth=4, boosting="dart",
                              drop_rate=0.5, num_trees=5), 0))


def _job(name):
    params, shards = JOBS[name]
    ds, dv = _table(params)
    seen = []
    # every job but GOSS (which draws its own sample) bags 70 % of the rows
    bagged = {} if params.get("boosting") == "goss" else {"subsample": 0.7}
    booster = dryad.train(
        dict(BASE, **bagged, **params), ds, valid_sets=[dv], backend="tpu",
        mesh=_mesh(shards),
        callback=lambda it, info: seen.append(
            (it, {k: v for k, v in info.items() if k.startswith("valid")})))
    return seen, booster.to_bytes()


@pytest.mark.parametrize("name", list(JOBS))
def test_job_model_and_callback_values_are_the_two_lookups(name,
                                                           monkeypatch):
    (seen, model), (seen0, model0) = _against_two_lookups(
        monkeypatch, lambda: _job(name))
    assert len(seen) == JOBS[name][0].get("num_trees", TREES)
    assert all(info for _, info in seen)
    assert seen == seen0
    assert model == model0
