"""The training eval's tree walk (engine/train._fresh_tree + tree_leaves).

The trainer scores its valid rows on a tree that has not left the device.
Where the static shapes fit the packed widths it packs that tree's
traversal fields on the device into ``predict``'s (M, 2) node-word table
and walks it as route walks the train rows: one table gather a level and
``select_bins``.  These tests hold that walk to the structure-of-arrays
arm it replaces and to the CPU reference, bitwise, across the widths the
benchmark's cells have, and hold the job's reported metric and model bytes
to what the structure-of-arrays arm gives.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dryad_tpu as dryad
from dryad_tpu.booster import CAT_WORDS
from dryad_tpu.cpu.predict import predict_tree_leaves
from dryad_tpu.datasets import higgs_like
from dryad_tpu.engine import train as engine_train
from dryad_tpu.engine.predict import (pack_node_words,
                                      pack_node_words_device,
                                      packed_shapes_fit, tree_leaves)
from dryad_tpu.obs.registry import default_registry


def _random_trees(rng, T, M, F, B, *, cats=False):
    """``T`` random trees in the trainer's (T, M) output tables: node 0 the
    root, children numbered as they are made, every field random within its
    range (thresholds, missing directions and, with ``cats``, bitsets)."""
    out = {
        "feature": np.full((T, M), -1, np.int32),
        "threshold": np.zeros((T, M), np.int32),
        "left": np.zeros((T, M), np.int32),
        "right": np.zeros((T, M), np.int32),
        "value": rng.standard_normal((T, M)).astype(np.float32),
        "is_cat": np.zeros((T, M), bool),
        "cat_bitset": np.zeros((T, M, CAT_WORDS), np.uint32),
        "gain": np.zeros((T, M), np.float32),
        "default_left": np.ones((T, M), bool),
        "cover": np.zeros((T, M), np.float32),
    }
    depth = 0
    for t in range(T):
        frontier, used, level = [0], 1, 0
        while frontier and used + 2 <= M:
            nxt = []
            for node in frontier:
                if used + 2 > M or rng.random() < 0.15:
                    continue
                out["feature"][t, node] = rng.integers(0, F)
                out["default_left"][t, node] = rng.random() < 0.5
                if cats and rng.random() < 0.4:
                    out["is_cat"][t, node] = True
                    out["cat_bitset"][t, node] = rng.integers(
                        0, 1 << 32, CAT_WORDS, dtype=np.uint64)
                else:
                    out["threshold"][t, node] = rng.integers(0, B)
                out["left"][t, node], out["right"][t, node] = used, used + 1
                nxt += [used, used + 1]
                used += 2
            frontier, level = nxt, level + 1
        depth = max(depth, level)
    return out, depth


def _rows(rng, N, F, B, dtype):
    Xb = rng.integers(0, B, (N, F)).astype(dtype)
    Xb[rng.random((N, F)) < 0.2] = 0          # bin 0: the missing bin
    return Xb


def _walk(out, t, Xb, depth_bound, B, has_cat):
    """(the trainer's walk, the structure-of-arrays walk) of tree ``t``,
    both traced with ``t`` and ``depth_bound`` as the trainer passes them."""
    @jax.jit
    def both(out, t, Xb, depth_bound):
        fresh = engine_train._fresh_tree(out, t, Xb.shape[1], B, has_cat)
        soa = {key: out[key][t] for key in engine_train._TREE_KEYS}
        return (tree_leaves(fresh, Xb, depth_bound),
                tree_leaves(soa, Xb, depth_bound),
                fresh["value"])

    fresh = jax.eval_shape(
        lambda o: engine_train._fresh_tree(o, 0, Xb.shape[1], B, has_cat),
        out)
    got, soa, value = both({k: jnp.asarray(v) for k, v in out.items()},
                           jnp.int32(t), jnp.asarray(Xb), depth_bound)
    np.testing.assert_array_equal(np.asarray(value), out["value"][t])
    return np.asarray(got), np.asarray(soa), set(fresh)


# N, F, B, bins dtype, M: the cells' widths (Higgs 28, MS LTR 136, Epsilon
# 2000 u8 columns; one u16 table), each to the depth its M allows
SHAPES = {
    "u8x28": (700, 28, 256, np.uint8, 511),
    "u8x136": (500, 136, 256, np.uint8, 509),
    "u8x2000": (96, 2000, 256, np.uint8, 127),
    "u16x20": (600, 20, 1000, np.uint16, 255),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_walk_matches_soa_and_cpu(shape):
    N, F, B, dtype, M = SHAPES[shape]
    rng = np.random.default_rng(31 + list(SHAPES).index(shape))
    out, depth = _random_trees(rng, 3, M, F, B)
    Xb = _rows(rng, N, F, B, dtype)
    for t in range(3):
        got, soa, keys = _walk(out, t, Xb, jnp.int32(depth), B, False)
        assert keys == {"node_word", "value"}
        np.testing.assert_array_equal(got, soa)
        np.testing.assert_array_equal(
            got, predict_tree_leaves(out, Xb, t, depth))


def test_walk_categorical_keeps_the_bitset():
    rng = np.random.default_rng(41)
    out, depth = _random_trees(rng, 2, 63, 12, 256, cats=True)
    assert out["is_cat"].any()
    Xb = _rows(rng, 800, 12, 256, np.uint8)
    for t in range(2):
        got, soa, keys = _walk(out, t, Xb, depth, 256, True)
        assert keys == {"node_word", "value", "cat_bitset"}
        np.testing.assert_array_equal(got, soa)
        np.testing.assert_array_equal(
            got, predict_tree_leaves(out, Xb, t, depth))


def test_walk_traced_depth_bound_below_the_trees_depth():
    """A traced bound smaller than the tree is deep stops the walk at the
    same internal nodes on both arms and on the CPU."""
    rng = np.random.default_rng(43)
    out, depth = _random_trees(rng, 1, 511, 28, 256)
    assert depth > 4
    Xb = _rows(rng, 600, 28, 256, np.uint8)
    for bound in (1, 3, depth):
        got, soa, _ = _walk(out, 0, Xb, jnp.int32(bound), 256, False)
        np.testing.assert_array_equal(got, soa)
        np.testing.assert_array_equal(
            got, predict_tree_leaves(out, Xb, 0, bound))
        # a short bound stops rows above their leaves; the full one does not
        assert (out["feature"][0][got] >= 0).any() == (bound < depth)


@pytest.mark.parametrize("F,B,M", [(4097, 256, 31), (28, 256, 65537)],
                         ids=["features_past_12_bits", "nodes_past_16_bits"])
def test_shape_past_a_packed_width_falls_back(F, B, M):
    assert not packed_shapes_fit(F, B, M)
    rng = np.random.default_rng(47)
    out, depth = _random_trees(rng, 1, M, F, B)
    Xb = _rows(rng, 64, F, B, np.uint8)
    got, soa, keys = _walk(out, 0, Xb, depth, B, False)
    assert "node_word" not in keys and "feature" in keys
    np.testing.assert_array_equal(got, soa)
    np.testing.assert_array_equal(got, predict_tree_leaves(out, Xb, 0, depth))


def test_packed_shapes_fit_at_the_widths_edges():
    assert packed_shapes_fit(4096, 65536, 65536)
    assert not packed_shapes_fit(4097, 65536, 65536)
    assert not packed_shapes_fit(4096, 65537, 65536)
    assert not packed_shapes_fit(4096, 65536, 65537)


@pytest.mark.parametrize("cats", [False, True], ids=["numeric", "categorical"])
def test_device_packing_is_pack_node_words_bit_for_bit(cats):
    rng = np.random.default_rng(53)
    out, _ = _random_trees(rng, 4, 255, 4096, 65536, cats=cats)
    # garbage in the leaves' fields must pack to zero, as on the host
    leaf = out["feature"] < 0
    out["threshold"][leaf] = rng.integers(0, 65536, int(leaf.sum()))
    out["left"][leaf] = rng.integers(0, 255, int(leaf.sum()))
    fields = [out[key] for key in engine_train._WALK_KEYS]
    want = pack_node_words(*fields)
    got = jax.jit(pack_node_words_device)(*map(jnp.asarray, fields))
    assert got.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(got), want)
    # one tree sliced out by a traced index, as the trainer packs it
    one = jax.jit(lambda t, *f: pack_node_words_device(*(a[t] for a in f)))(
        jnp.int32(2), *map(jnp.asarray, fields))
    np.testing.assert_array_equal(np.asarray(one), want[2])


# ---- trained models: learned missing directions, categorical, K = 3 -------

def _model(kind):
    rng = np.random.default_rng(59)
    if kind == "missing":
        X, y = higgs_like(900, seed=11)
        X = X.copy()
        X[::4, 2] = np.nan
        X[1::5, 4] = np.nan
        X[2::3, 0] = np.nan
        params, cat = dict(objective="binary", num_trees=6, num_leaves=15), ()
    elif kind == "categorical":
        X = rng.standard_normal((900, 6)).astype(np.float32)
        X[:, 1] = rng.integers(0, 12, 900)
        X[::9, 3] = np.nan
        y = (X[:, 0] + (X[:, 1] > 5) > 0).astype(np.float32)
        params, cat = dict(objective="binary", num_trees=6, num_leaves=15), (1,)
    else:
        X = rng.standard_normal((700, 8)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32) + (X[:, 2] > 0.4)
        params, cat = dict(objective="multiclass", num_class=3, num_trees=4,
                           num_leaves=7), ()
    ds = dryad.Dataset(X, y, max_bins=32, categorical_features=cat)
    return dryad.train(dict(params, max_bins=32), ds, backend="cpu"), ds


@pytest.mark.parametrize("kind", ["missing", "categorical", "multiclass_k3"])
def test_walk_on_trained_models(kind):
    booster, ds = _model(kind)
    out = booster.tree_arrays()
    has_cat = bool(out["is_cat"].any())
    assert has_cat == (kind == "categorical")
    if kind == "missing":
        assert not out["default_left"][out["feature"] >= 0].all()
    if kind == "multiclass_k3":
        assert booster.num_outputs == 3
    B, depth = ds.mapper.total_bins, max(booster.max_depth_seen, 1)
    for t in range(out["feature"].shape[0]):
        got, soa, keys = _walk(out, t, ds.X_binned, depth, B, has_cat)
        assert ("cat_bitset" in keys) == has_cat and "node_word" in keys
        np.testing.assert_array_equal(got, soa)
        np.testing.assert_array_equal(
            got, predict_tree_leaves(out, ds.X_binned, t, depth))


# ---- the job: the metric the callback reports and the model's bytes -------

def _job(params, *, F=10, nan_col=None):
    X, y = higgs_like(2600, seed=23, num_features=F)
    if nan_col is not None:
        X = X.copy()
        X[::6, nan_col] = np.nan
    ds = dryad.Dataset(X[:2000], y[:2000], max_bins=32)
    dv = ds.bind(X[2000:], y[2000:])
    seen = []
    booster = dryad.train(
        dict(params, max_bins=32), ds, valid_sets=[dv], backend="tpu",
        callback=lambda it, info: seen.append(
            (it, {k: v for k, v in info.items() if k.startswith("valid")})))
    return seen, booster.to_bytes()


DEPTHWISE = dict(objective="binary", num_trees=6, num_leaves=15, max_depth=4,
                 growth="depthwise")
LEAFWISE = dict(objective="binary", num_trees=6, num_leaves=15,
                growth="leafwise")
# (params, DRYAD_CHUNK): the chunk program scores the valid rows inside
# itself; per-iteration dispatch (Epsilon's path) through _apply_valid_jit
JOBS = {
    "depthwise_chunked": (DEPTHWISE, "1"),
    "leafwise_chunked": (LEAFWISE, "1"),
    "depthwise_per_iteration": (DEPTHWISE, "0"),
}


@pytest.mark.parametrize("job", list(JOBS))
def test_job_reports_the_same_metric_and_model_as_the_soa_walk(
        job, monkeypatch):
    """``dryad.train`` with a valid set on the packed walk against the same
    job on the structure-of-arrays walk (the program as it was): every
    value the callback saw and the model's bytes are equal."""
    params, chunk = JOBS[job]
    monkeypatch.setenv("DRYAD_CHUNK", chunk)
    def arms():
        walk = default_registry().gauge("dryad_eval_walk")
        return {arm: walk.labels(arm=arm).value()
                for arm in ("packed", "legacy")}

    assert default_registry().enabled
    jax.clear_caches()                  # the gauge is set where eval traces
    packed = _job(params, nan_col=3)
    assert arms() == {"packed": 1.0, "legacy": 0.0}
    monkeypatch.setattr(engine_train, "packed_shapes_fit", lambda *a: False)
    jax.clear_caches()
    try:
        legacy = _job(params, nan_col=3)
    finally:
        jax.clear_caches()              # no later test meets the patched trace
    assert arms() == {"packed": 0.0, "legacy": 1.0}
    assert any("valid_auc" in info for _, info in packed[0])
    assert packed[0] == legacy[0]
    assert packed[1] == legacy[1]
