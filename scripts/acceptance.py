"""Run the five BASELINE.json acceptance configs end-to-end on the attached
device and print one result line each (recorded in STATUS.md).

Shapes follow BASELINE.json:7-11; synthetic stand-ins from
dryad_tpu.datasets since the real datasets aren't present in this
environment. Scale knob: ACCEPT_SCALE in (0, 1] shrinks row counts for
quick runs.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

import dryad_tpu as dryad
from dryad_tpu.datasets import (
    covertype_like,
    criteo_like,
    epsilon_like,
    higgs_like,
    mslr_like,
)
from dryad_tpu.metrics import accuracy as _acc
from dryad_tpu.metrics import auc, ndcg_at_k, rmse

SCALE = float(os.environ.get("ACCEPT_SCALE", 1.0))


def _n(n):
    return max(1000, int(n * SCALE))


def run(name, fn) -> bool:
    """One config's result line; False when it failed (every config still
    runs, and the exit code reports the failures)."""
    t0 = time.perf_counter()
    try:
        metrics = fn()
        metrics.update(status="ok", seconds=round(time.perf_counter() - t0, 1))
    except Exception as e:  # noqa: BLE001 — report every config, then fail
        metrics = {"status": f"FAIL: {type(e).__name__}: {e}",
                   "seconds": round(time.perf_counter() - t0, 1)}
    print(json.dumps({"config": name, **metrics}), flush=True)
    return metrics["status"] == "ok"


def higgs_100k():
    X, y = higgs_like(_n(100_000), seed=7)
    ds = dryad.Dataset(X, y)
    p = dict(objective="binary", num_trees=100, num_leaves=63, max_depth=6,
             growth="depthwise")
    b = dryad.train(p, ds, backend="tpu")
    b_cpu = dryad.train(p, ds, backend="cpu")
    same = bool(np.array_equal(b.feature, b_cpu.feature))
    return {"auc": round(auc(y, b.predict_binned(ds.X_binned)), 4),
            "cpu_tree_parity": same}


def covertype():
    X, y = covertype_like(_n(581_000), seed=11)
    ds = dryad.Dataset(X, y)
    p = dict(objective="multiclass", num_class=7, num_trees=30, num_leaves=63,
             max_depth=6, growth="depthwise")
    b = dryad.train(p, ds, backend="tpu")
    pred = b.predict_binned(ds.X_binned)
    return {"accuracy": round(_acc(y, pred), 4)}


def epsilon():
    X, y = epsilon_like(_n(400_000), num_features=2000, seed=13)
    ds = dryad.Dataset(X, y)
    p = dict(objective="regression", num_trees=20, num_leaves=63, max_depth=6,
             growth="depthwise")
    b = dryad.train(p, ds, backend="tpu")
    r = rmse(y, b.predict_binned(ds.X_binned))
    return {"rmse": round(r, 4), "label_std": round(float(np.std(y)), 4)}


def mslr():
    X, y, group = mslr_like(num_queries=_n(3000) // 3, seed=17)
    ds = dryad.Dataset(X, y, group=group)
    # max_depth set -> the batched leaf-wise grower (exact best-first
    # selection over a depth-capped expansion) replaces the sequential
    # O(N·leaves) slot machine
    p = dict(objective="lambdarank", num_trees=50, num_leaves=31,
             max_depth=10)
    b = dryad.train(p, ds, backend="tpu")
    qoff = np.concatenate([[0], np.cumsum(group)])
    scores = b.predict_binned(ds.X_binned, raw_score=True)
    base = ndcg_at_k(y, np.zeros_like(scores), qoff, 10)
    return {"ndcg@10": round(ndcg_at_k(y, scores, qoff, 10), 4),
            "random_ndcg": round(base, 4)}


def criteo():
    (indptr, indices, values, F), y, cat_ids = criteo_like(_n(500_000), seed=19)
    ds = dryad.Dataset(None, y, csr=(indptr, indices, values, F),
                       categorical_features=cat_ids, max_bins=256)
    p = dict(objective="binary", num_trees=30, num_leaves=63, max_depth=6,
             growth="depthwise", categorical_features=list(cat_ids))
    b = dryad.train(p, ds, backend="tpu")
    return {"auc": round(auc(y, b.predict_binned(ds.X_binned)), 4),
            "cat_splits": int(b.is_cat.sum())}


if __name__ == "__main__":
    import jax

    if jax.devices()[0].platform == "cpu":
        raise SystemExit("acceptance.py measures the attached device; jax "
                         "initialised with platform 'cpu' only")
    results = [
        run("higgs_100k_depth6_100trees", higgs_100k),
        run("covertype_581k_softmax", covertype),
        run("epsilon_400kx2000_regression", epsilon),
        run("mslr_lambdarank_ndcg", mslr),
        run("criteo_sparse_categorical", criteo),
    ]
    raise SystemExit(0 if all(results) else 1)
