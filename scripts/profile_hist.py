"""Microbenchmark: where does a depth-8 boosting iteration spend its time?

Times each device stage of the levelwise grower in isolation on the
Higgs-200k shape (N=200k, F=28, B=256): single-leaf histogram, per-level
segmented histogram (P=128), split scan, argsort, predict-shaped sort.

r13: rides the canonical harness (engine/probes.timed_fori — K dependent
iterations in ONE jit, carried whole-unit perturbation, terminal real
fetch, runtime liveness proof), replacing the r2-era per-call walls this
script carried under ``no-block-until-ready`` waivers.  Arrays ride as
jit ARGUMENTS (the HTTP-413 closure rule).

Usage: PYTHONPATH=. python scripts/profile_hist.py [rows]
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

import dryad_tpu as dryad
from dryad_tpu.datasets import higgs_like
from dryad_tpu.engine.histogram import (
    build_hist,
    build_hist_multi,
    build_hist_segmented,
)
from dryad_tpu.engine.probes import timed_fori
from dryad_tpu.engine.split import find_best_split


def main():
    N = int(sys.argv[1]) if len(sys.argv) > 1 else 200_000
    F, B, P = 28, 256, 128
    K, reps = 3, 2
    X, y = higgs_like(N, seed=7)
    ds = dryad.Dataset(X, y, max_bins=B)
    Xb = jnp.asarray(ds.X_binned)
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (N,), jnp.float32)
    h = jnp.abs(g) + 0.1
    mask = jax.random.uniform(jax.random.PRNGKey(2), (N,)) < 0.8
    sel = jax.random.randint(jax.random.PRNGKey(1), (N,), 0, P).astype(
        jnp.int32)
    print(f"devices: {jax.devices()}  rows={N}")

    def show(tag, step, *args):
        ms, spread = timed_fori(step, K, reps, *args, label=tag)
        flag = "  SUSPECT" if spread > 0.05 else ""
        print(f"{tag:28s} {ms:8.2f} ms  spread {spread:.3f}{flag}")

    # single-leaf masked histogram — roll the MASK by the carried scalar
    def single(precision):
        def step(s, Xb, g, h, mask):
            si = s.astype(jnp.int32)
            hist = build_hist(Xb, g, h, jnp.roll(mask, si), B,
                              precision=precision, backend="auto")
            # plane sum: a single bin can be empty in binned Higgs data
            return s + 1.0, hist[0].sum()
        return step

    show("single-leaf hist (exact)", single("exact"), Xb, g, h, mask)
    show("single-leaf hist (fast)", single("fast"), Xb, g, h, mask)

    # segmented P=128 — rotate the SORT KEY (slot ids), selection fixed
    def seg(precision):
        def step(s, Xb, g, h, sel):
            si = s.astype(jnp.int32)
            hist = build_hist_segmented(Xb, g, h, (sel + si) % P, P, B,
                                        precision=precision, backend="auto")
            return s + 1.0, hist[0, 0].sum()
        return step

    show("segmented P=128 (exact)", seg("exact"), Xb, g, h, sel)
    show("segmented P=128 (fast)", seg("fast"), Xb, g, h, sel)

    # dense multi P=16
    def multi_step(s, Xb, g, h, sel):
        si = s.astype(jnp.int32)
        hist = build_hist_multi(Xb, g, h, (sel + si) % 16, 16, B)
        return s + 1.0, hist[0, 0].sum()

    show("multi dense P=16 (exact)", multi_step, Xb, g, h, sel)

    # the stable argsort a legacy level pays — rotated sort key
    def sort_step(s, sel):
        si = s.astype(jnp.int32)
        srt = jnp.argsort((sel + si) % P, stable=True)
        return s + 1.0, srt[0].astype(jnp.float32) + srt[-1].astype(
            jnp.float32)

    show("stable argsort (N,)", sort_step, sel)

    # split scan over the full-tree histogram
    hist0 = build_hist(Xb, g, h, mask, B, backend="auto")
    fmask = jnp.ones((F,), bool)
    iscat = jnp.zeros((F,), bool)

    def split_step(s, hh, fmask, iscat):
        smod = s - jnp.floor(s / 8.0) * 8.0
        hh2 = hh * (1.0 + 0.01 * smod)
        res = find_best_split(
            hh2, hh2[0].sum(), hh2[1].sum(), hh2[2].sum(),
            lambda_l2=1.0, min_child_weight=1e-3, min_data_in_leaf=20,
            min_split_gain=0.0, feat_mask=fmask, is_cat_feat=iscat,
            allow=jnp.bool_(True), has_cat=False)
        return s + 1.0, res.gain

    show("split scan (tree hist)", split_step, hist0, fmask, iscat)


if __name__ == "__main__":
    main()
