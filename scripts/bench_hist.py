"""Reliable device-side histogram timing: loop inside ONE jit program.

Per-call host timing of a builder includes its dispatch, and separately
jitted parts measure slower than their sum, so both backends' builders
are timed
through the canonical harness (engine/probes.timed_fori since r13): K
dependent iterations under one lax.fori_loop, carried perturbation
liveness-proven at runtime, terminal real fetch, min-of-reps + spread.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import dryad_tpu as dryad
from dryad_tpu.datasets import higgs_like
from dryad_tpu.engine.histogram import build_hist, build_hist_segmented
from dryad_tpu.engine.probes import timed_fori

N, F, B = 200_000, 28, 256
K = 10


def main():
    X, y = higgs_like(N, seed=7)
    ds = dryad.Dataset(X, y, max_bins=B)
    Xb = jnp.asarray(ds.X_binned)
    g0 = jax.random.normal(jax.random.PRNGKey(0), (N,), jnp.float32)
    h0 = jnp.abs(g0) + 0.1
    mask = jax.random.uniform(jax.random.PRNGKey(2), (N,)) < 0.8
    sel = jax.random.randint(jax.random.PRNGKey(1), (N,), 0, 128).astype(
        jnp.int32)

    for backend in ("xla", "pallas"):
        def single_step(s, Xb, g, h, mask):
            si = s.astype(jnp.int32)
            hist = build_hist(Xb, g, h, jnp.roll(mask, si), B,
                              backend=backend)
            return s + 1.0, hist[0].sum()

        def seg_step(s, Xb, g, h, sel):
            si = s.astype(jnp.int32)
            hist = build_hist_segmented(Xb, g, h, (sel + si) % 128, 128, B,
                                        backend=backend)
            return s + 1.0, hist[0, 0].sum()

        t1, sp1 = timed_fori(single_step, K, 2, Xb, g0, h0, mask,
                             label=f"single-{backend}")
        t2, sp2 = timed_fori(seg_step, K, 2, Xb, g0, h0, sel,
                             label=f"seg-{backend}")
        print(f"{backend:7s} single: {t1:7.2f} ms (spread {sp1:.3f})   "
              f"seg P=128: {t2:7.2f} ms (spread {sp2:.3f})")


if __name__ == "__main__":
    main()
