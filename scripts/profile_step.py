"""Component timing for the 10M-row train step (VERDICT r1 item 2).

r13: rides the canonical harness (engine/probes.timed_fori — K dependent
iterations inside ONE jit, carried whole-unit perturbation, terminal
real fetch, runtime liveness proof).  The r2-era closure constants are
gone: every array — including the grown tree's — rides as a jit
ARGUMENT (the HTTP-413 rule), and the traversal stage perturbs the
THRESHOLDS (the old ``value + s`` perturbation never reached the
traversal, whose output is leaf ids — a dead input the harness would
reject).

Usage: PYTHONPATH=. python scripts/profile_step.py [rows] [K]
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.config import make_params
from dryad_tpu.engine.grower import grow_any
from dryad_tpu.engine.predict import tree_leaves
from dryad_tpu.engine.probes import timed_fori
from dryad_tpu.engine.train import _row_records
from dryad_tpu.objectives import get_objective


def main():
    N = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000_000
    K = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    F, B = 28, 256
    rng = np.random.default_rng(0)
    plat = jax.devices()[0].platform
    print(f"rows={N} features={F} bins={B} reps={K} device={jax.devices()[0]}")

    Xb = jnp.asarray(rng.integers(1, B, size=(N, F), dtype=np.uint8))
    y = jnp.asarray((rng.random(N) < 0.5).astype(np.float32))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=N).astype(np.float32))
    bag = jnp.ones((N,), bool)
    fmask = jnp.ones((F,), bool)
    iscat = jnp.zeros((F,), bool)

    p = make_params(dict(objective="binary", num_leaves=255, max_depth=8,
                         growth="depthwise"))
    obj = get_objective(p)

    def show(tag, step, *args):
        ms, spread = timed_fori(step, K, 2, *args, label=tag)
        flag = "  SUSPECT" if spread > 0.05 else ""
        print(f"{tag:22s} {ms:9.1f} ms  spread {spread:.3f}{flag}")
        return ms

    # grad/hess
    def gh_step(s, gg, yy):
        gr, hs = obj.grad_hess_jax(gg + s, yy)
        return s + 1.0, gr[0] + hs[N // 2]

    show("grad/hess:", gh_step, g, y)

    # grower
    def grow_step(s, X, gg, hh, bb, fmask, iscat):
        tr = grow_any(p, B, X, gg + s, hh, bb, fmask, iscat,
                      has_cat=False, platform=plat)
        # whole value table: internal nodes' values stay 0, so a fixed
        # pair of entries can be constant and read as dead
        return s + 1.0, jnp.sum(tr["value"])

    t_grow = show("grower (depthwise):", grow_step, Xb, g, h, bag,
                  fmask, iscat)

    # traversal on a grown tree (tree arrays as jit args) — the
    # perturbation shifts the THRESHOLDS (period 8), so every level's
    # comparisons move and the leaf-id sum shifts far above fp32 ulp
    tree = dict(grow_any(p, B, Xb, g, h, bag, fmask, iscat,
                         has_cat=False, platform=plat))

    def trav_step(s, X, tr):
        si = s.astype(jnp.int32)
        lv = tree_leaves({**tr, "threshold": tr["threshold"] + si % 8},
                         X, p.max_depth)
        return s + 1.0, jnp.sum(lv.astype(jnp.float32))

    show(f"traversal (d={p.max_depth}):", trav_step, Xb, tree)

    # score update given leaves
    leaves = tree_leaves(tree, Xb, p.max_depth)
    sc = jnp.zeros((N, 1), jnp.float32)

    def upd_step(s, lv, val, sc):
        col = jnp.take(sc, 0, axis=1) + (val + s)[lv]
        sc2 = jax.lax.dynamic_update_index_in_dim(sc, col, 0, axis=1)
        return s + 1.0, sc2[0, 0] + sc2[N // 2, 0]

    show("score update:", upd_step, leaves, tree["value"], sc)

    # full step: grow + score update via the grower's row_key/key_leaf
    def full_step(s, X, gg, hh, bb, fmask, iscat, sc):
        tr = grow_any(p, B, X, gg + s, hh, bb, fmask, iscat,
                      has_cat=False, platform=plat)
        col = jnp.take(sc, 0, axis=1) + _row_records(
            tr["key_leaf"], tr["value"], tr["row_key"])[0]
        return s + 1.0, jnp.sum(col) * jnp.float32(1.0 / N)

    t_full = show("grow+update(rowleaf):", full_step, Xb, g, h, bag,
                  fmask, iscat, sc)
    print(f"  outside-grower:     {(t_full - t_grow):9.1f} ms")


if __name__ == "__main__":
    main()
