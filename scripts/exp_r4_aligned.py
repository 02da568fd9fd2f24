"""Round-4 levers, measured composed (CLAUDE.md fori doctrine):

  A. generic plan (packed sort + alignment gather)  [r3 shipping path]
  B. aligned plan (count-injected sort, no alignment gather)
  C. each at two fill factors — 50% selected (the static worst case the
     grid is sized for) and 15% selected (a realistic deep level) — so the
     skip-empty kernel's saving is visible separately from the plan's.

The perturbation flips sel entries (the sort key), so plan, gathers, tiles
and kernel all stay live; counts are recomputed from the perturbed sel via
a chunked one-hot reduce INSIDE the loop (exactness preserved).

Usage: PYTHONPATH=... python scripts/exp_r4_aligned.py [rows] [P] [reps]
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.engine.pallas_hist import (
    _TILE_ROWS, hist_from_plan, make_records, tile_plan, tile_plan_aligned,
)


def main():
    N = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000_000
    P = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    K = int(sys.argv[3]) if len(sys.argv) > 3 else 5
    F, B = 28, 256
    T = _TILE_ROWS
    rng = np.random.default_rng(0)
    plat = jax.devices()[0].platform
    print(f"rows={N} P={P} reps={K} device={jax.devices()[0]}", flush=True)

    Xb = jnp.asarray(rng.integers(1, B, size=(N, F), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=N).astype(np.float32))
    bound = N // 2 + 1
    rec = jax.block_until_ready(make_records(Xb, g, h))

    def mksel(frac):
        # frac of rows spread over P slots, rest dropped (sentinel P)
        s = rng.integers(0, P, size=N).astype(np.int32)
        drop = rng.random(N) >= frac
        return jnp.asarray(np.where(drop, P, s))

    def loop_time(tag, step, *arrays):
        f = jax.jit(lambda s0, *a: jax.lax.fori_loop(
            0, K, lambda i, s: step(s, *a), s0))
        _ = float(f(jnp.float32(0.0), *arrays))
        t0 = time.perf_counter()
        _ = float(f(jnp.float32(0.0), *arrays))
        dt = (time.perf_counter() - t0) / K
        print(f"{tag:52s} {dt*1e3:9.1f} ms", flush=True)
        return dt

    def psel(s, ss):
        flip = (s * 1e-30).astype(jnp.int32)
        return ss.at[0].set(jnp.minimum(ss[0] + flip, P))

    def full_generic(s, ss, rc):
        sp = psel(s, ss)
        buf, tl, tf = tile_plan(sp, N, P, T, rows_bound=bound)
        hist = hist_from_plan(Xb, g, h, buf, tl, tf, P, B, platform=plat,
                              records=rc)
        return hist[0, 0, 0, 0] * 1e-30 + s * 0.0

    def full_aligned(s, ss, cnt, rc):
        # counts ride precomputed (the grower reads them off its own
        # histograms for free); the sel[0] perturbation's off-by-one vs cnt
        # misplaces at most one row — irrelevant for timing
        sp = psel(s, ss)
        buf, tl, tf = tile_plan_aligned(sp, cnt, N, P, T, rows_bound=bound)
        hist = hist_from_plan(Xb, g, h, buf, tl, tf, P, B, platform=plat,
                              records=rc)
        return hist[0, 0, 0, 0] * 1e-30 + s * 0.0

    for frac in (0.5, 0.15):
        sel = mksel(frac)
        sel_np = np.asarray(sel)
        cnt = jnp.asarray(np.bincount(sel_np[sel_np < P],
                                      minlength=P)[:P].astype(np.int32))
        loop_time(f"generic plan, fill={frac:.2f}", full_generic, sel, rec)
        loop_time(f"aligned plan, fill={frac:.2f}", full_aligned, sel, cnt,
                  rec)


if __name__ == "__main__":
    main()
