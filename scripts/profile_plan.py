"""Fine-grained stage breakdown of the segmented histogram pipeline at 10M.

profile_level.py showed the whole build_hist_segmented call dominated by
its surrounding data movement, not the kernel — this script times each
stage (tile plan, row gather, dtype cast, tile transpose, weight packing,
the kernel alone) and the packed single-word sort candidate in isolation.

r13: every stage rides the canonical harness (engine/probes.timed_fori)
with runtime liveness proofs; the r3-era ``block_until_ready`` setup
materializations are gone — device inputs passed as jit arguments are
forced by the harness's warm fetch before any timed wall starts, so no
explicit sync is needed.

Usage: PYTHONPATH=... python scripts/profile_plan.py [rows] [P] [reps]
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.engine.pallas_hist import (
    _TILE_ROWS, _hist_tiles, _pack_weights, _tiles_from_rows, tile_plan,
)
from dryad_tpu.engine.probes import timed_fori


def main():
    N = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000_000
    P = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    K = int(sys.argv[3]) if len(sys.argv) > 3 else 5
    F, B = 28, 256
    T = _TILE_ROWS
    rng = np.random.default_rng(0)
    plat = jax.devices()[0].platform
    print(f"rows={N} P={P} reps={K} device={jax.devices()[0]}")

    Xb = jnp.asarray(rng.integers(1, B, size=(N, F), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=N).astype(np.float32))
    sel_np = rng.integers(0, 2 * P, size=N).astype(np.int32)
    sel_np = np.where(sel_np < P, sel_np, P)
    sel = jnp.asarray(sel_np)
    bound = N // 2 + 1

    def show(tag, step, *args):
        ms, spread = timed_fori(step, K, 2, *args, label=tag)
        flag = "  SUSPECT" if spread > 0.05 else ""
        print(f"{tag:42s} {ms:9.1f} ms  spread {spread:.3f}{flag}")

    def rot(sel_, si):
        # rotate the SORT KEY mod P; sentinel P (dropped rows) stays put
        return jnp.where(sel_ < P, (sel_ + si) % P, P)

    # ---- stage 1: plan ------------------------------------------------------
    def argsort_step(s, ss):
        srt = jnp.argsort(rot(ss, s.astype(jnp.int32)), stable=True)
        return s + 1.0, (srt[0] + srt[N // 2]).astype(jnp.float32)

    show("argsort(sel) stable", argsort_step, sel)

    def packed_sort_step(s, ss):
        key = rot(ss, s.astype(jnp.int32)).astype(jnp.uint32) \
            * jnp.uint32(1 << 24) + jnp.arange(N, dtype=jnp.uint32)
        srt = jnp.sort(key)
        return s + 1.0, (srt[0] & jnp.uint32(0xFFFFFF)).astype(jnp.float32) \
            + (srt[N // 2] & jnp.uint32(0xFFFFFF)).astype(jnp.float32)

    show("packed uint32 single sort", packed_sort_step, sel)

    def plan_step(s, ss):
        buf, tl, tf = tile_plan(rot(ss, s.astype(jnp.int32)), N, P, T,
                                rows_bound=bound)
        return s + 1.0, (buf[0] + tl[0]).astype(jnp.float32)

    show("tile_plan total", plan_step, sel)

    buf, tile_leaf, tile_first = tile_plan(sel, N, P, T, rows_bound=bound)
    n_tiles = buf.shape[0] // T

    # ---- stage 2: gathers ---------------------------------------------------
    # the gather INDEX buffer rolls with the carried scalar: same access
    # volume every trip, different addresses — the stage cannot hoist
    # (gather locality measurably does not matter here, CLAUDE.md)
    Xp = jnp.concatenate([Xb, jnp.zeros((1, F), Xb.dtype)])

    def gx_step(s, xp, bb):
        rows = xp[jnp.roll(bb, s.astype(jnp.int32))]
        return s + 1.0, (rows[0, 0] + rows[rows.shape[0] // 2, 0]).astype(
            jnp.float32)

    show("X row gather uint8 (plan buf)", gx_step, Xp, buf)

    buf_sorted = jnp.sort(jnp.where(buf < N, buf, N))
    show("X row gather uint8 (sorted buf)", gx_step, Xp, buf_sorted)

    ghp = jnp.concatenate([jnp.stack([g, h], axis=1),
                           jnp.zeros((1, 2), jnp.float32)])

    def ggh_step(s, gp, bb):
        rows = gp[jnp.roll(bb, s.astype(jnp.int32))]
        return s + 1.0, rows[0, 0] + rows[rows.shape[0] // 2, 0]

    show("g/h two-col gather", ggh_step, ghp, buf)

    # ---- stage 3: cast + tile transpose ------------------------------------
    Xrows = Xp[buf]

    def cast_step(s, xr):
        si = s.astype(jnp.int32)
        # period-8 offset: a period-2 one repeats the same contrib
        # multiset across the liveness seeds at even K (harness-rejected)
        Xt = _tiles_from_rows(xr.astype(jnp.int32) + si % 8, n_tiles, T, B)
        return s + 1.0, Xt.reshape(-1)[0].astype(jnp.float32) \
            + Xt.reshape(-1)[-1].astype(jnp.float32)

    show("astype(i32) + tiles transpose", cast_step, Xrows)

    def t_u8_step(s, xr):
        si = s.astype(jnp.int32)
        xr = xr + (si % 8).astype(jnp.uint8)
        Fc = 32
        fpad = (-F) % Fc
        xrp = jnp.pad(xr, ((0, 0), (0, fpad)))
        Xt = xrp.reshape(n_tiles, T, 1, Fc).transpose(2, 0, 3, 1)
        return s + 1.0, Xt.reshape(-1)[0].astype(jnp.float32) \
            + Xt.reshape(-1)[-1].astype(jnp.float32)

    show("uint8 tiles transpose (no cast)", t_u8_step, Xrows)

    # ---- stage 4: weight packing -------------------------------------------
    ght = ghp[buf].reshape(n_tiles, T, 2)
    valid = (buf < N).reshape(n_tiles, T)

    def packw_step(s, gt, vv):
        Wt = _pack_weights(gt[:, :, 0] + s, gt[:, :, 1], vv)
        return s + 1.0, Wt[0, 0, 0].astype(jnp.float32) \
            + Wt[-1, 0, -1].astype(jnp.float32)

    show("pack_weights (current engine)", packw_step, ght, valid)

    # ---- stage 5: kernel alone ---------------------------------------------
    Xt = _tiles_from_rows(Xp[buf].astype(jnp.int32), n_tiles, T, B)
    Wt = _pack_weights(ght[:, :, 0], ght[:, :, 1], valid)
    tile_skip = jnp.zeros_like(tile_leaf)

    def kern_step(s, xt, wt, tl, tf, sk):
        hist = _hist_tiles(xt, wt + s.astype(jnp.bfloat16), tl,
                           tf, sk, num_cols=P, total_bins=B,
                           num_features=F, platform=plat)
        return s + 1.0, hist[0, 0].sum() + hist[-1, 0].sum()

    show("_hist_tiles kernel alone (i32 tiles)", kern_step, Xt, Wt,
         tile_leaf, tile_first, tile_skip)

    # ---- whole current pipeline for reference ------------------------------
    from dryad_tpu.engine.histogram import build_hist_segmented

    def whole_step(s, Xb, g, h, ss):
        hist = build_hist_segmented(Xb, g, h, rot(ss, s.astype(jnp.int32)),
                                    P, B, rows_per_chunk=65536,
                                    platform=plat, rows_bound=bound)
        return s + 1.0, hist[0, 0].sum()

    show("build_hist_segmented (whole)", whole_step, Xb, g, h, sel)


if __name__ == "__main__":
    main()
