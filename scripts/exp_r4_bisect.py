"""Round-4 composed-pipeline bisect at 10M: where do the ~300 ms that are
invisible in isolated stage timings (profile_plan.py) live?

profile_plan r4 re-run: parts sum to ~378 ms (plan 78 + X gather 122 +
g/h gather 46 + transpose 28 + pack 23 + kernel 82) but the composed
build_hist_segmented measures 679 ms.  This script times PREFIXES of the
composed pipeline (plan -> gather -> unpack -> transpose -> pack ->
kernel), all inside one jit with the sort key perturbed per iteration
(CLAUDE.md doctrine: the perturbation must reach every live stage), so the
jump between prefixes locates the composition cost.

Usage: PYTHONPATH=... python scripts/exp_r4_bisect.py [rows] [P] [reps]
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.engine import pallas_hist as ph
from dryad_tpu.engine.pallas_hist import (
    _TILE_ROWS, _hist_tiles, _pack_weights, _tiles_from_rows,
    hist_from_plan, make_records, tile_plan,
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
    sel_np = rng.integers(0, 2 * P, size=N).astype(np.int32)
    sel_np = np.where(sel_np < P, sel_np, P)
    sel = jnp.asarray(sel_np)
    bound = N // 2 + 1
    rec = jax.block_until_ready(make_records(Xb, g, h))

    def loop_time(tag, step, *arrays):
        f = jax.jit(lambda s0, *a: jax.lax.fori_loop(
            0, K, lambda i, s: step(s, *a), s0))
        _ = float(f(jnp.float32(0.0), *arrays))
        t0 = time.perf_counter()
        _ = float(f(jnp.float32(0.0), *arrays))
        dt = (time.perf_counter() - t0) / K
        print(f"{tag:46s} {dt*1e3:9.1f} ms", flush=True)
        return dt

    # the perturbation flips a few sel entries per trip -> the sort key,
    # hence the plan, hence every downstream gather/tile/kernel, changes
    def psel(s, ss):
        flip = (s * 1e-30).astype(jnp.int32)
        return ss.at[0].set(jnp.minimum(ss[0] + flip, P))

    def pfx_plan(s, ss):
        buf, tl, tf = tile_plan(psel(s, ss), N, P, T, rows_bound=bound)
        return buf[0].astype(jnp.float32) * 1e-30 + s * 0.0

    def pfx_gather(s, ss, rc):
        buf, tl, tf = tile_plan(psel(s, ss), N, P, T, rows_bound=bound)
        safe = jnp.minimum(buf, N - 1)
        r = rc[safe]
        return r[0, 0].astype(jnp.float32) * 1e-30

    def pfx_unpack(s, ss, rc):
        buf, tl, tf = tile_plan(psel(s, ss), N, P, T, rows_bound=bound)
        n_tiles = buf.shape[0] // T
        safe = jnp.minimum(buf, N - 1)
        r = rc[safe]
        gh = jax.lax.bitcast_convert_type(r[:, :2], jnp.float32)
        fw = r.shape[1] - 2
        Xr = jax.lax.bitcast_convert_type(
            r[:, 2:], jnp.uint8).reshape(n_tiles * T, fw * 4)[:, :F]
        return (Xr[0, 0].astype(jnp.float32) + gh[0, 0]) * 1e-30

    def pfx_tiles(s, ss, rc):
        buf, tl, tf = tile_plan(psel(s, ss), N, P, T, rows_bound=bound)
        n_tiles = buf.shape[0] // T
        safe = jnp.minimum(buf, N - 1)
        r = rc[safe]
        gh = jax.lax.bitcast_convert_type(r[:, :2], jnp.float32)
        fw = r.shape[1] - 2
        Xr = jax.lax.bitcast_convert_type(
            r[:, 2:], jnp.uint8).reshape(n_tiles * T, fw * 4)[:, :F]
        Xt = _tiles_from_rows(Xr, n_tiles, T, B)
        return (Xt[0, 0, 0, 0].astype(jnp.float32) + gh[0, 0]) * 1e-30

    def pfx_pack(s, ss, rc):
        buf, tl, tf = tile_plan(psel(s, ss), N, P, T, rows_bound=bound)
        n_tiles = buf.shape[0] // T
        valid = (buf < N).reshape(n_tiles, T)
        safe = jnp.minimum(buf, N - 1)
        r = rc[safe]
        gh = jax.lax.bitcast_convert_type(r[:, :2], jnp.float32)
        gt = gh[:, 0].reshape(n_tiles, T)
        ht = gh[:, 1].reshape(n_tiles, T)
        fw = r.shape[1] - 2
        Xr = jax.lax.bitcast_convert_type(
            r[:, 2:], jnp.uint8).reshape(n_tiles * T, fw * 4)[:, :F]
        Xt = _tiles_from_rows(Xr, n_tiles, T, B)
        Wt = _pack_weights(gt, ht, valid)
        return (Xt[0, 0, 0, 0].astype(jnp.float32) + Wt[0, 0, 0]
                .astype(jnp.float32)) * 1e-30

    def pfx_full(s, ss, rc):
        buf, tl, tf = tile_plan(psel(s, ss), N, P, T, rows_bound=bound)
        hist = hist_from_plan(Xb, g, h, buf, tl, tf, P, B, platform=plat,
                              records=rc)
        return hist[0, 0, 0, 0] * 1e-30

    loop_time("plan", pfx_plan, sel)
    loop_time("plan+recgather", pfx_gather, sel, rec)
    loop_time("plan+recgather+unpack", pfx_unpack, sel, rec)
    loop_time("plan+recgather+unpack+tiles", pfx_tiles, sel, rec)
    loop_time("plan+...+pack_weights", pfx_pack, sel, rec)
    loop_time("FULL hist_from_plan (records)", pfx_full, sel, rec)

    # non-records variant for reference (what profile_plan measured);
    # Xb/g/h ride as ARGUMENTS — as closure constants the 280 MB matrix
    # blows the remote-compile request limit (HTTP 413)
    def pfx_full_norec(s, ss, X, gg, hh):
        buf, tl, tf = tile_plan(psel(s, ss), N, P, T, rows_bound=bound)
        hist = hist_from_plan(X, gg, hh, buf, tl, tf, P, B, platform=plat,
                              records=None)
        return hist[0, 0, 0, 0] * 1e-30
    loop_time("FULL hist_from_plan (no records)", pfx_full_norec, sel, Xb,
              g, h)


if __name__ == "__main__":
    main()
