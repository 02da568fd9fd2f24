"""Measure the leaf-ordered permutation kernel against the per-level
sort + record-gather pair it is designed to replace (VERDICT r4 #2).

Configuration mirrors the 10M depth-8 worst case: N rows across P
segments, a random split per segment.  CLAUDE.md methodology: K dependent
reps inside ONE jit, the perturbation reaching the moved data (the side
bits derive from a loop-carried scalar), device-resident inputs.

Usage: PYTHONPATH=. python scripts/exp_r5_perm.py [N] [P]
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.engine import leafperm
from dryad_tpu.engine import pallas_hist

T = leafperm._TILE_ROWS


def loop_time(fn, *a_, K=5):
    def prog(s0, *a):
        return jax.lax.fori_loop(0, K, lambda i, s: fn(s, *a), s0)

    f = jax.jit(prog)
    # each timed call ends in a host fetch of the carried scalar, which
    # waits for the device
    float(f(jnp.float32(0), *a_))
    t0 = time.perf_counter()
    float(f(jnp.float32(1), *a_))
    return (time.perf_counter() - t0) / K * 1000


def device_correctness_check():
    """Small-N bitwise check vs the numpy oracle ON THE REAL DEVICE —
    interpret mode zero-fills uninitialized buffers and cannot catch
    hardware-layout bugs (the zero-alias finding), so the measurement run
    opens with this."""
    rng = np.random.default_rng(11)
    seg_counts = [700, 3, 1200, 0, 513]
    lt = np.maximum(-(-np.asarray(seg_counts) // T), 1)
    n_tiles = int(lt.sum())
    rec = np.zeros((n_tiles * T, 128), np.uint8)
    tile_slot = np.repeat(np.arange(len(seg_counts)), lt).astype(np.int32)
    row_seg = np.full(n_tiles * T, -1, np.int32)
    base = np.concatenate([[0], np.cumsum(lt)])
    for s, cnt in enumerate(seg_counts):
        r0 = base[s] * T
        rec[r0: r0 + cnt] = rng.integers(1, 255, (cnt, 128), dtype=np.uint8)
        row_seg[r0: r0 + cnt] = s
    rec[:, 8] = row_seg >= 0                       # valid flag
    # every segment splits on its own feature at bin 127 (bytes are
    # uniform in [1, 255)); the oracle's sides come from the same records
    P = len(seg_counts)
    run_rec = leafperm.pack_run_records(
        do=np.ones(P), feature=np.arange(P), thresh=np.full(P, 127))
    side = leafperm.layout_sides_np(rec, tile_slot, run_rec)
    bound = leafperm.tiles_bound(rec.shape[0], P)
    got, _, base_r = leafperm.move_level(
        jnp.asarray(rec), jnp.asarray(tile_slot), jnp.asarray(run_rec),
        bin_dtype=np.uint8, n_out_tiles=bound)
    n_out = int(base_r[-1]) + 1
    want, _, _ = leafperm.permute_records_np(rec, tile_slot, side, P, bound)
    np.testing.assert_array_equal(np.asarray(got)[: n_out * T],
                                  want[: n_out * T])
    print("on-device bitwise vs oracle: OK", flush=True)


def main():
    N = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000_000
    P = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    WB = 128
    rng = np.random.default_rng(0)
    print(f"device={jax.devices()[0]} N={N} P={P} WB={WB}", flush=True)
    device_correctness_check()

    # tile-aligned layout with P roughly-equal segments
    cnt = np.full(P, N // P, np.int32)
    cnt[: N % P] += 1
    lt = np.maximum(-(-cnt // T), 1)
    n_tiles = int(lt.sum())
    tile_slot = np.repeat(np.arange(P), lt).astype(np.int32)
    base = np.concatenate([[0], np.cumsum(lt)])
    row_seg = np.full(n_tiles * T, -1, np.int32)
    for s in range(P):
        row_seg[base[s] * T: base[s] * T + cnt[s]] = s
    rec = rng.integers(0, 255, (n_tiles * T, WB), dtype=np.uint8)
    rec[row_seg < 0] = 0
    rec[:, 8] = row_seg >= 0                       # valid flag
    rec_d = jnp.asarray(rec)
    tile_slot_d = jnp.asarray(tile_slot)
    bound = leafperm.tiles_bound(rec.shape[0], P)

    # ---- the level move: per-tile parameters, count, bookkeeping, move ----
    def perm_step(s, rec_d, tile_slot_d):
        # perturbed split: the runs' threshold on feature 0 (bytes uniform
        # in [0, 255)) changes with s, reaching every stage; s advances by
        # whole units per rep (dead-input trap note in CLAUDE.md): thr
        # alternates between reps
        run_rec = leafperm.pack_run_records(
            jnp.ones((P,)), jnp.zeros((P,)),
            jnp.full((P,), 115 + 13 * (s - jnp.floor(s / 2) * 2)))
        out, _, _ = leafperm.move_level(
            rec_d, tile_slot_d, run_rec, bin_dtype=jnp.uint8,
            n_out_tiles=bound)
        return s + 1.0 + out[0, 0].astype(jnp.float32) * 1e-20

    t_perm = loop_time(perm_step, rec_d, tile_slot_d, K=3)
    print(f"leafperm (count + bookkeeping + move, full N): {t_perm:8.1f} ms/level",
          flush=True)

    # ---- current pipeline: packed sort + record gather --------------------
    sel_np = rng.integers(0, P, N).astype(np.int32)
    sel_d = jnp.asarray(sel_np)
    records = jnp.asarray(
        rng.integers(-2**31, 2**31 - 1, (N, 9), dtype=np.int64)
        .astype(np.int32))

    def sort_step(s, sel_d):
        selp = (sel_d + s.astype(jnp.int32)) % P      # perturb the SORT KEY
        key = ((selp.astype(jnp.uint32) << jnp.uint32(24))
               | jnp.arange(N, dtype=jnp.uint32))
        srt = jnp.sort(key)
        return s + 1.0 + srt[0].astype(jnp.float32) * 1e-20

    t_sort = loop_time(sort_step, sel_d, K=3)

    half = N // 2
    # a RANDOM permutation prefix — the real plan gathers rows scattered
    # across the whole table (an earlier draft used slot ids as indices,
    # touching only P distinct rows: a degenerate tiny-working-set gather
    # that under-measured the baseline ~10x; caught in review)
    perm_idx = jnp.asarray(rng.permutation(N)[:half].astype(np.int32))

    def gather_step(s, records, perm_idx):
        idx = (perm_idx + s.astype(jnp.int32)) % N    # perturb the INDEX
        r = records[idx]
        return s + 1.0 + r[0, 0].astype(jnp.float32) * 1e-20

    t_gath = loop_time(gather_step, records, perm_idx, K=3)
    print(f"current  packed sort(full N) {t_sort:8.1f} ms   "
          f"record gather(N/2) {t_gath:8.1f} ms   "
          f"sum {t_sort + t_gath:8.1f} ms", flush=True)
    print(f"projected saving: {t_sort + t_gath - t_perm:8.1f} ms/level",
          flush=True)


if __name__ == "__main__":
    main()
