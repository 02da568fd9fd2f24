"""Scale-prove streamed ingest (VERDICT r4 #8): ingest ~1e8 synthetic
sparse rows through the out-of-core CSR path under a RECORDED peak-RSS
budget, and assert stream ≡ in-memory bins on a subsample.

The Criteo envelope claim (streaming.py: 1e9 x 39 = 39 GB/pod, per-host
slices) has only been e2e-tested at 500k rows; this drives the same code
at 1e8 x 32 sparse features (3.2 GB binned — a realistic single-host
slice of the 39 GB pod matrix) while holding peak RSS well under the
naive dense-float footprint (1e8 x 32 f32 = 12.8 GB raw floats, which
this path never materializes).

Usage: python scripts/ingest_scale.py [rows] [--budget-gb 8]
(CPU-only — run it while the chip is idle; it is host-heavy.)
"""

import argparse
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def peak_rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("rows", nargs="?", type=int, default=100_000_000)
    ap.add_argument("--features", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=2_000_000)
    ap.add_argument("--budget-gb", type=float, default=8.0)
    args = ap.parse_args()
    N, F, C = args.rows, args.features, args.chunk

    from dryad_tpu.data.streaming import dataset_from_csr_chunks

    # synthetic sparse generator: ~10% density, deterministic per chunk;
    # NOTHING big is kept — each chunk is rebuilt on every pass
    nnz_per_row = max(F // 10, 3)

    def make_chunk(c0, n):
        rng = np.random.default_rng(1000 + c0 // C)
        indptr = np.arange(0, (n + 1) * nnz_per_row, nnz_per_row,
                           dtype=np.int64)
        # unique columns per row by construction: distinct offsets mod F
        # rotated per row (duplicate columns would make the dense
        # reference order-dependent)
        offs = rng.choice(F, nnz_per_row, replace=False).astype(np.int32)
        rows_local = np.arange(c0, c0 + n, dtype=np.int64)[:, None]
        cols = ((rows_local + offs[None, :]) % F).astype(np.int32).ravel()
        vals = rng.normal(size=n * nnz_per_row).astype(np.float32)
        return indptr, cols, vals

    def chunks():
        for c0 in range(0, N, C):
            n = min(C, N - c0)
            yield make_chunk(c0, n)

    rng_y = np.random.default_rng(5)
    y = (rng_y.random(N) < 0.5).astype(np.float32)

    t0 = time.perf_counter()
    ds = dataset_from_csr_chunks(chunks, y, N, F, max_bins=64,
                                 sample_rows=1 << 20, seed=3)
    wall = time.perf_counter() - t0
    rss = peak_rss_gb()
    binned_gb = ds.X_binned.nbytes / 1e9
    print(f"ingested {N:,} x {F} sparse rows in {wall:.0f}s | "
          f"binned matrix {binned_gb:.2f} GB | peak RSS {rss:.2f} GB "
          f"(budget {args.budget_gb} GB)", flush=True)

    # ---- stream ≡ in-memory on a subsample ---------------------------------
    sub = 500_000
    indptr, cols, vals = make_chunk(0, sub)
    # densify the first `sub` rows for the in-memory reference (vectorized:
    # fixed nnz per row makes the row index a repeat)
    dense = np.zeros((sub, F), np.float32)
    rows_idx = np.repeat(np.arange(sub), nnz_per_row)
    dense[rows_idx, cols[: sub * nnz_per_row]] = vals[: sub * nnz_per_row]
    Xb_ref = ds.mapper.transform(dense)
    np.testing.assert_array_equal(np.asarray(ds.X_binned[:sub]), Xb_ref)
    print("stream == in-memory bins on 500k-row subsample: EXACT",
          flush=True)

    ok = rss <= args.budget_gb
    print(f"RSS budget: {'OK' if ok else 'EXCEEDED'}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
