"""Device histogram builder — the TPU equivalent of the reference's CUDA
per-feature histogram kernel (BASELINE.json:5; SURVEY.md §2 #5).

TPUs have no atomic scatter-add, so the bincount-style scatter the CUDA
kernel relies on is reformulated as a **masked one-hot matmul** that runs on
the MXU (SURVEY.md §7 step 2):

    hist[k, f, b] = sum_r w[k, r] * [bin(r, f) == b]      k in {grad, hess, count}

i.e. a (3, C) x (C, F*B) matmul per row-chunk, with the one-hot operand
built by comparing the chunk's bin ids against an iota and never leaving the
fusion scope of one chunk.  Chunks are processed under ``lax.scan`` so the
one-hot temporary stays bounded regardless of N (Epsilon's 2000 features
stress this — BASELINE.json:9).

Accumulation is fp32: exact for counts below 2**24 and within last-ulp of
the CPU reference's f64 histograms for gain argmax purposes (documented
tolerance, SURVEY.md §7 hard part c).

When ``axis_name`` is set the per-shard partial histogram is reduced
cross-shard by ``distributed.reduce_hist`` — the fused ``jax.lax.psum``
(the NCCL-allreduce replacement, SURVEY.md §2 #14; grad, hess, and count
ride one fused collective per call) or, for the level builders under
``hist_reduce="feature"`` (r16), a feature-partition reduce-scatter that
leaves each shard its owned fully-reduced F/n slice.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def resolve_backend(backend: str, *, segmented: bool = False,
                    platform: str | None = None) -> str:
    """auto -> the measured winner per path: the Pallas kernel on a TPU
    for BOTH the leaf-segmented level pass (1.7x over the XLA matmul) and,
    since the round-3 pipeline shrink, the single-mask pass too (the XLA
    one-hot materializes C x F*B fp32 per chunk in HBM — 252 vs 136 ms at
    Higgs-10M, 1262 vs 320 ms at Epsilon shapes); XLA on CPU (Pallas would
    run interpreted) and on any non-TPU accelerator (the kernel uses
    TPU-only Mosaic features).

    ``platform`` overrides the process default backend when the caller
    knows the devices that will actually run the program (e.g. a CPU mesh
    forced on a TPU-attached process — train_device resolves against its
    mesh and passes a concrete backend down)."""
    if backend == "auto":
        from dryad_tpu.policy.gates import resolve

        return resolve("hist_backend",
                       {"platform": platform or jax.default_backend()})
    return backend


def _resolve_precision(precision: str):
    """exact -> HIGHEST (6-pass fp32 MXU; the default would round the f32
    operands to bf16 and break gain-argmax parity with the CPU reference).
    fast -> DEFAULT (single-pass bf16, ~6x; counts stay exact because the
    0/1 products accumulate in f32)."""
    import jax as _jax

    return (_jax.lax.Precision.HIGHEST if precision == "exact"
            else _jax.lax.Precision.DEFAULT)


def _chunk_rows(num_rows: int, num_features: int, total_bins: int,
                rows_per_chunk: int, elem_budget: int = 1 << 26) -> int:
    """Row-chunk size: respect the caller's cap and a one-hot element budget."""
    by_budget = max(256, elem_budget // max(num_features * total_bins, 1))
    c = min(rows_per_chunk, by_budget, max(num_rows, 1))
    return max(c, 1)


@jax.named_scope("dryad.hist")
def build_hist(
    Xb: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    mask: jnp.ndarray,
    total_bins: int,
    *,
    rows_per_chunk: int = 65536,
    axis_name: str | None = None,
    precision: str = "exact",
    backend: str = "xla",
    platform: str | None = None,
) -> jnp.ndarray:
    """Masked per-(feature, bin) sums -> (3, F, B) fp32: grad, hess, count.

    ``mask`` (N,) bool selects the rows that contribute (the rows of the leaf
    being histogrammed — the replacement for gathering a dynamic row list,
    which XLA's static-shape model rules out).
    """
    if resolve_backend(backend, platform=platform) == "pallas":
        from dryad_tpu.engine import pallas_hist

        if pallas_hist.supports(total_bins):
            return pallas_hist.build_hist_pallas(
                Xb, g, h, mask, total_bins, axis_name=axis_name,
                platform=platform,
            )
    # NOTE: this body must stay accumulation-order-identical to
    # build_hist_classes (its K=1 case) — test_build_hist_classes_matches_
    # per_class pins the bitwise contract with a multi-chunk fixture, and
    # scripts/smoke_tpu.py re-asserts it on the real device (the lowering
    # is fusion-sensitive there).  Delegating to the classes builder was
    # tried and measured 3.6x slower per call; unifying the other way
    # (precomputing w in the classes builder) would materialize (2K+1)*N
    # floats in HBM — 600 MB for K=7 at 10M rows — so the two bodies stay
    # separate on purpose.
    N, F = Xb.shape
    B = int(total_bins)
    prec = _resolve_precision(precision)
    C = _chunk_rows(N, F, B, rows_per_chunk)
    pad = (-N) % C
    if pad:
        Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
        g = jnp.pad(g, (0, pad))
        h = jnp.pad(h, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    n_chunks = (N + pad) // C

    Xc = Xb.reshape(n_chunks, C, F)
    m = mask.astype(jnp.float32).reshape(n_chunks, C)
    # weights (n_chunks, 3, C): grad, hess, count — one matmul covers all three
    w = jnp.stack(
        [g.astype(jnp.float32).reshape(n_chunks, C) * m,
         h.astype(jnp.float32).reshape(n_chunks, C) * m,
         m],
        axis=1,
    )
    iota = jnp.arange(B, dtype=jnp.int32)

    def body(acc, chunk):
        xc, wc = chunk
        onehot = (xc.astype(jnp.int32)[:, :, None] == iota).astype(jnp.float32)
        part = jax.lax.dot_general(
            wc, onehot.reshape(C, F * B),
            (((1,), (0,)), ((), ())),
            precision=prec,
            preferred_element_type=jnp.float32,
        )
        return acc + part, None

    acc0 = jnp.zeros((3, F * B), jnp.float32)
    if axis_name is not None:
        # under shard_map the carry must be marked device-varying to match
        # the varying per-chunk partials (JAX vma tracking)
        acc0 = jax.lax.pcast(acc0, axis_name, to="varying")
    acc, _ = jax.lax.scan(body, acc0, (Xc, w))
    hist = acc.reshape(3, F, B)
    if axis_name is not None:
        from dryad_tpu.engine.distributed import reduce_hist

        hist = reduce_hist(hist, axis_name)  # the NCCL-allreduce equivalent
    return hist


@partial(jax.jit, static_argnames=("total_bins", "rows_per_chunk"))
def build_hist_jit(Xb, g, h, mask, total_bins, rows_per_chunk=65536):
    return build_hist(Xb, g, h, mask, total_bins, rows_per_chunk=rows_per_chunk)


@jax.named_scope("dryad.hist")
def build_hist_classes(
    Xb: jnp.ndarray,
    g_all: jnp.ndarray,   # (N, K) f32
    h_all: jnp.ndarray,   # (N, K) f32
    mask: jnp.ndarray,
    total_bins: int,
    *,
    rows_per_chunk: int = 65536,
    precision: str = "exact",
    axis_name: str | None = None,
) -> jnp.ndarray:
    """Shared-plan histograms for K classes in ONE pass -> (K, 3, F, B).

    Multiclass iterations grow K trees whose ROOT level histograms all
    cover the same rows (trees only diverge after the first split), so the
    K per-class root passes collapse into a single matmul whose weight
    matrix carries 2K+1 rows (g_0..g_{K-1}, h_0..h_{K-1} + one shared
    count) — the MXU pads the row dimension to 8/128 anyway, so K=7 costs
    the same pass a single class does (CLAUDE.md open item; Covertype).

    Per-class slices are accumulation-order-identical to ``build_hist``
    (same chunking, same products, same dot) — the bitwise contract is
    pinned by test_build_hist_classes_matches_per_class on a multi-chunk
    fixture; keep the two bodies in sync.
    """
    N, F = Xb.shape
    B = int(total_bins)
    K = g_all.shape[1]
    prec = _resolve_precision(precision)
    C = _chunk_rows(N, F, B, rows_per_chunk)
    pad = (-N) % C
    if pad:
        Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
        g_all = jnp.pad(g_all, ((0, pad), (0, 0)))
        h_all = jnp.pad(h_all, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, (0, pad))
    n_chunks = (N + pad) // C

    Xc = Xb.reshape(n_chunks, C, F)
    m = mask.astype(jnp.float32).reshape(n_chunks, C)
    # class-MAJOR chunk layout (n_chunks, K, C): the row dimension C stays
    # in lanes.  A (C, K) minor-dim-K layout pads K up to 128 under XLA's
    # (8, 128) tiling — measured 5x slower build_hist calls at K=1 when
    # this function became the shared implementation (CLAUDE.md lane rule)
    gc = g_all.astype(jnp.float32).T.reshape(K, n_chunks, C).transpose(1, 0, 2)
    hc = h_all.astype(jnp.float32).T.reshape(K, n_chunks, C).transpose(1, 0, 2)
    iota = jnp.arange(B, dtype=jnp.int32)

    def body(acc, chunk):
        xc, gk, hk, mk = chunk                      # gk/hk: (K, C)
        onehot = (xc.astype(jnp.int32)[:, :, None] == iota).astype(jnp.float32)
        # (2K+1, C) rows: g_0..g_{K-1}, h_0..h_{K-1}, count
        w = jnp.concatenate([gk * mk[None, :], hk * mk[None, :],
                             mk[None, :]])
        part = jax.lax.dot_general(
            w, onehot.reshape(C, F * B),
            (((1,), (0,)), ((), ())),
            precision=prec,
            preferred_element_type=jnp.float32,
        )
        return acc + part, None

    acc0 = jnp.zeros((2 * K + 1, F * B), jnp.float32)
    if axis_name is not None:
        # under shard_map the carry must be marked device-varying to match
        # the varying per-chunk partials (JAX vma tracking)
        acc0 = jax.lax.pcast(acc0, axis_name, to="varying")
    acc, _ = jax.lax.scan(body, acc0, (Xc, gc, hc, m))
    gs = acc[:K].reshape(K, 1, F, B)
    hs = acc[K: 2 * K].reshape(K, 1, F, B)
    cnt = jnp.broadcast_to(acc[2 * K].reshape(1, 1, F, B), (K, 1, F, B))
    hist = jnp.concatenate([gs, hs, cnt], axis=1)  # (K, 3, F, B)
    if axis_name is not None:
        from dryad_tpu.engine.distributed import reduce_hist

        hist = reduce_hist(hist, axis_name)  # the NCCL-allreduce equivalent
    return hist


@jax.named_scope("dryad.hist")
def build_hist_multi(
    Xb: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    sel: jnp.ndarray,
    num_cols: int,
    total_bins: int,
    *,
    rows_per_chunk: int = 65536,
    axis_name: str | None = None,
    precision: str = "exact",
    hist_reduce: str = "fused",
) -> jnp.ndarray:
    """Histograms for ``num_cols`` leaves in ONE pass -> (P, 3, F, B) fp32.

    ``sel`` (N,) assigns each row to a column in [0, P); P means "drop".
    This is the level-wise formulation (SURVEY.md §7 step 6): batching every
    leaf of a tree level into the matmul's N dimension costs barely more
    than a single masked pass, because the MXU pads N to 128 anyway — the
    per-leaf masked approach wastes that padding P times over.

    One ``psum`` covers all P leaves' grad/hess/count stats when
    ``axis_name`` is set — the per-level histogram allreduce.
    """
    N, F = Xb.shape
    B = int(total_bins)
    P = int(num_cols)
    prec = _resolve_precision(precision)
    C = _chunk_rows(N, F, B, rows_per_chunk)
    pad = (-N) % C
    if pad:
        Xb = jnp.pad(Xb, ((0, pad), (0, 0)))
        g = jnp.pad(g, (0, pad))
        h = jnp.pad(h, (0, pad))
        sel = jnp.pad(sel, (0, pad), constant_values=P)
    n_chunks = (N + pad) // C

    Xc = Xb.reshape(n_chunks, C, F)
    gc = g.astype(jnp.float32).reshape(n_chunks, C)
    hc = h.astype(jnp.float32).reshape(n_chunks, C)
    sc = sel.astype(jnp.int32).reshape(n_chunks, C)
    iota_b = jnp.arange(B, dtype=jnp.int32)
    iota_p = jnp.arange(P, dtype=jnp.int32)

    def body(acc, chunk):
        xc, gk, hk, sk = chunk
        onehot = (xc.astype(jnp.int32)[:, :, None] == iota_b).astype(jnp.float32)
        onesel = (sk[None, :] == iota_p[:, None]).astype(jnp.float32)  # (P, C)
        w = jnp.stack([onesel * gk[None, :], onesel * hk[None, :], onesel])
        part = jax.lax.dot_general(
            w.reshape(3 * P, C), onehot.reshape(C, F * B),
            (((1,), (0,)), ((), ())),
            precision=prec,
            preferred_element_type=jnp.float32,
        )
        return acc + part, None

    acc0 = jnp.zeros((3 * P, F * B), jnp.float32)
    if axis_name is not None:
        acc0 = jax.lax.pcast(acc0, axis_name, to="varying")
    acc, _ = jax.lax.scan(body, acc0, (Xc, gc, hc, sc))
    hist = acc.reshape(3, P, F, B).transpose(1, 0, 2, 3)
    if axis_name is not None:
        from dryad_tpu.engine.distributed import reduce_hist

        hist = reduce_hist(hist, axis_name, hist_reduce)
    return hist


def _segment_tile(num_rows: int, num_cols: int) -> int:
    """Tile size for the segmented builder: bound per-leaf padding overhead
    (each leaf wastes < one tile) while keeping tiles MXU-friendly."""
    t = 128
    while t < 1024 and t * 4 * num_cols < num_rows:
        t *= 2
    return t


@jax.named_scope("dryad.hist")
def build_hist_segmented(
    Xb: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    sel: jnp.ndarray,
    num_cols: int,
    total_bins: int,
    *,
    rows_per_chunk: int = 65536,
    axis_name: str | None = None,
    precision: str = "exact",
    backend: str = "xla",
    rows_bound: int | None = None,
    platform: str | None = None,
    records: jnp.ndarray | None = None,
    sel_counts: jnp.ndarray | None = None,
    stage_gather: bool = True,
    hist_reduce: str = "fused",
) -> jnp.ndarray:
    """Histograms for ``num_cols`` leaves -> (P, 3, F, B) fp32, O(N·F·B) work.

    The dense ``build_hist_multi`` weight matrix makes every row pay for
    every leaf column (3P·N·F·B MACs) — fine for a handful of leaves, fatal
    at depth 8.  Here rows are *sorted by leaf* so each leaf occupies
    contiguous tiles, every tile's (3, T) @ (T, F*B) matmul serves exactly
    one leaf, and per-tile results scatter to leaves with one tiny matmul.
    Work: 3·(N + P·T)·F·B MACs per level — leaf-count independent, the same
    asymptotics the reference's CUDA scatter-add kernel gets from atomics.

    ``sel`` (N,) in [0, P]; P drops the row.  Deterministic: stable sort +
    fixed tile accumulation order.
    """
    if resolve_backend(backend, segmented=True, platform=platform) == "pallas":
        from dryad_tpu.engine import pallas_hist

        if pallas_hist.supports(total_bins):
            return pallas_hist.build_hist_segmented_pallas(
                Xb, g, h, sel, num_cols, total_bins, axis_name=axis_name,
                rows_bound=rows_bound, platform=platform, records=records,
                sel_counts=sel_counts, stage_gather=stage_gather,
                hist_reduce=hist_reduce,
            )
    N, F = Xb.shape
    B = int(total_bins)
    P = int(num_cols)
    prec = _resolve_precision(precision)
    T = _segment_tile(N, P)
    # one shared bucketing plan with the Pallas path (incl. the rows_bound
    # safety squeeze); clamped trailing tiles hold only sentinel rows, so
    # their leaf assignment contributes zeros to the scatter below
    from dryad_tpu.engine.pallas_hist import tile_plan

    buf, tile_leaf, _ = tile_plan(sel, N, P, T, rows_bound=rows_bound)
    n_tiles = buf.shape[0] // T

    # gather rows (sentinel N -> zero row)
    Xp = jnp.concatenate([Xb, jnp.zeros((1, F), Xb.dtype)])
    gp = jnp.concatenate([g.astype(jnp.float32), jnp.zeros((1,), jnp.float32)])
    hp = jnp.concatenate([h.astype(jnp.float32), jnp.zeros((1,), jnp.float32)])
    Xt = Xp[buf].reshape(n_tiles, T, F)
    gt = gp[buf].reshape(n_tiles, T)
    ht = hp[buf].reshape(n_tiles, T)
    valid = (buf < N).astype(jnp.float32).reshape(n_tiles, T)

    # chunk tiles so the one-hot temporary stays bounded
    tiles_per_chunk = max(1, _chunk_rows(n_tiles * T, F, B, rows_per_chunk) // T)
    cpad = (-n_tiles) % tiles_per_chunk
    if cpad:
        Xt = jnp.pad(Xt, ((0, cpad), (0, 0), (0, 0)))
        gt = jnp.pad(gt, ((0, cpad), (0, 0)))
        ht = jnp.pad(ht, ((0, cpad), (0, 0)))
        valid = jnp.pad(valid, ((0, cpad), (0, 0)))
    nc = (n_tiles + cpad) // tiles_per_chunk
    iota_b = jnp.arange(B, dtype=jnp.int32)

    def body(_, chunk):
        xc, gk, hk, vk = chunk                      # (Tc, T, ...)
        onehot = (xc.astype(jnp.int32)[..., None] == iota_b).astype(jnp.float32)
        w = jnp.stack([gk * vk, hk * vk, vk], axis=1)      # (Tc, 3, T)
        part = jax.lax.dot_general(
            w, onehot.reshape(xc.shape[0], T, F * B),
            (((2,), (1,)), ((0,), (0,))),
            precision=prec,
            preferred_element_type=jnp.float32,
        )                                           # (Tc, 3, F*B)
        return None, part

    _, tile_hists = jax.lax.scan(
        body, None,
        (Xt.reshape(nc, tiles_per_chunk, T, F),
         gt.reshape(nc, tiles_per_chunk, T),
         ht.reshape(nc, tiles_per_chunk, T),
         valid.reshape(nc, tiles_per_chunk, T)),
    )
    tile_hists = tile_hists.reshape(n_tiles + cpad, 3 * F * B)[:n_tiles]

    # scatter tiles -> leaves: one (P, n_tiles) x (n_tiles, 3FB) matmul
    onehot_tl = (tile_leaf[None, :] == jnp.arange(P, dtype=jnp.int32)[:, None])
    hist = jax.lax.dot_general(
        onehot_tl.astype(jnp.float32), tile_hists,
        (((1,), (0,)), ((), ())),
        precision=prec,
        preferred_element_type=jnp.float32,
    ).reshape(P, 3, F, B)
    if axis_name is not None:
        from dryad_tpu.engine.distributed import reduce_hist

        hist = reduce_hist(hist, axis_name, hist_reduce)
    return hist
