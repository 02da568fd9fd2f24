"""Pallas TPU histogram kernel — the native-kernel equivalent of the
reference's CUDA per-feature histogram builder (BASELINE.json:5; SURVEY.md
§2 #5, §7 step 3).

Why a hand-written kernel beats the XLA one-hot matmul (engine/histogram.py):

* **No HBM one-hot.**  XLA materializes the (rows, F*B) one-hot operand in
  HBM (hundreds of MB per chunk); here it is built in VMEM per row tile and
  consumed by the MXU inside the same kernel step.
* **Exact fp32 in one MXU pass.**  The MXU multiplies bf16 and accumulates
  f32.  The one-hot operand is 0/1 — exact in bf16 — so splitting each f32
  grad/hess into three bf16 limbs (truncated 8+8+8 mantissa bits) makes the
  products exact.  The XLA path needs ``Precision.HIGHEST`` (six passes)
  for the same accuracy because it cannot know one operand is exact.
  grad-hi/mid/lo, hess-hi/mid/lo and count ride as rows of one weight
  matrix, so "exact" costs exactly what "fast" would.
* **Leaf-segmented accumulation in VMEM.**  Rows arrive pre-grouped by
  leaf (tiles of one leaf are consecutive); the output block index is the
  tile's leaf id (scalar-prefetched), so Pallas keeps one leaf's partial
  histogram resident in VMEM across its tiles and spills it exactly once.

Hard-won lowering constraints baked into the design (measured on v5e):

* The MXU contraction must have a 128-row operand: ``w (8, T) @ onehot``
  lowers ~4x slower than ``w (128, T) @ onehot`` sliced back to 8 rows.
* Weight limbs must be split with *bitmask truncation*: the naive
  ``x - f32(bf16(x))`` is folded to zero by XLA's excess-precision
  simplifier under jit, and ``lax.reduce_precision`` lowers ~30x slower
  than bitwise ops here.
* Row tiles of 256 hit a pathological Mosaic path (~5x); use 512.
* Bin tiles are stored FEATURE-MAJOR ``(n_fb, n_tiles, Fc, T)`` — with the
  row dim T in lanes the HBM buffer has no lane padding; the row-major
  ``(T, Fc)`` alternative pads 8x under XLA's (8,128) tiling (12.9 GB on
  Epsilon shapes) and reads ~20x slower in-kernel.

Grid layout: ``(feature_chunks, row_tiles)`` — row tiles innermost so the
revisited output block (leaf, chunk) stays in VMEM while a leaf's tiles
stream through.  Feature chunking bounds the VMEM one-hot for wide data
(Epsilon: 2000 features — BASELINE.json:9).

The kernel is pure accumulation; the surrounding XLA program does the
cheap O(N) bookkeeping (leaf bucketing, gathers, weight limb splitting)
and the cross-device ``psum`` that replaces the reference's NCCL allreduce.

Two entries share the step (``_accumulate_tile``) and the output's
untangling (``_untangle``); which one runs follows from what the caller
holds.  ``_hist_tiles`` takes STAGED tiles: natural-order rows that XLA
gathered, transposed to feature-major u8 and gave weight-limb rows (the
root pass, the plan path).  ``_hist_tiles_rec`` takes a leaf-ordered
layout buffer as it lies (``leafperm.hist_from_layout``, the wired
levels): the selected tile is the block's address and the unpacking
happens in VMEM, so nothing row-sized is staged at all.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dryad_tpu.engine import leafperm   # the layout's record format

# weight rows: g_hi g_mid g_lo h_hi h_mid h_lo count (+ pad to the MXU tile)
_WROWS = 8
_MXU_M = 128          # weight rows padded to a full MXU tile (see module doc)
_LANE_BUDGET = 8192   # max Fc*Bp per chunk: bounds the one-hot SUBLANE dim
                      # (8 MB bf16 at T=512 in VMEM) AND the output block's
                      # lane dim (out_specs (1, _WROWS, Fc*Bp))
_TILE_ROWS = 512      # rows per tile (MXU K dim; 256 lowers pathologically)
# cap: Fc floors at 8 for sublane alignment, so Bp must satisfy
# 8 * Bp <= _LANE_BUDGET or the per-step one-hot exceeds the VMEM budget
_MAX_PALLAS_BINS = 1024


def supports(total_bins: int) -> bool:
    return int(total_bins) <= _MAX_PALLAS_BINS


def _interpret(platform: str | None = None) -> bool:
    return (platform or jax.default_backend()) == "cpu"


def _pow2_bins(B: int) -> int:
    """Bin dim padded to a power of two (>=16) for lane alignment."""
    return max(16, 1 << (B - 1).bit_length())


def _feature_chunk(F: int, Bp: int) -> int:
    """Features per chunk: a power of two (>= 8) so the kernel can recover
    the bin index from the tiled one-hot layout with a shift, bounded so
    Fc*Bp one-hot lanes fit the VMEM budget; Fc*Bp stays a multiple of 128
    (lane rule) since both factors are pow2 with product >= 128.

    Among the admissible sizes, pick the one minimizing the padded total
    ceil(F/Fc)*Fc — the largest pow2 is NOT always best (F=130 would pad
    97% at Fc=256 but only 5% at Fc=8)."""
    budget = max(8, _LANE_BUDGET // Bp)
    best, best_padded = 8, None
    fc = 8
    while fc <= budget:
        padded = -(-F // fc) * fc
        if best_padded is None or padded <= best_padded:
            best, best_padded = fc, padded   # ties -> larger fc (fewer chunks)
        if fc >= F:
            break
        fc *= 2
    return best


def _split3_f32(x: jnp.ndarray):
    """f32 -> three f32 limbs, each exact in bf16, whose sum is x exactly.

    Implemented by masking mantissa bits (truncation split), for two
    reasons: (a) XLA's excess-precision simplifier folds the naive
    ``x - f32(bf16(x))`` to zero inside jit, silently deleting the mid/lo
    limbs; (b) ``lax.reduce_precision`` survives jit but lowers ~30x slower
    than bitwise ops on this backend.  Masking the low 16 mantissa bits is
    exact, the residuals are exact f32 subtractions, and after two
    truncations the final residual fits bf16 exactly.
    """
    mask16 = jnp.uint32(0xFFFF0000)
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(u & mask16, jnp.float32)
    r1 = x - hi
    u1 = jax.lax.bitcast_convert_type(r1, jnp.uint32)
    mid = jax.lax.bitcast_convert_type(u1 & mask16, jnp.float32)
    return hi, mid, r1 - mid


def _split3(x: jnp.ndarray):
    """``_split3_f32``'s limbs as bf16 (the staged weight rows)."""
    hi, mid, lo = _split3_f32(x)
    # lo first: the order of the three converts is part of every traced
    # program's digest (analysis/goldens), so it stays as it always was
    lo = lo.astype(jnp.bfloat16)
    return hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16), lo


def _pack_weights(g: jnp.ndarray, h: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """(n_tiles, T) f32 grad/hess + validity -> (n_tiles, 8, T) bf16 rows.

    Only the 8 real weight rows hit HBM; the kernel zero-pads to the 128-row
    MXU tile in VMEM (the old (n_tiles, 128, T) buffer materialized ~1.3 GB
    of zeros per deep 10M-row level and the kernel re-read all of it)."""
    v = valid.astype(jnp.float32)
    gv = g.astype(jnp.float32) * v
    hv = h.astype(jnp.float32) * v
    cnt = v.astype(jnp.bfloat16)
    w = jnp.stack([*_split3(gv), *_split3(hv), cnt], axis=-2)
    return jnp.pad(w, ((0, 0), (0, _WROWS - w.shape[-2]), (0, 0)))


def _accumulate_tile(o_ref, first, x, weight_rows, padded_bins: int):
    """The shared step of both entries: one tile's bin ids ``x`` (Fc, T)
    int32 and weight rows ``weight_rows()`` (8, T) bf16 (a thunk: the
    staged entry reads them from VMEM only once the one-hot exists) ->
    w (128, T) @ one-hot (Fc*Bp, T)^T, written to (``first``) or added
    into the leaf's resident output block.

    The one-hot is built in the sublane-tiled layout that matches
    feature-major bin ids: ``pltpu.repeat`` TILES the bin-id block Bp
    times along sublanes (row r of the one-hot holds feature r mod Fc,
    bin r >> log2(Fc)); a shifted iota supplies the compared bin.  (The
    obvious 3-D reshape is an "unsupported shape cast" to Mosaic whenever
    Bp < 128; this layout needs no relayout at all.)  Both dot operands
    contract their trailing (lane) dim — the MXU consumes the transposed
    RHS natively.  The caller untangles the bin-major row order once,
    outside the kernel (``_untangle``)."""
    Fc, T = x.shape
    Bp = padded_bins
    shift = Fc.bit_length() - 1                # Fc is a power of two
    x_rep = pltpu.repeat(x, Bp, axis=0)       # (Fc*Bp, T) TILED copies
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (Fc * Bp, T), 0) >> shift
    onehot = (x_rep == iota_b).astype(jnp.bfloat16)
    # zero-pad the 8 weight rows to the 128-row MXU tile in VMEM (HBM
    # never holds more than the real rows — see _pack_weights)
    w = jnp.concatenate(
        [weight_rows(), jnp.zeros((_MXU_M - _WROWS, T), jnp.bfloat16)],
        axis=0)
    part = jax.lax.dot_general(
        w, onehot,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[:_WROWS]                                 # (8, Fc*Bp)

    @pl.when(first)
    def _():
        o_ref[0] = part

    @pl.when(jnp.logical_not(first))
    def _():
        o_ref[0] = o_ref[0] + part


def _tile_flags(tile_first_ref, tile_skip_ref, o_ref):
    """(first, skip) of this grid step's plan slot, and the one write a
    skipped slot may owe: an empty leaf's mandatory first tile
    zero-initializes its output block."""
    i = pl.program_id(1)
    first = tile_first_ref[i] == 1
    skip = tile_skip_ref[i] == 1

    @pl.when(first & skip)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    return first, skip


def _hist_kernel(tile_leaf_ref, tile_first_ref, tile_skip_ref, x_ref, w_ref,
                 o_ref, *, padded_bins: int):
    """One (feature-chunk, row-tile) step over STAGED tiles (the root pass
    and the plan path, whose rows the surrounding XLA code gathered).

    Tiles arrive FEATURE-MAJOR (Fc, T): the row dim T sits in lanes, so the
    HBM tile buffer has no lane padding (a (T, Fc) layout with Fc < 128
    pads up to 8x under XLA's (8,128) tiling — 12.9 GB for Epsilon-shaped
    data — and reads ~20x slower in-kernel).

    ``tile_skip`` marks tiles with zero live rows (the plan's static grid
    covers the worst-case N/2 smaller-children bound, but real levels often
    select far less — every padding tile used to pay the full one-hot +
    MXU dot for an exact-zero contribution).  Skipped tiles do no compute;
    their in_specs also remap to block 0 so consecutive skips elide the
    DMA.  An empty leaf's mandatory first tile still zero-initializes its
    output block.
    """
    first, skip = _tile_flags(tile_first_ref, tile_skip_ref, o_ref)

    @pl.when(jnp.logical_not(skip))
    def _():
        _accumulate_tile(o_ref, first,
                         x_ref[0, 0].astype(jnp.int32),  # (Fc, T) u8 -> i32
                         lambda: w_ref[0], padded_bins)


def _untangle(out, num_features: int, total_bins: int, chunk: int):
    """(P, 8, n_fb*Fc*Bp) kernel output -> (P, 3, F, B): the kernel's
    columns are (bin-major, feature-minor) per chunk, and the limb rows
    sum to grad / hess / count."""
    P = out.shape[0]
    Bp = _pow2_bins(total_bins)
    n_fb = out.shape[2] // (chunk * Bp)
    out = (out.reshape(P, _WROWS, n_fb, Bp, chunk)
              .transpose(0, 1, 2, 4, 3)
              .reshape(P, _WROWS, n_fb * chunk, Bp))[:, :, :num_features,
                                                     :total_bins]
    hg = out[:, 0] + out[:, 1] + out[:, 2]
    hh = out[:, 3] + out[:, 4] + out[:, 5]
    hc = out[:, 6]
    return jnp.stack([hg, hh, hc], axis=1)         # (P, 3, F, B)


@functools.partial(
    jax.jit, static_argnames=("num_cols", "total_bins", "num_features",
                              "axis_name", "platform")
)
def _hist_tiles(Xt, Wt, tile_leaf, tile_first, tile_skip, *, num_cols: int,
                total_bins: int, num_features: int,
                axis_name: str | None = None,
                platform: str | None = None) -> jnp.ndarray:
    """Core pallas_call: leaf-grouped tiles -> (P, 3, F, B) f32 histograms.

    Xt (n_fb, n_tiles, Fc, T) uint8 bin ids (feature-chunked, -padded; the
    kernel converts — u8 tiles move 4x fewer HBM bytes than the old i32),
    Wt (n_tiles, 8, T) bf16 weight limb rows, tile_leaf (n_tiles,)
    monotone non-decreasing leaf per tile, tile_first (n_tiles,) 1 on a
    leaf's first tile, tile_skip (n_tiles,) 1 on tiles with zero live rows
    (no compute, no fresh DMA — see _hist_kernel).  Every leaf in [0, P)
    must own at least one tile so its output block is written.

    ``axis_name`` must name the shard_map axis when tracing inside one —
    the per-shard partial histogram varies over it (vma) until the caller's
    psum.
    """
    n_fb, n_tiles, Fc, T = Xt.shape
    B = int(total_bins)
    P = int(num_cols)
    F = int(num_features)
    Bp = _pow2_bins(B)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_fb, n_tiles),
        in_specs=[
            # skipped tiles remap to block 0: consecutive skips keep the
            # same block index, so Pallas elides their input DMA entirely
            pl.BlockSpec((1, 1, Fc, T),
                         lambda j, i, tl, tf, sk: (j, i * (1 - sk[i]),
                                                   0, 0)),
            pl.BlockSpec((1, _WROWS, T),
                         lambda j, i, tl, tf, sk: (i * (1 - sk[i]),
                                                   0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _WROWS, Fc * Bp),
                               lambda j, i, tl, tf, sk: (tl[i], 0, j)),
    )
    out_shape = jax.ShapeDtypeStruct(
        (P, _WROWS, n_fb * Fc * Bp), jnp.float32,
        vma=None if axis_name is None else frozenset({axis_name}))
    out = pl.pallas_call(
        functools.partial(_hist_kernel, padded_bins=Bp),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=_interpret(platform),
        name="_hist_tiles",
    )(tile_leaf, tile_first, tile_skip, Xt, Wt)

    return _untangle(out, F, B, Fc)


def _hist_rec_kernel(src_ref, tile_leaf_ref, tile_first_ref, tile_skip_ref,
                     rec_ref, o_ref, *, padded_bins: int, chunk: int,
                     num_features: int, itemsize: int):
    """One (feature-chunk, plan-slot) step over a layout record tile IN
    PLACE: the block is the (T, _REC_WB) uint8 tile ``src[i]`` of the
    leaf-ordered layout buffer itself, so nothing row-sized is staged for
    the kernel (PR 31: the tile gather, the u8 relayout to feature-major
    tiles and the weight limbs were 45 ms of XLA passes a level).

    Unpacking happens in VMEM, the way the layout's own kernels read a
    tile (``leafperm._tile_sides``): a one-hot selector contracted with
    the tile on its byte dimension brings the wanted bytes out
    LANE-oriented, exactly (a byte is exact in bf16, one product a sum is
    non-zero).  Selector rows: this chunk's ``Fc`` feature bytes (u16
    bins: ``Fc`` low bytes, then ``Fc`` high bytes), then four groups of
    eight rows, group k holding byte k of g (row 0) and of h (row 1), and
    group 0 the valid flag (row 2).  The groups recombine with integer
    shifts into one (8, T) word block whose rows 0 and 1 bitcast to g and
    h; the flag multiplies them, ``_split3_f32`` splits them, and the
    seven rows ``_pack_weights`` would have written are assembled by
    sublane selects: same limbs, same one-hot, same product, same
    per-tile grouping as the staged entry, so the histogram is bitwise
    the staged one.

    A live slot whose tile holds only sentinels (flag 0 on every row)
    skips the one-hot and the product on a branch over the flag row it
    already holds; no pass over the records' rows outside the kernel
    decides it."""
    first, skip = _tile_flags(tile_first_ref, tile_skip_ref, o_ref)
    j = pl.program_id(0)       # read out here: the interpreter's branch
                               # bodies have no grid position

    @pl.when(jnp.logical_not(skip))
    def _():
        rec = leafperm._tile_bf16(rec_ref.at[0])       # (T, WB) bf16
        T, WB = rec.shape
        Fc, F = chunk, num_features
        nx = Fc * itemsize                             # feature-byte rows
        R = -(-(nx + 32) // 16) * 16                   # whole bf16 tiles
        row = jax.lax.broadcasted_iota(jnp.int32, (R, WB), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, WB), 1)
        f = j * Fc + (row & (Fc - 1))                  # Fc is a power of two
        tgt_x = leafperm._REC_X + f * itemsize + (row >> (Fc.bit_length() - 1))
        q, k = (row - nx) & 7, (row - nx) >> 3
        tgt_w = jnp.where(q == 0, leafperm._REC_G + k,
                          jnp.where(q == 1, leafperm._REC_H + k,
                                    jnp.where((q == 2) & (k == 0),
                                              leafperm._REC_FLAG, -1)))
        tgt = jnp.where(row < nx, jnp.where(f < F, tgt_x, -1),
                        jnp.where(row < nx + 32, tgt_w, -1))
        sel = (lane == tgt).astype(jnp.bfloat16)
        vb = jax.lax.dot_general(
            sel, rec, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)  # (R, T)
        x = vb[0:Fc]
        if itemsize == 2:
            x = x + vb[Fc:2 * Fc] * 256                # little-endian u16
        words = (vb[nx:nx + 8] | (vb[nx + 8:nx + 16] << 8)
                 | (vb[nx + 16:nx + 24] << 16) | (vb[nx + 24:nx + 32] << 24))
        v = (words[2:3] == 1).astype(jnp.float32)      # (1, T) valid rows

        def weight_rows():
            gh = jax.lax.bitcast_convert_type(words, jnp.float32) * v
            hi, mid, lo = _split3_f32(gh)              # rows 0 / 1: g / h
            r8 = jax.lax.broadcasted_iota(jnp.int32, (_WROWS, T), 0)
            w8 = jnp.zeros((_WROWS, T), jnp.float32)
            for r, limb in enumerate((hi[0:1], mid[0:1], lo[0:1],
                                      hi[1:2], mid[1:2], lo[1:2], v)):
                w8 = jnp.where(r8 == r, limb, w8)
            return w8.astype(jnp.bfloat16)             # every limb is exact

        any_valid = jnp.max(v) > 0

        @pl.when(any_valid)
        def _():
            _accumulate_tile(o_ref, first, x, weight_rows, padded_bins)

        @pl.when(jnp.logical_not(any_valid) & first)
        def _():
            o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("num_cols", "total_bins", "num_features",
                              "bin_dtype", "axis_name", "platform")
)
def _hist_tiles_rec(rec, src, tile_leaf, tile_first, tile_skip, *,
                    num_cols: int, total_bins: int, num_features: int,
                    bin_dtype, axis_name: str | None = None,
                    platform: str | None = None) -> jnp.ndarray:
    """``_hist_tiles`` for rows that lie in a leaf-ordered layout buffer:
    plan slot i histograms record tile ``src[i]`` of ``rec``
    (n_tiles_in*T, _REC_WB) uint8 where it lies — the gather is the
    block's ``index_map``, the unpacking the kernel's own
    (``_hist_rec_kernel``).  tile_leaf / tile_first / tile_skip as in
    ``_hist_tiles``; a skipped slot's block remaps to tile 0, so runs of
    skips move nothing.  Wide records (more than one feature chunk) keep
    the ``(n_fb, n_tiles)`` grid: chunk j selects its own bytes from the
    same record block.  -> (P, 3, F, B) f32."""
    T = _TILE_ROWS
    n_tiles = src.shape[0]
    B = int(total_bins)
    P = int(num_cols)
    F = int(num_features)
    Bp = _pow2_bins(B)
    Fc = _feature_chunk(F, Bp)
    n_fb = -(-F // Fc)
    itemsize = jnp.dtype(bin_dtype).itemsize
    rec3 = rec.reshape(rec.shape[0] // T, T, rec.shape[1])
    if _interpret(platform):
        # the HLO interpreter writes every blocked operand back whole at
        # every grid step, so a layout buffer of some hundred MB costs tens
        # of ms a step there: on the CPU the kernel is handed the selected
        # tiles' used bytes alone, with the same addressing over them
        rec3 = rec3[src][:, :, :leafperm._REC_X + F * itemsize]
        src = jnp.arange(n_tiles, dtype=src.dtype)
    WB = rec3.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_fb, n_tiles),
        in_specs=[
            pl.BlockSpec((1, T, WB),
                         lambda j, i, sr, tl, tf, sk: (sr[i] * (1 - sk[i]),
                                                       0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _WROWS, Fc * Bp),
                               lambda j, i, sr, tl, tf, sk: (tl[i], 0, j)),
    )
    out_shape = jax.ShapeDtypeStruct(
        (P, _WROWS, n_fb * Fc * Bp), jnp.float32,
        vma=None if axis_name is None else frozenset({axis_name}))
    out = pl.pallas_call(
        functools.partial(_hist_rec_kernel, padded_bins=Bp, chunk=Fc,
                          num_features=F, itemsize=itemsize),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=_interpret(platform),
        name="_hist_tiles_rec",
    )(src, tile_leaf, tile_first, tile_skip, rec3)
    return _untangle(out, F, B, Fc)


def _tiles_from_rows(X_rows: jnp.ndarray, n_tiles: int, T: int, B: int) -> jnp.ndarray:
    """(n_tiles*T, F) gathered bin rows -> feature-chunked (n_fb, n_tiles, Fc, T).

    Always a real transpose (T and Fc swap) — its cost is part of every
    root-pass and plan-path call (the wired levels stage nothing:
    ``_hist_tiles_rec``); the payoff is the unpadded, fast-reading tile
    buffer (see _hist_kernel).  Stays in the narrow storage dtype end to end (the
    kernel converts): the u8 transpose measured ~2x faster than i32 and the
    tile buffer is 4x smaller in HBM.
    """
    F = X_rows.shape[-1]
    Fc = _feature_chunk(F, _pow2_bins(B))
    fpad = (-F) % Fc
    if fpad:
        X_rows = jnp.pad(X_rows, ((0, 0), (0, fpad)))
    n_fb = (F + fpad) // Fc
    Xt = X_rows.reshape(n_tiles, T, n_fb, Fc)
    # feature-major (Fc, T) tiles: T in lanes -> no XLA lane padding on the
    # HBM buffer and a ~20x faster in-kernel read (see _hist_kernel doc)
    return Xt.transpose(2, 0, 3, 1)


def build_hist_pallas(
    Xb: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    mask: jnp.ndarray,
    total_bins: int,
    *,
    axis_name: str | None = None,
    platform: str | None = None,
) -> jnp.ndarray:
    """Single-leaf masked histogram -> (3, F, B) f32 (root / leaf-wise path).

    Rows stream in natural order (no leaf bucketing needed); masked-out rows
    ride along with zero weight limbs.
    """
    N, F = Xb.shape
    B = int(total_bins)
    T = _TILE_ROWS
    pad = (-N) % T
    Xp = jnp.pad(Xb, ((0, pad), (0, 0)))           # stays u8/u16 (kernel casts)
    gp = jnp.pad(g.astype(jnp.float32), (0, pad))
    hp = jnp.pad(h.astype(jnp.float32), (0, pad))
    mp = jnp.pad(mask, (0, pad))
    n_tiles = (N + pad) // T

    Xt = _tiles_from_rows(Xp, n_tiles, T, B)
    mt = mp.reshape(n_tiles, T)
    Wt = _pack_weights(gp.reshape(n_tiles, T), hp.reshape(n_tiles, T), mt)
    tile_leaf = jnp.zeros((n_tiles,), jnp.int32)
    tile_first = jnp.zeros((n_tiles,), jnp.int32).at[0].set(1)
    tile_skip = 1 - jnp.any(mt, axis=1).astype(jnp.int32)

    hist = _hist_tiles(
        Xt, Wt, tile_leaf, tile_first, tile_skip,
        num_cols=1, total_bins=B, num_features=F, axis_name=axis_name,
        platform=platform,
    )[0]
    if axis_name is not None:
        from dryad_tpu.engine.distributed import reduce_hist

        hist = reduce_hist(hist, axis_name)
    return hist


def tile_plan(sel: jnp.ndarray, N: int, P: int, T: int,
              rows_bound: int | None = None):
    """Bucket rows by leaf into fixed tiles.

    Returns (buf, tile_leaf, tile_first): ``buf`` (n_tiles*T,) row ids with
    sentinel N for padding slots; ``tile_leaf`` monotone leaf per tile
    (every leaf owns >= 1 tile); ``tile_first`` marks each leaf's first
    tile.  Deterministic: stable sort by leaf, fixed slot order.

    ``rows_bound`` caps the total selected rows when the caller can prove a
    tighter bound than N — the level-wise grower histograms only smaller
    children, which cover at most half the rows, halving the static tile
    count (and the kernel's grid).  Rows beyond the bound would be silently
    dropped, so only pass a mathematically guaranteed bound.
    """
    bound = N if rows_bound is None else min(int(rows_bound), N)
    n_tiles = bound // T + P + 1
    sel = sel.astype(jnp.int32)
    if N <= (1 << 24) and P < 256:
        # pack (slot, row) into ONE uint32 word (slot<<24 | row) and sort the
        # single array — the two-operand argsort + the sel[order] re-gather
        # measured ~1.8x slower at 10M.  Stability is by construction (row id
        # in the low bits); the resulting plan is value-identical to the
        # argsort formulation, so every downstream program is unchanged.
        key = ((sel.astype(jnp.uint32) << jnp.uint32(24))
               | jnp.arange(N, dtype=jnp.uint32))
        srt = jnp.sort(key)
        sel_sorted = (srt >> jnp.uint32(24)).astype(jnp.int32)
        order = (srt & jnp.uint32(0xFFFFFF)).astype(jnp.int32)
    else:
        order = jnp.argsort(sel, stable=True).astype(jnp.int32)
        sel_sorted = sel[order]
    start = jnp.searchsorted(sel_sorted, jnp.arange(P + 1, dtype=jnp.int32),
                             side="left").astype(jnp.int32)
    counts = start[1:] - start[:-1]                       # (P,)
    # every leaf gets >= 1 tile so its (pallas) output block is initialized
    leaf_tiles = jnp.maximum((counts + (T - 1)) // T, 1)
    seg_base = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(leaf_tiles).astype(jnp.int32)])
    # Safety squeeze: if the caller's rows_bound was violated, raw bases can
    # exceed the grid.  Clamp so leaf i starts no later than n_tiles-(P-i) —
    # every leaf keeps >= 1 in-range tile (outputs stay initialized) and
    # rows beyond a leaf's allotment drop deterministically instead of
    # corrupting a neighbour's tiles.
    seg_base = jnp.minimum(
        seg_base, jnp.int32(n_tiles) - (P - jnp.arange(P + 1, dtype=jnp.int32)))
    cap_rows = (seg_base[1:] - seg_base[:-1]) * T         # (P,)

    tile_leaf = jnp.searchsorted(seg_base[1:], jnp.arange(n_tiles, dtype=jnp.int32),
                                 side="right").astype(jnp.int32)
    # Fill tile slots by GATHERING from the sorted order (TPU scatters
    # serialize — the old (N,)-scatter construction cost ~250 ms at 10M
    # rows; this gather formulation is the same plan ~4x cheaper): slot j
    # of tile t holds the (j + t*T - seg_base[leaf]*T)-th row of leaf's
    # contiguous run in `order`, sentinel N when past the leaf's count/cap.
    # All plan lookups happen per TILE (n_tiles ≈ N/T entries) and broadcast
    # across the T slot positions — only the final order[src] gather touches
    # an (N,)-sized table.  tile_leaf == P marks trailing pad tiles.
    tile_idx = jnp.arange(n_tiles, dtype=jnp.int32)
    lc = jnp.minimum(tile_leaf, P - 1)                     # (n_tiles,)
    base_t = tile_idx * T - seg_base[lc] * T               # first slot's in-leaf offset
    cnt_t = jnp.minimum(counts[lc], cap_rows[lc])
    start_t = start[lc]
    j = jnp.arange(T, dtype=jnp.int32)
    off = base_t[:, None] + j[None, :]                     # (n_tiles, T)
    ok = (tile_leaf < P)[:, None] & (off >= 0) & (off < cnt_t[:, None])
    src = start_t[:, None] + off
    buf = jnp.where(ok, order[jnp.clip(src, 0, N - 1)], N).reshape(-1)
    tile_leaf = jnp.minimum(tile_leaf, P - 1)             # clamp trailing pad tiles
    tile_first = jnp.concatenate([
        jnp.ones((1,), jnp.int32),
        (tile_leaf[1:] != tile_leaf[:-1]).astype(jnp.int32),
    ])
    return buf, tile_leaf, tile_first


def tile_plan_aligned(sel: jnp.ndarray, counts: jnp.ndarray, N: int, P: int,
                      T: int, rows_bound: int | None = None):
    """``tile_plan`` when the caller KNOWS each slot's exact row count.

    The level-synchronous growers do: a slot's count is the chosen split's
    smaller-child count (CL/CR off the parent histogram — exact integers in
    f32 below 2**24).  Injecting ``(-count) % T`` pad keys per slot into
    the packed sort makes every slot's run tile-aligned IN the sorted array
    itself, so ``buf`` is a plain slice — the 5M-access ``order[src]``
    alignment gather of the generic plan (~55 ms/level at 10M) disappears.

    The produced (buf, tile_leaf, tile_first) is VALUE-IDENTICAL to
    ``tile_plan``'s (same stable row order per slot, same sentinel
    placement, same static shapes), so every downstream program is
    unchanged — tests pin the equality.

    Admissibility (callers gate): N <= 2**24 - 1 (the row field stores
    row ids < N plus the sentinel N itself — pad keys reuse the sentinel,
    never values past it), P <= 254 (slot 0xFF marks inert injected
    keys), and ``counts`` must be exact —
    a wrong count silently misaligns the plan (the generic path's safety
    squeeze has nothing to squeeze here), which is why only growers that
    read counts off their own histograms may pass them.
    """
    bound = N if rows_bound is None else min(int(rows_bound), N)
    n_tiles = bound // T + P + 1                   # same grid as tile_plan
    sel = sel.astype(jnp.int32)
    cnt = counts.astype(jnp.int32)                 # (P,) exact
    lt = jnp.maximum((cnt + (T - 1)) // T, 1)      # aligned tiles per slot
    seg_base = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(lt).astype(jnp.int32)])

    key_real = ((sel.astype(jnp.uint32) << jnp.uint32(24))
                | jnp.arange(N, dtype=jnp.uint32))
    # slot p needs lt[p]*T - cnt[p] <= T pad keys (row field N = sentinel);
    # unused injected keys get slot 0xFF and sort past everything live
    pad_needed = lt * T - cnt                      # (P,) in [0, T]
    padj = jnp.arange(T, dtype=jnp.int32)[None, :]
    slot_col = jnp.arange(P, dtype=jnp.uint32)[:, None]
    key_pad = jnp.where(
        padj < pad_needed[:, None],
        (slot_col << jnp.uint32(24)) | jnp.uint32(N),
        jnp.uint32(0xFF) << jnp.uint32(24))
    # one extra inert tile: n_tiles*T can exceed N + P*T by up to T
    key_tail = jnp.full((T,), jnp.uint32(0xFF) << jnp.uint32(24), jnp.uint32)
    srt = jnp.sort(jnp.concatenate([key_real, key_pad.reshape(-1), key_tail]))
    srt = srt[: n_tiles * T]
    slot_s = (srt >> jnp.uint32(24)).astype(jnp.int32)
    row_s = (srt & jnp.uint32(0xFFFFFF)).astype(jnp.int32)
    buf = jnp.where(slot_s < P, row_s, N)          # pads carry row N already

    tile_idx = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_leaf = jnp.searchsorted(seg_base[1:], tile_idx,
                                 side="right").astype(jnp.int32)
    tile_leaf = jnp.minimum(tile_leaf, P - 1)
    tile_first = jnp.concatenate([
        jnp.ones((1,), jnp.int32),
        (tile_leaf[1:] != tile_leaf[:-1]).astype(jnp.int32),
    ])
    return buf, tile_leaf, tile_first


@jax.named_scope("dryad.hist")
def make_records(Xb: jnp.ndarray, g: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """Per-TREE (N, 2 + ceil(F*bytes/4)) int32 record table [g, h, X words].

    g/h are constant across a tree's levels, so interleaving them with the
    bin row once per tree lets every level pay ONE row gather instead of a
    separate X gather + g/h gather (the per-access overhead of 10M-row
    random gathers dominated the per-level cost; halving the access count
    measured ~1.7x on the whole level).  X bytes are bitcast back out by
    hist_from_plan; uint16 bins ride as 2-byte units of the same words.
    """
    N, F = Xb.shape
    nbytes = Xb.dtype.itemsize * F
    fw = -(-nbytes // 4)                     # ceil: rows pad up to whole words
    Xu8 = jax.lax.bitcast_convert_type(
        Xb, jnp.uint8).reshape(N, nbytes) if Xb.dtype != jnp.uint8 else Xb
    Xu8 = jnp.pad(Xu8, ((0, 0), (0, fw * 4 - nbytes)))
    Xw = jax.lax.bitcast_convert_type(
        Xu8.reshape(N, fw, 4), jnp.int32).reshape(N, fw)
    gw = jax.lax.bitcast_convert_type(g.astype(jnp.float32), jnp.int32)
    hw = jax.lax.bitcast_convert_type(h.astype(jnp.float32), jnp.int32)
    return jnp.concatenate([gw[:, None], hw[:, None], Xw], axis=1)


def hist_from_plan(
    Xb: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    buf: jnp.ndarray,
    tile_leaf: jnp.ndarray,
    tile_first: jnp.ndarray,
    num_cols: int,
    total_bins: int,
    *,
    axis_name: str | None = None,
    platform: str | None = None,
    records: jnp.ndarray | None = None,
    stage_gather: bool = True,
    hist_reduce: str = "fused",
) -> jnp.ndarray:
    """Histogram leaf-grouped rows given a precomputed tile plan.

    Padding slots (sentinel N in ``buf``) clamp to row N-1 and ride with
    zero weight — their one-hot columns hit real bins but multiply zero, so
    the sums are unchanged (this replaces the old sentinel-row concatenate,
    which re-materialized the whole (N, F) matrix every level).

    ``records`` (make_records) collapses the X and g/h gathers into one.
    CONTRACT: it must have been built from the SAME (Xb, g, h) passed here —
    on the records path the g/h arguments are ignored (values come from the
    table) and Xb contributes only shape/dtype; a stale table silently
    yields histograms of the old gradients.
    """
    N, F = Xb.shape
    B = int(total_bins)
    T = _TILE_ROWS
    n_tiles = buf.shape[0] // T
    valid = (buf < N).reshape(n_tiles, T)
    live = jnp.any(valid, axis=1)                   # (n_tiles,)
    safe = jnp.minimum(buf, N - 1)

    if records is not None:
        # STAGED gather: the plan's static shape covers the worst-case N/2
        # smaller-children bound, but live tiles always form a PREFIX (both
        # plans pack leaf segments at the front; everything after the last
        # live tile is sentinel), and gather cost is per-ACCESS (CLAUDE.md)
        # — so when the actual selection is small, gathering a quarter- or
        # half-prefix and zero-padding the rest halves-to-quarters the
        # dominant per-level HBM cost.  lax.cond picks the smallest prefix
        # covering the live tiles at runtime; zero rows carry zero weights
        # and bin 0, contributing nothing (same sentinel algebra as pads).
        # Single-device only: under shard_map the predicate would vary by
        # shard (vma) and every shard must run one program.  Callers pass
        # stage_gather=False when the leaf budget fills every level (a
        # full tree keeps the prefix at ~100% and the cond's three gather
        # kernels only bloat compile — Epsilon-width programs measured
        # minutes of extra remote compile for zero runtime win).
        if stage_gather and axis_name is None and n_tiles >= 8:
            n_pref = jnp.max(jnp.where(
                live, jnp.arange(1, n_tiles + 1, dtype=jnp.int32), 0))

            def stage(nt):
                def go(b):
                    sf = jnp.minimum(b[: nt * T], N - 1)
                    r = records[sf]
                    return jnp.pad(r, ((0, (n_tiles - nt) * T), (0, 0)))
                return go

            q1, q2 = n_tiles // 4, n_tiles // 2
            rec = jax.lax.cond(
                n_pref <= q1,
                stage(q1),
                lambda b: jax.lax.cond(n_pref <= q2, stage(q2),
                                       stage(n_tiles), b),
                buf)
        else:
            rec = records[safe]                     # ONE (n_rows, 2+fw) gather
        gh = jax.lax.bitcast_convert_type(rec[:, :2], jnp.float32)
        gt = gh[:, 0].reshape(n_tiles, T)
        ht = gh[:, 1].reshape(n_tiles, T)
        fw = rec.shape[1] - 2
        nbytes = Xb.dtype.itemsize * F
        Xr = jax.lax.bitcast_convert_type(
            rec[:, 2:], jnp.uint8).reshape(n_tiles * T, fw * 4)[:, :nbytes]
        if Xb.dtype != jnp.uint8:
            Xr = jax.lax.bitcast_convert_type(
                Xr.reshape(n_tiles * T, F, Xb.dtype.itemsize), Xb.dtype)
        X_rows = Xr.reshape(n_tiles * T, F)
    else:
        # gather in the narrow storage dtype (the kernel casts): the (N, F)
        # u8 gather moves 4x fewer bytes than an i32 one
        X_rows = Xb[safe]
        ght = jnp.stack([g.astype(jnp.float32),
                         h.astype(jnp.float32)], axis=1)[safe]
        gt, ht = ght[:, 0].reshape(n_tiles, T), ght[:, 1].reshape(n_tiles, T)

    Xt = _tiles_from_rows(X_rows, n_tiles, T, B)
    Wt = _pack_weights(gt, ht, valid)

    hist = _hist_tiles(
        Xt, Wt, tile_leaf, tile_first, 1 - live.astype(jnp.int32),
        num_cols=int(num_cols), total_bins=B, num_features=F,
        axis_name=axis_name, platform=platform,
    )
    if axis_name is not None:
        from dryad_tpu.engine.distributed import reduce_hist

        hist = reduce_hist(hist, axis_name, hist_reduce)
    return hist


def build_hist_segmented_pallas(
    Xb: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    sel: jnp.ndarray,
    num_cols: int,
    total_bins: int,
    *,
    axis_name: str | None = None,
    rows_bound: int | None = None,
    platform: str | None = None,
    records: jnp.ndarray | None = None,
    sel_counts: jnp.ndarray | None = None,
    stage_gather: bool = True,
    hist_reduce: str = "fused",
) -> jnp.ndarray:
    """Per-leaf histograms for a whole tree level -> (P, 3, F, B) f32.

    ``sel`` (N,) in [0, P]; P drops the row.  O(N·F·B) MXU work independent
    of leaf count — the TPU analog of the CUDA kernel's atomic scatter-add
    asymptotics.  ``records`` (make_records, computed once per tree) fuses
    the level's X and g/h gathers into one.  ``sel_counts`` (P,) — the
    exact per-slot row counts, when the caller reads them off its own
    histograms — switches to the pad-injected aligned sort
    (tile_plan_aligned), dropping the plan's alignment gather.
    """
    N = Xb.shape[0]
    P = int(num_cols)
    if sel_counts is not None and N <= (1 << 24) - 1 and P <= 254:
        buf, tile_leaf, tile_first = tile_plan_aligned(
            sel, sel_counts, N, P, _TILE_ROWS, rows_bound=rows_bound)
    else:
        buf, tile_leaf, tile_first = tile_plan(sel, N, P, _TILE_ROWS,
                                               rows_bound=rows_bound)
    return hist_from_plan(
        Xb, g, h, buf, tile_leaf, tile_first, num_cols, total_bins,
        axis_name=axis_name, platform=platform, records=records,
        stage_gather=stage_gather, hist_reduce=hist_reduce,
    )

# ---------------------------------------------------------------------------
# natural-order multi-slot pass (shallow levels: <= 16 slots)
# ---------------------------------------------------------------------------
_NAT_SLOTS = 16
_NAT_DROP = 31        # sel sentinel (any value >= _NAT_SLOTS drops the row)
# global-matrix gate for the natural-order pass, MB (see maybe_natural_tiles)
_NAT_GATE_MB = int(os.environ.get("DRYAD_NAT_MB", "512"))


def nat_gate_admits(num_rows: int, num_features: int, itemsize: int,
                    n_shards: int = 1) -> bool:
    """The ONE natural-order gate predicate (GLOBAL padded matrix bytes vs
    ``_NAT_GATE_MB``) — shared by maybe_natural_tiles and
    train._comm_stats so the observability accounting can never drift from
    the grower's actual program choice (ADVICE r4)."""
    return (num_rows * n_shards * num_features * itemsize
            <= (_NAT_GATE_MB << 20))


def maybe_natural_tiles(Xb: jnp.ndarray, total_bins: int,
                        axis_name: str | None = None):
    """natural_tiles when the GLOBAL matrix is small enough, else None.

    The gate must see the global size: under shard_map Xb is the local
    shard, and gating per-shard would let 1-shard and N-shard runs of the
    same data take different histogram programs (near-tie argmaxes could
    flip — the CLAUDE.md same-program rule).  psum of a constant folds to
    axis_size at trace time, so the check stays static.

    Gate history: r3 measured the nat pass REGRESSING the chunked 10M
    marginal 2x (buffer pressure in the then-program) and gated it at
    128 MB; after the r4 pipeline cuts (aligned plan, staged gather,
    skip-empty tiles, device-cached X) the same measurement shows it
    WINNING (2.78 -> 2.55 s/iter at 10M), so the default gate is now
    512 MB — wide enough for Higgs-10M's 280 MB, still excluding
    Epsilon-shaped 800 MB matrices: r5 finally measured that shape
    (exp_r5_eps.py: nat 347 vs plan 368 ms per 16-slot level — a ~6%
    win worth ~1% of an Epsilon iteration) and KEEPS the exclusion; the
    small win does not justify doubling peak bin-matrix residency.
    ``DRYAD_NAT_MB`` overrides for measurement — read ONCE at import (a
    per-call read would be silently ignored whenever the jit cache already
    holds a program for these shapes: the env var is not part of the key).
    """
    n_shards = int(jax.lax.psum(1, axis_name)) if axis_name else 1
    N, F = Xb.shape
    if not nat_gate_admits(N, F, Xb.dtype.itemsize, n_shards):
        return None
    return natural_tiles(Xb, total_bins)


@jax.named_scope("dryad.hist")
def build_hist_small(nat_tiles, g, h, sel, num_cols: int, total_bins: int,
                     num_features: int, *, axis_name: str | None = None,
                     platform: str | None = None,
                     hist_reduce: str = "fused") -> jnp.ndarray:
    """(P, 3, F, B) via the natural-order pass: owns the drop-sentinel
    mapping (callers use sel == P for "drop") and the slot-budget check.

    ``num_cols`` is forwarded so the allreduce inside covers only the P live
    slots — psumming the full 16-slot kernel output shipped 2x the needed
    bytes at P=8 (ADVICE r3 #2); with the slice before the psum, the nat
    pass's collective payload equals the plan path's (P, 3, F, B), keeping
    ``train._comm_stats`` exact for both."""
    P = int(num_cols)
    assert P <= _NAT_SLOTS, "natural-order pass holds at most 16 slots"
    sel_nat = jnp.where(sel >= P, _NAT_DROP, sel)
    return build_hist_nat(nat_tiles, g, h, sel_nat,
                          total_bins=int(total_bins),
                          num_features=int(num_features),
                          num_cols=P,
                          axis_name=axis_name, platform=platform,
                          hist_reduce=hist_reduce)


@jax.named_scope("dryad.hist")
def natural_tiles(Xb: jnp.ndarray, total_bins: int) -> jnp.ndarray:
    """Feature-chunked tiles of the WHOLE matrix in natural row order — a
    pure function of (Xb, bins), so the level-synchronous growers build it
    once per tree and every shallow level reuses it (no sort, no gather)."""
    N = Xb.shape[0]
    T = _TILE_ROWS
    pad = (-N) % T
    Xp = jnp.pad(Xb, ((0, pad), (0, 0)))
    return _tiles_from_rows(Xp, (N + pad) // T, T, total_bins)


def _nat_kernel(x_ref, w_ref, o_ref, *, padded_bins: int):
    """All (<=16) slots' histograms in ONE natural-order pass: slot s owns
    weight rows 8s..8s+6 of the 128-row MXU tile (16 x 8 = 128 exactly);
    row 8s+7 is dead (it carries the slot-id lane used for the row mask).
    No tile plan: the per-row slot id rides as ROW 7 of the 8-row weight
    block (slot values <= 31 are exact in bf16), and a shifted row-iota
    mask zeroes every weight row whose slot does not match the lane's."""
    i = pl.program_id(1)
    x = x_ref[0, 0].astype(jnp.int32)              # (Fc, T)
    Fc, T = x.shape
    Bp = padded_bins
    shift = Fc.bit_length() - 1
    x_rep = pltpu.repeat(x, Bp, axis=0)       # tiled whole copies
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (Fc * Bp, T), 0) >> shift
    onehot = (x_rep == iota_b).astype(jnp.bfloat16)

    limbs = w_ref[0]                               # (8, T): 7 limbs + sel row
    sel = limbs[7:8, :].astype(jnp.int32)
    w = pltpu.repeat(limbs, _NAT_SLOTS, axis=0)    # (128,T) r=limbs[r%8]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (_NAT_SLOTS * 8, T), 0)
    keep = ((row_iota >> 3) == sel) & ((row_iota & 7) != 7)
    w = jnp.where(keep, w, jnp.bfloat16(0))
    part = jax.lax.dot_general(
        w, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (128, Fc*Bp)

    @pl.when(i == 0)
    def _():
        o_ref[0] = part

    @pl.when(i != 0)
    def _():
        o_ref[0] = o_ref[0] + part


@functools.partial(jax.jit, static_argnames=("total_bins", "num_features",
                                             "num_cols", "axis_name",
                                             "platform", "hist_reduce"))
def build_hist_nat(Xt_nat, g, h, sel, *, total_bins: int, num_features: int,
                   num_cols: int = _NAT_SLOTS,
                   axis_name: str | None = None,
                   platform: str | None = None,
                   hist_reduce: str = "fused") -> jnp.ndarray:
    """(num_cols, 3, F, B) histograms from natural-order tiles; ``sel`` (N,)
    in [0, 16); values >= 16 drop the row.  Replaces the plan+gather
    pipeline for levels with few candidates — measured 154 vs 281 ms at
    10M, P=8 (the tile plan's full-N sort and the row gather dominate
    there).  The kernel always produces all 16 slots (its 128-row MXU tile
    is fixed); ``num_cols`` slices BEFORE the psum so sharded callers
    allreduce only live slots (ADVICE r3 #2)."""
    B = int(total_bins)
    F = int(num_features)
    Bp = _pow2_bins(B)
    n_fb, n_tiles, Fc, T = Xt_nat.shape
    N = g.shape[0]
    pad = n_tiles * T - N
    gp = jnp.pad(g.astype(jnp.float32), (0, pad))
    hp = jnp.pad(h.astype(jnp.float32), (0, pad))
    sp = jnp.pad(sel.astype(jnp.int32), (0, pad),
                 constant_values=_NAT_DROP)
    sp = jnp.minimum(sp, _NAT_DROP)
    valid = (sp < _NAT_SLOTS).astype(jnp.float32)
    gv = (gp * valid).reshape(n_tiles, T)
    hv = (hp * valid).reshape(n_tiles, T)
    cnt = valid.astype(jnp.bfloat16).reshape(n_tiles, T)
    selr = sp.astype(jnp.bfloat16).reshape(n_tiles, T)
    W = jnp.stack([*_split3(gv), *_split3(hv), cnt, selr], axis=-2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(n_fb, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, Fc, T), lambda j, i: (j, i, 0, 0)),
            pl.BlockSpec((1, 8, T), lambda j, i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _NAT_SLOTS * 8, Fc * Bp),
                               lambda j, i: (j, 0, 0)),
    )
    out_shape = jax.ShapeDtypeStruct(
        (n_fb, _NAT_SLOTS * 8, Fc * Bp), jnp.float32,
        vma=None if axis_name is None else frozenset({axis_name}))
    out = pl.pallas_call(
        functools.partial(_nat_kernel, padded_bins=Bp),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=_interpret(platform),
        name="build_hist_nat",
    )(Xt_nat, W)
    out = (out.reshape(n_fb, _NAT_SLOTS, 8, Bp, Fc)
              .transpose(1, 2, 0, 4, 3)
              .reshape(_NAT_SLOTS, 8, n_fb * Fc, Bp))[:, :, :F, :B]
    out = out[:num_cols]
    hg = out[:, 0] + out[:, 1] + out[:, 2]
    hh = out[:, 3] + out[:, 4] + out[:, 5]
    hc = out[:, 6]
    hist = jnp.stack([hg, hh, hc], axis=1)         # (num_cols, 3, F, B)
    if axis_name is not None:
        from dryad_tpu.engine.distributed import reduce_hist

        hist = reduce_hist(hist, axis_name, hist_reduce)
    return hist
