"""Leaf-ordered permutation kernel (VERDICT r4 #2 / CLAUDE.md open item
#1): maintain the per-tree record table GROUPED BY LEAF incrementally,
deleting the two dominant deep-level data-movement costs at 10M rows —
the full-N packed sort (~75 ms/level) and the half-N per-access record
gather (~110 ms/level).

Layout invariant.  Records live in a TILE-ALIGNED leaf-ordered buffer:
segment k (one leaf slot) owns ``lt[k] = max(ceil(cnt[k]/T), 1)``
consecutive row tiles; rows past cnt[k] in its range are ZERO (sentinel)
rows — zero weight, bin 0, contributing nothing to any histogram (the
same sentinel algebra pallas_hist's plans use).

Per level, every segment splits into (left, right) children (pass-through
segments keep all rows "left").  A row's destination is a pure function
of (source tile, side, stable rank within that (tile, side)), so the
movement decomposes into per-tile work with NO sort and NO row scatter —
and, since every row of a tile belongs to one run and so to one split,
with nothing row-sized left in XLA either (``move_level``, PR 26):

* **per-tile parameters**: the growers' packed per-run split record
  (feature, threshold bin, default-left, categorical flag, splits?) is
  gathered once per TILE into one SMEM word (and, with categorical
  features, the run's bitset row per tile);
* **a counting pass** (kernel ``permute_records_count``) reads every record
  tile once and returns its left/right row counts — destinations are
  prefixes over EARLIER tiles, so the counts must exist before any row
  is placed;
* **``level_moves``** turns the counts into destination offsets and segment
  bases with tile-sized prefix work (arrays of n_tiles and of P entries);
* **the move kernel** (``permute_records``) derives, for the tile it holds
  in VMEM, each row's side (a thin selector product brings the valid
  flag and the split feature's bin out lane-oriented; then the routing
  rules of the growers' natural-order partition, integer for integer)
  and its stable in-tile rank (side rows times a strict upper-triangular
  ones matrix), then does the
* **stable two-way compaction on the MXU**: records are uint8 lanes
  (bytes are exact in bf16; the 0/1 one-hot times byte products
  accumulate exactly in f32), ``P_side (T, T) @ rec (T, WB)`` compacts
  one side's rows to the front in stable order and zero-fills the tail —
  and zeros ARE the sentinel encoding;
* **two fixed-size windowed writes per tile** at row offsets
  ``dst_side[i] = T·new_base(child) + (side-rows of earlier tiles of the
  same segment)``.

Write-ordering safety.  The new layout places ALL left children (in
source-segment order), one slack tile, ALL right children (same order),
one slack tile.  Pallas grid steps execute sequentially, and within a
region each write begins exactly where the previous real rows ended, so
a write's zero tail is either overwritten by a LATER step of the same
region, lands in the segment's own pad slots, or falls into slack —
never on rows written earlier.  (An interleaved [L_k][R_k] layout breaks
this: an L tail can cross into R territory that earlier steps already
wrote — found in design review, hence the region split.)

The histogram pass then reads the selected children's segments as
CONTIGUOUS tile runs where they lie: the histogram kernel takes this
buffer itself, one 64 KB record tile a grid step, addressed by a
prefetched tile index (``hist_from_layout``, PR 31 — no gather, no
relayout and no weight rows are staged for it), and no per-level sort
exists at all.

Bitwise-tested in interpret mode against the numpy oracle
(tests/test_leafperm.py: ``permute_records_np`` with sides from
``layout_sides_np``); ``scripts/exp_r5_perm.py`` measures the level move
on-device against the sort+gather pair it replaces.  WIRED into
``levelwise.py``'s deep phase in r6 and EVERYWHERE in r10: both
level-synchronous growers (``levelwise.py`` — shallow AND deep levels —
and the batched leaf-wise expansion in ``leafwise_fast.py``) carry
(rec, tile_run, run_slot) through their level fori state and call the one
entry point ``move_level``.  The layout is anchored at the ROOT
(``natural_root_layout``: the natural-order record buffer IS a valid
one-segment layout, out-of-bag rows encoded as flag-0 records that level
0's move drops), so the old shallow->deep handoff sort+gather per tree
(``initial_layout``) is gone from the growers too — it remains as the
probe/oracle constructor for mid-tree layouts (bench, tests).
``scripts/smoke_tpu.py --gate`` pins the fused move against the oracle and
wired-vs-legacy tree equality on device for both growers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE_ROWS = 512     # must match pallas_hist._TILE_ROWS (shared layouts)
# Destination-row granule: Mosaic can only slice an HBM uint8 memref at
# sublane-tile multiples ("failed to prove divisible by the tiling" for
# arbitrary offsets — measured on v5e), so every windowed write starts on
# a 32-row boundary.  Each source tile's per-side contribution therefore
# OCCUPIES roundup32(rows) slots; the ≤31-row gaps are zero sentinels.
# Overhead ≤ 2*32/512 = 12.5% extra rows per level, non-compounding (the
# next level's compaction drops sentinels and re-pads afresh).
_ALIGN = 32


def _interpret(platform: str | None = None) -> bool:
    return (platform or jax.default_backend()) == "cpu"


def aligned_layout(counts: jnp.ndarray, T: int = _TILE_ROWS):
    """(lt, base): per-segment tile counts (>= 1 each) and first-tile
    indices for exact row ``counts``; ``base[-1]`` = total tiles."""
    cnt = counts.astype(jnp.int32)
    lt = jnp.maximum((cnt + (T - 1)) // T, 1)
    base = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(lt).astype(jnp.int32)])
    return lt, base


# Per-tile split word (int32) the layout kernels read from SMEM — the top
# half of the growers' packed per-slot word plus the split feature:
#   bits 0-12 threshold bin | 13 categorical | 14 default-left |
#   15 the run splits this level | 16-22 feature
# (a record holds at most (_REC_WB - 9) = 119 feature bytes: 7 bits)
_PAR_THR_MASK = 0x1FFF
_PAR_CAT_BIT, _PAR_DLEFT_BIT, _PAR_DO_BIT, _PAR_FEAT_SHIFT = 13, 14, 15, 16
_CNT_LANES = 128     # tiles per block of the counting pass's output
_CNT_STEP = 8        # tiles per grid step of the counting pass
assert _CNT_LANES % _CNT_STEP == 0
_RANK_LANES = 128    # lane block of the in-tile prefix sum


def _tile_bf16(tile_ref):
    # Mosaic has no direct u8->bf16 cast; route through i32/f32 (byte
    # values <= 255 are exact at every step)
    return (tile_ref[...].astype(jnp.int32).astype(jnp.float32)
            .astype(jnp.bfloat16))                     # (T, WB)


def _tile_sides(w, rec, cat_ref, *, T: int, WB: int, itemsize: int,
                learn_missing: bool, n_cat_bins: int):
    """One record tile's sides from its run's split word ``w`` (scalar).

    rec (T, WB) bf16.  An (8, WB) one-hot selector contracted with the
    tile on WB brings the valid flag (byte 8) and the split feature's bin
    (byte ``9 + f*itemsize``, two bytes for u16 bins) out LANE-oriented as
    (8, T): a byte is exact in bf16 and one product per sum is non-zero.
    The routing is ``packed_route``'s integer arithmetic (the growers'
    natural-order partition), so the two agree on every row.  Returns
    (8, T) int32: row 0 = goes left, row 1 = goes right, rest zero;
    flag-0 rows (sentinels, out-of-bag) are on neither side."""
    row = jax.lax.broadcasted_iota(jnp.int32, (8, WB), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, WB), 1)
    b0 = _REC_X + ((w >> _PAR_FEAT_SHIFT) & 0x7F) * itemsize
    tgt = jnp.where(row == 0, _REC_FLAG, jnp.where(row == 1, b0, -1))
    if itemsize == 2:
        tgt = jnp.where(row == 2, b0 + 1, tgt)
    sel = (lane == tgt).astype(jnp.bfloat16)
    vb = jax.lax.dot_general(
        sel, rec, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)   # (8, T)
    valid = vb[0:1] == 1
    bins = vb[1:2]
    if itemsize == 2:
        bins = bins + vb[2:3] * 256                    # little-endian u16
    wv = jnp.full((1, T), w, jnp.int32)
    gl = bins <= (wv & _PAR_THR_MASK)
    if learn_missing:
        gl &= (((wv >> _PAR_DLEFT_BIT) & 1) == 1) | (bins > 0)
    if cat_ref is not None:
        # the run's bitset row, looked up per row by a one-hot product
        Bcp = cat_ref.shape[-1]
        oh = (jax.lax.broadcasted_iota(jnp.int32, (Bcp, T), 0)
              == jnp.minimum(bins, n_cat_bins - 1)).astype(jnp.bfloat16)
        mask = jnp.broadcast_to(cat_ref[...], (8, Bcp)).astype(jnp.bfloat16)
        cat_row = jax.lax.dot_general(
            mask, oh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[0:1] > 0.5
        # (a select between two i1 vectors does not lower in Mosaic)
        is_cat = ((wv >> _PAR_CAT_BIT) & 1) == 1
        gl = (is_cat & cat_row) | (~is_cat & gl)
    # a run that does not split (pass-through, or a dead run's zero word)
    # sends every valid row left
    right = valid & (((wv >> _PAR_DO_BIT) & 1) == 1) & ~gl
    left = valid & ~right
    r8 = jax.lax.broadcasted_iota(jnp.int32, (8, T), 0)
    return jnp.where(r8 == 0, left.astype(jnp.int32),
                     jnp.where(r8 == 1, right.astype(jnp.int32), 0))


def _count_kernel(par_ref, *refs, has_cat: bool, n_tiles: int, **kw):
    """Counting pass: ``_CNT_STEP`` tiles a grid step (the pass is a thin
    product per 64 KB tile — one tile a step would be all step overhead);
    tile t's (left, right) row counts go to lane ``t % 128`` of a
    resident (8, 128) output block (row 0 / row 1)."""
    cat_ref, rec_ref, out_ref = refs if has_cat else (None,) + refs
    t0 = pl.program_id(0) * _CNT_STEP
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, _CNT_LANES), 1)
    out = out_ref[0]
    for k in range(_CNT_STEP):
        # a ragged last step re-reads the last tile's word over whatever
        # the block holds past the buffer; its lanes are sliced off
        w = par_ref[jnp.minimum(t0 + k, n_tiles - 1)]
        S = _tile_sides(w, _tile_bf16(rec_ref.at[k]),
                        None if cat_ref is None else cat_ref.at[k], **kw)
        cnt = jnp.sum(S.astype(jnp.float32), axis=1, keepdims=True)
        out = jnp.where(lane == (t0 + k) % _CNT_LANES,
                        cnt.astype(jnp.int32), out)
    out_ref[0] = out


def _perm_kernel(dstl_ref, dstr_ref, par_ref, *refs, has_cat: bool,
                 T: int, WB: int, **kw):
    """One source tile: sides, stable in-tile ranks, two stable
    compactions + two windowed writes.

    The ranks are an exclusive prefix sum of the side rows along the
    tile: per 128-lane block ``side (8, 128) @ strict-upper-triangular
    ones (128, 128)`` plus the earlier blocks' totals (exact in f32 up to
    T; one MXU weight tile, where a (T, T) triangle would load sixteen).
    A row off a side gets rank T = "no row", so each one-hot
    ``iota_o == rank`` compacts one side to the front and zero-fills the
    rest."""
    (tri_ref, cat_ref, rec_ref, init_ref, out_ref, outl_vmem, outr_vmem,
     seml, semr) = refs if has_cat else refs[:1] + (None,) + refs[1:]
    i = pl.program_id(0)
    rec = _tile_bf16(rec_ref.at[0])
    S = _tile_sides(par_ref[i], rec,
                    None if cat_ref is None else cat_ref.at[0],
                    T=T, WB=WB, **kw)
    Sf = S.astype(jnp.float32)
    tri = tri_ref[...]
    carry = jnp.zeros((8, 1), jnp.float32)
    parts = []
    for k in range(T // _RANK_LANES):
        blk = Sf[:, k * _RANK_LANES:(k + 1) * _RANK_LANES]
        parts.append(carry + jax.lax.dot_general(
            blk.astype(jnp.bfloat16), tri, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        carry = carry + jnp.sum(blk, axis=1, keepdims=True)
    rk = jnp.concatenate(parts, axis=1).astype(jnp.int32)
    pos = jnp.where(S == 1, rk, T)                     # (8, T)
    iota_o = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    PL = (iota_o == pos[0:1]).astype(jnp.bfloat16)
    PR = (iota_o == pos[1:2]).astype(jnp.bfloat16)
    outl_vmem[...] = jax.lax.dot_general(
        PL, rec, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32).astype(
            jnp.uint8).reshape(T // _ALIGN, _ALIGN, WB)
    outr_vmem[...] = jax.lax.dot_general(
        PR, rec, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32).astype(
            jnp.uint8).reshape(T // _ALIGN, _ALIGN, WB)
    # the out ref is viewed in _ALIGN-row GRANULES (g, _ALIGN, WB) and the
    # dst scalars arrive pre-divided by _ALIGN: Mosaic cannot PROVE a raw
    # runtime row offset divisible by its tiling, but a leading-granule
    # index is divisible by construction
    cl = pltpu.make_async_copy(
        outl_vmem, out_ref.at[pl.ds(dstl_ref[i], T // _ALIGN)], seml)
    cr = pltpu.make_async_copy(
        outr_vmem, out_ref.at[pl.ds(dstr_ref[i], T // _ALIGN)], semr)
    cl.start()
    cr.start()
    # waits keep the writes ordered with the NEXT step's (they overlap a
    # predecessor's zero tail by design) and the scratch reusable
    cl.wait()
    cr.wait()


@jax.named_scope("dryad.layout")
@functools.partial(jax.jit, static_argnames=(
    "bin_dtype", "learn_missing", "n_out_tiles", "platform", "axis_name"))
def move_level(lay_rec: jnp.ndarray, lay_tile_run: jnp.ndarray,
               run_rec: jnp.ndarray, run_catmask: jnp.ndarray | None = None,
               *, bin_dtype, learn_missing: bool = False,
               n_out_tiles: int | None = None, platform: str | None = None,
               axis_name: str | None = None):
    """One level's movement of the leaf-ordered layout — THE entry point
    both level-synchronous growers (and the probes) call.

    lay_rec (n_tiles*T, _REC_WB-wide) uint8 layout records; lay_tile_run
    (n_tiles,) int32 ascending tile -> run map; run_rec (P, 2) uint32 the
    growers' packed split record of each run (word 0: bit 31 splits, 30
    default-left, 29 categorical, 16-28 threshold bin; word 1: feature; a
    zero row = pass-through); run_catmask (P, Bc) bool each run's
    categorical left-membership row, or None without categorical
    features.  ``n_out_tiles`` (default: the input's tile count, the
    stationary wired buffer) MUST include the two slack tiles
    ``level_moves`` accounts for.

    Every row of a tile belongs to one run, so one split: the per-run
    records are gathered per TILE (never per row), a counting kernel
    reads each tile's left/right row counts, ``level_moves`` turns them
    into destinations with tile-sized prefix work, and the move kernel
    derives sides and in-tile ranks itself from the tile it already
    holds.  Returns (lay_rec_new, base_l, base_r) — the
    (n_out_tiles*T, WB) buffer in [left children | slack | right children
    | slack] order and ``level_moves``' (P+1,) first-tile indices.

    ``axis_name`` marks the outputs device-varying when tracing under
    ``shard_map`` (each shard moves its own local layout; no collective
    here — the histogram psum stays the growers' only one).

    The output is ALIASED to a zero buffer: rows no DMA write covers
    (inner pad rows of multi-tile segments with uneven source fill,
    untouched slack) must be zero sentinels — an uninitialized ANY-space
    buffer holds stale HBM bytes on real hardware (interpret mode
    zero-fills and masks this; caught in review)."""
    n_rows, WB = lay_rec.shape
    T = _TILE_ROWS
    if n_rows % T:
        # a ragged tail would be dropped, and on hardware the rows past
        # it are stale HBM, not the interpreter's zeros
        raise ValueError(f"move_level: {n_rows} rows is not a "
                         f"multiple of the {T}-row tile")
    n_tiles = n_rows // T
    assert lay_tile_run.shape == (n_tiles,), (lay_tile_run.shape, n_tiles)
    n_out_tiles = n_tiles if n_out_tiles is None else int(n_out_tiles)
    P = run_rec.shape[0]
    has_cat = run_catmask is not None
    vma = None if axis_name is None else frozenset({axis_name})

    def varying(x):
        # constants entering a shard-local kernel must carry the same
        # varying-manual-axes as the per-shard operands beside them
        return x if axis_name is None else jax.lax.pcast(
            x, axis_name, to="varying")

    # ---- per-TILE parameters: (P,)-level compose, (n_tiles,) gathers ------
    run_par = ((run_rec[:, 0] >> 16)
               | (run_rec[:, 1] << _PAR_FEAT_SHIFT)).astype(jnp.int32)
    par = run_par[lay_tile_run]
    rec3 = lay_rec.reshape(n_tiles, T, WB)
    cat_ops, n_cat_bins = [], 1
    if has_cat:
        n_cat_bins = run_catmask.shape[1]
        Bcp = -(-n_cat_bins // 128) * 128
        cat_ops = [jnp.pad(run_catmask.astype(jnp.float32),
                           ((0, 0), (0, Bcp - n_cat_bins))
                           )[lay_tile_run][:, None, :]]
    kw = dict(has_cat=has_cat, T=T, WB=WB,
              itemsize=jnp.dtype(bin_dtype).itemsize,
              learn_missing=bool(learn_missing), n_cat_bins=n_cat_bins)

    def tile_specs(k):
        # [the runs' bitset rows,] the record tiles: k tiles a grid step
        return ([pl.BlockSpec((k, 1, cat_ops[0].shape[-1]),
                              lambda i, *_: (i, 0, 0))] if has_cat else []
                ) + [pl.BlockSpec((k, T, WB), lambda i, *_: (i, 0, 0))]

    # ---- counting pass: destinations are prefixes over EARLIER tiles ------
    nb = -(-n_tiles // _CNT_LANES)
    cnt = pl.pallas_call(
        functools.partial(_count_kernel, n_tiles=n_tiles, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(-(-n_tiles // _CNT_STEP),),
            in_specs=tile_specs(_CNT_STEP),
            out_specs=pl.BlockSpec(
                (1, 8, _CNT_LANES),
                lambda i, *_: (i * _CNT_STEP // _CNT_LANES, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((nb, 8, _CNT_LANES), jnp.int32,
                                       vma=vma),
        interpret=_interpret(platform),
        name="permute_records_count",
    )(par, *cat_ops, rec3)
    counts = cnt[:, :2].transpose(0, 2, 1).reshape(nb * _CNT_LANES, 2)
    dstl, dstr, base_l, base_r, _ = level_moves(
        lay_tile_run, counts[:n_tiles], P)

    # ---- the move ------------------------------------------------------------
    # memory-safety clamp (tile_plan's "safety squeeze" precedent): a
    # violated caller bound must misplace rows DETERMINISTICALLY inside
    # the buffer, never DMA past it (granule writes cover T rows from dst)
    dst_cap = jnp.int32((n_out_tiles - 1) * T)
    dstl = jnp.minimum(dstl, dst_cap)
    dstr = jnp.minimum(dstr, dst_cap)
    r = jnp.arange(_RANK_LANES)
    tri = varying((r[:, None] < r[None, :]).astype(jnp.bfloat16))
    G = n_out_tiles * T // _ALIGN
    zeros = varying(jnp.zeros((G, _ALIGN, WB), jnp.uint8))
    n_in = 3 + 1 + len(cat_ops) + 1      # prefetched scalars, tri, cat, rec
    out = pl.pallas_call(
        functools.partial(_perm_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_tiles,),
            in_specs=[pl.BlockSpec((_RANK_LANES, _RANK_LANES),
                                   lambda i, *_: (0, 0))]
            + tile_specs(1)
            + [pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            scratch_shapes=[
                pltpu.VMEM((T // _ALIGN, _ALIGN, WB), jnp.uint8),
                pltpu.VMEM((T // _ALIGN, _ALIGN, WB), jnp.uint8),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ]),
        out_shape=jax.ShapeDtypeStruct((G, _ALIGN, WB), jnp.uint8, vma=vma),
        # the zero buffer is the last operand: alias it to the output
        input_output_aliases={n_in: 0},
        interpret=_interpret(platform),
        name="permute_records",
    )(dstl // _ALIGN, dstr // _ALIGN, par, tri, *cat_ops, rec3, zeros)
    return out.reshape(n_out_tiles * T, WB), base_l, base_r


@jax.named_scope("dryad.layout")
def level_moves(tile_slot: jnp.ndarray, counts: jnp.ndarray,
                n_parents: int, T: int = _TILE_ROWS):
    """XLA bookkeeping for one level — O(n_tiles) prefix work, no sort,
    nothing row-sized.

    tile_slot (n_tiles,) int32: source segment per tile (layout
    invariant).  counts (n_tiles, 2) int32: each tile's rows going to the
    left / right child (the counting pass; sentinel rows in neither).
    ``n_parents`` (static): parent segment count P; pass-through parents
    route all rows left — their right segment still gets the mandatory
    1-tile allocation but receives only zeros.

    Returns (dstl, dstr, base_l, base_r, n_out_tiles): the new
    layout is [left children in parent order | slack | right children |
    slack]; ``dstl``/``dstr`` are each tile's destination ROW offsets,
    ``base_l``/``base_r`` the (P+1,) FIRST-TILE indices of each
    parent's left/right child segment (right already offset past the
    left region), from which callers derive the next level's tile→segment
    map.  Within a segment, each source tile's contribution sits at an
    _ALIGN-rounded offset (interior runs of < _ALIGN zero sentinels — see
    the _ALIGN note), so real rows are NOT a contiguous prefix.
    ``n_out_tiles`` is a traced scalar — callers pick the static bound
    (see tiles_bound)."""
    A = _ALIGN
    # each tile's contribution OCCUPIES an _ALIGN-rounded slot run so its
    # write start stays Mosaic-sliceable (see _ALIGN note)
    nl_t = -(-counts[:, 0] // A) * A
    nr_t = -(-counts[:, 1] // A) * A
    cl = jnp.cumsum(nl_t) - nl_t                       # global tile prefixes
    cr = jnp.cumsum(nr_t) - nr_t
    first = jnp.concatenate([jnp.ones((1,), bool),
                             tile_slot[1:] != tile_slot[:-1]])
    # per-tile prefix WITHIN its segment = global prefix minus the
    # segment's first tile's global prefix (max-scan trick: cl is
    # non-decreasing, so carrying the last first-tile value is a max scan)
    segl = jax.lax.associative_scan(jnp.maximum, jnp.where(first, cl, -1))
    segr = jax.lax.associative_scan(jnp.maximum, jnp.where(first, cr, -1))
    prefl = cl - segl
    prefr = cr - segr

    # segment capacities cover the PADDED contributions (per-segment sum
    # of rounded per-tile sizes = last prefix + last size)
    last = jnp.concatenate([tile_slot[1:] != tile_slot[:-1],
                            jnp.ones((1,), bool)])
    lastl = jnp.where(last, prefl + nl_t, -1)
    lastr = jnp.where(last, prefr + nr_t, -1)
    P = int(n_parents)
    pad_l = jnp.zeros((P,), jnp.int32).at[tile_slot].max(lastl)
    pad_r = jnp.zeros((P,), jnp.int32).at[tile_slot].max(lastr)
    lt_l, base_l = aligned_layout(pad_l, T)            # left region
    lt_r, base_r = aligned_layout(pad_r, T)            # right region
    left_tiles = base_l[-1]
    # region layout: [left | 1 slack | right | 1 slack]
    off_r = left_tiles + 1
    dstl = (base_l[tile_slot] * T + prefl).astype(jnp.int32)
    dstr = ((off_r + base_r[tile_slot]) * T + prefr).astype(jnp.int32)
    n_out_tiles = off_r + base_r[-1] + 1
    return dstl, dstr, base_l, base_r + off_r, n_out_tiles


def tiles_bound(n_rows: int, n_parents: int, T: int = _TILE_ROWS) -> int:
    """Static bound for ``n_out_tiles``: every row lands somewhere, each
    source tile adds up to 2·(_ALIGN-1) interior pad rows (alignment
    rounding per side), plus per-segment tile-alignment waste, mandatory
    empty-segment tiles and the two slack tiles.  The padding does NOT
    compound across levels (pads drop at the next compaction): the tile
    count converges to ≲ rows/T · 1/(1 − 2·_ALIGN/T) ≈ 1.14x."""
    n_src_tiles = n_rows // T
    pad_rows = 2 * _ALIGN * n_src_tiles
    return (n_rows + pad_rows) // T + 2 * n_parents + 4


# ---------------------------------------------------------------------------
# layout records + histograms straight from the layout
# ---------------------------------------------------------------------------
# Layout record byte format (WB = 128):
#   [ g f32 (4) | h f32 (4) | valid u8 (1) | X bins u8/u16 (F·itemsize) ]
# padded with zeros to WB.  The valid flag distinguishes real rows from
# sentinels without assuming anything about g/h values; zero rows decode
# to valid=0, g=h=0, bin 0 — inert in every consumer by construction.
_REC_WB = 128
_REC_G, _REC_H, _REC_FLAG, _REC_X = 0, 4, 8, 9     # byte offsets


@jax.named_scope("dryad.layout")
def make_layout_records(Xb: jnp.ndarray, g: jnp.ndarray, h: jnp.ndarray,
                        valid: jnp.ndarray | None = None) -> jnp.ndarray:
    """(N, _REC_WB) uint8 layout records in natural row order — the
    root-segment initial layout (pad to tile multiples before use).

    ``valid`` (N,) bool marks rows that participate (the bag mask for a
    root-anchored layout): rows outside it get valid flag 0 and are
    DROPPED by the first level's move (the kernels put flag-0 rows on
    neither side), so out-of-bag rows never ride a permute past level 0."""
    N, F = Xb.shape
    nbytes = F * Xb.dtype.itemsize
    assert _REC_X + nbytes <= _REC_WB, "feature bytes exceed the record"
    gb = jax.lax.bitcast_convert_type(
        g.astype(jnp.float32), jnp.uint8).reshape(N, 4)
    hb = jax.lax.bitcast_convert_type(
        h.astype(jnp.float32), jnp.uint8).reshape(N, 4)
    xb = (jax.lax.bitcast_convert_type(Xb, jnp.uint8).reshape(N, nbytes)
          if Xb.dtype != jnp.uint8 else Xb)
    flag = (jnp.ones((N, 1), jnp.uint8) if valid is None
            else valid.astype(jnp.uint8).reshape(N, 1))
    rec = jnp.concatenate([gb, hb, flag, xb], axis=1)
    return jnp.pad(rec, ((0, 0), (0, _REC_WB - rec.shape[1])))


@jax.named_scope("dryad.hist")
def hist_from_layout(rec: jnp.ndarray, seg_first: jnp.ndarray,
                     seg_ntiles: jnp.ndarray, num_cols: int,
                     total_bins: int, num_features: int, bin_dtype,
                     n_sel_tiles: int, *,
                     axis_name: str | None = None,
                     platform: str | None = None,
                     hist_reduce: str = "fused") -> jnp.ndarray:
    """(P, 3, F, B) histograms for P selected segments of a leaf-ordered
    layout — NO sort, NO gather, nothing row-sized staged: each segment is
    a CONTIGUOUS tile run, so the histogram kernel reads the record tiles
    where they lie (``pallas_hist._hist_tiles_rec``: the tile of each plan
    slot is a prefetched scalar in the block's ``index_map``; bins, g, h
    and the valid flag are unpacked from the 64 KB tile in VMEM).  What
    XLA does here is the tile-sized plan: which tile each of the
    ``n_sel_tiles`` slots reads, its column, and whether it is read at all.

    seg_first/seg_ntiles (P,) int32: each selected segment's first tile
    and tile count in ``rec``.  ``n_sel_tiles`` MUST bound
    ``sum(max(seg_ntiles, 1))`` — every selection reserves at least one
    plan slot (an empty selection's mandatory slot zero-initializes its
    output block, tile_plan contract), so a bound on the raw tile sum
    alone would shift later segments past the end and silently truncate
    their histograms (caught in review; test-pinned).

    Parity note (test_hist_from_layout_bitwise_vs_plan, and
    test_hist_from_layout_in_place_vs_plan for the shapes no cell runs):
    on a PAD-FREE layout (contiguous per-segment rows — the per-tree
    initial layout) this is BITWISE equal to the tile-plan path, whose
    staged kernel shares the step's body.  Post-permute layouts
    carry _ALIGN interior sentinels that shift rows across tile
    boundaries, regrouping the kernel's per-tile partial sums — an
    ulp-class difference (the chunked-vs-dispatch tolerance class in
    CLAUDE.md), so a wired grower must use ONE histogram path per config,
    never mix them mid-tree."""
    from dryad_tpu.engine import pallas_hist

    T = _TILE_ROWS
    P = int(num_cols)
    if rec.shape[0] % T:
        raise ValueError(f"hist_from_layout: {rec.shape[0]} rows is not a "
                         f"multiple of the {T}-row tile")
    n_tiles_in = rec.shape[0] // T
    # dense plan: positions of each segment's tiles in the packed prefix
    base = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(jnp.maximum(seg_ntiles, 1))
                            .astype(jnp.int32)])
    idx = jnp.arange(n_sel_tiles, dtype=jnp.int32)
    tile_leaf = jnp.searchsorted(base[1:], idx, side="right").astype(
        jnp.int32)
    lc = jnp.minimum(tile_leaf, P - 1)
    off = idx - base[lc]
    live = (tile_leaf < P) & (off < seg_ntiles[lc])
    # a slot past its segment's tiles (an empty selection's mandatory slot,
    # the plan's tail) is skipped from this tile-sized data; a live tile
    # that holds only sentinels is the kernel's own branch
    src = jnp.where(live, seg_first[lc] + off, 0)
    src = jnp.clip(src, 0, n_tiles_in - 1)
    tile_first = jnp.concatenate([
        jnp.ones((1,), jnp.int32),
        (lc[1:] != lc[:-1]).astype(jnp.int32)])
    hist = pallas_hist._hist_tiles_rec(
        rec, src, lc, tile_first, 1 - live.astype(jnp.int32), num_cols=P,
        total_bins=int(total_bins), num_features=int(num_features),
        bin_dtype=jnp.dtype(bin_dtype), axis_name=axis_name,
        platform=platform)
    if axis_name is not None:
        # the same per-arm histogram reduction every builder tail issues:
        # the fused grad/hess/count psum (default) or the feature-arm
        # reduce-scatter (distributed.reduce_hist)
        from dryad_tpu.engine.distributed import reduce_hist

        hist = reduce_hist(hist, axis_name, hist_reduce)
    return hist


# ---------------------------------------------------------------------------
# levelwise deep-phase wiring (slot-keyed run bookkeeping)
# ---------------------------------------------------------------------------
# The wired grower (levelwise.py deep phase) carries the layout through its
# level fori state as (rec, tile_run, run_slot):
#
# * ``tile_run`` (n_buf_tiles,) int32 — per-tile RUN index, ascending in
#   layout order (the write-ordering safety of the move kernel requires
#   destination order == source processing order, which holds exactly when
#   run ids ascend with tile position — the oracle's implicit invariant).
# * ``run_slot`` (L,) int32 — run index -> grower leaf-slot id (sentinel L
#   for unused run indices).  Runs <-> live leaf slots stay bijective:
#   every level keeps all left/pass-through segments as their old runs
#   (left children keep the parent's slot — the levelwise convention) and
#   appends one new run per executed split (the right child's slot), so
#   the run count is 1 + total splits <= L and the (L,)-dense bookkeeping
#   never overflows.  Empty segments level_moves mandates (non-splitting
#   parents' right segments, unused run indices) are ABSORBED into the
#   preceding run: their tiles hold only zero sentinels, which contribute
#   nothing to any move or histogram (the oracle's slack-absorption rule).


def wired_tiles_bound(n_row_tiles: int, num_slots: int) -> int:
    """Static FIXED-POINT tile bound for the carried layout buffer.

    One level maps an n_buf-tile layout holding <= n_row_tiles*T real rows
    to <= (rows + 2*_ALIGN*n_buf)/T + 2*L + 2 tiles (each source tile adds
    < _ALIGN pad per side; every one of the L dense run indices gets a
    mandatory tile per region plus the two slack tiles).  Solving
    out <= n_buf for the stationary buffer gives n_buf >= 8/7 * (rows/T +
    2L + 2) at _ALIGN/T = 1/16 — pads do NOT compound (the next level's
    compaction drops them), so the same buffer carries every level."""
    base = n_row_tiles + 2 * num_slots + 2
    assert 2 * _ALIGN * 8 <= _TILE_ROWS, "fixed point needs 2A/T <= 1/8"
    return -(-8 * base // 7) + 2


def wired_sel_tiles_bound(n_row_tiles: int, n_buf_tiles: int,
                          num_cols: int, half: bool) -> int:
    """Static bound on ``hist_from_layout``'s ``n_sel_tiles`` for a
    smaller-children selection out of a ``n_buf_tiles`` layout — the ONE
    definition shared by the wired grower and the bench probe (an
    insufficient bound silently truncates later segments' histograms, so
    the two callers must never drift).  ``half=True`` when the caller can
    PROVE the selection covers at most half the real rows (single device
    below 2^24 rows, where the fp32 counts backing the smaller-child
    choice are exact); the n_buf/16 term covers the _ALIGN interior
    sentinels, 2*num_cols the per-segment ceil and the empty selections'
    mandatory plan slots."""
    if half:
        return n_row_tiles // 2 + n_buf_tiles // 16 + 2 * num_cols + 8
    return n_buf_tiles + 2 * num_cols


@jax.named_scope("dryad.layout")
def natural_root_layout(rec_nat: jnp.ndarray, num_runs: int,
                        n_buf_tiles: int, first_slot: int = 0,
                        sentinel: int | None = None,
                        axis_name: str | None = None):
    """Root-anchored layout (r10): the natural-order record buffer IS a
    valid layout with ONE segment — run 0 owns every tile, rows the
    caller marked invalid (``make_layout_records``' ``valid`` arg, i.e.
    out-of-bag) are dropped by level 0's move.  NO sort, NO gather: this
    replaces the shallow->deep ``initial_layout`` handoff entirely when
    the layout is live from level 0.

    Returns (rec_lay, tile_run, run_slot): records padded to
    ``n_buf_tiles`` tiles, all tiles in run 0, and a (num_runs,) dense
    run->slot table holding ``first_slot`` at run 0 and ``sentinel``
    (default ``num_runs``) elsewhere.  Under ``shard_map`` pass
    ``axis_name`` so the carried bookkeeping state enters the level loop
    device-varying like the outputs that replace it (same vma rule as
    move_level's aliased zero init)."""
    N = rec_nat.shape[0]
    T = _TILE_ROWS
    assert N <= n_buf_tiles * T, (N, n_buf_tiles)
    rec_lay = jnp.pad(rec_nat, ((0, n_buf_tiles * T - N), (0, 0)))
    sent = num_runs if sentinel is None else sentinel
    tile_run = jnp.zeros((n_buf_tiles,), jnp.int32)
    run_slot = jnp.full((num_runs,), sent, jnp.int32).at[0].set(first_slot)
    if axis_name is not None:
        tile_run = jax.lax.pcast(tile_run, axis_name, to="varying")
        run_slot = jax.lax.pcast(run_slot, axis_name, to="varying")
    return rec_lay, tile_run, run_slot


@jax.named_scope("dryad.layout")
def initial_layout(rec_nat: jnp.ndarray, sel: jnp.ndarray,
                   live: jnp.ndarray, num_slots: int, n_buf_tiles: int):
    """Mid-tree layout constructor: group natural-order layout records by
    leaf slot into the tile-aligned leaf-ordered layout.  Was the r6
    growers' shallow->deep handoff; since the r10 root anchoring
    (``natural_root_layout``) the growers never call it — it remains the
    bench probe's and the oracle tests' way to build a layout at an
    arbitrary tree depth (one ``tile_plan`` stable sort + one full-N
    record gather — exactly the pair the wired growers no longer pay).

    ``sel`` (N,) int32 in [0, L]; L drops the row (out-of-bag rows never
    enter the layout — their records would only ride dead weight through
    every level's move).  ``live`` (L,) bool marks slots that exist at the
    handoff depth; dead slots' mandatory plan tiles are absorbed into the
    preceding run.  Returns (rec_lay, tile_run, run_slot).

    Per-slot row order is the plan paths' STABLE row-id order (tile_plan's
    stable sort), and move_level preserves source order within
    (segment, side) — so every later level's per-slot order matches what
    tile_plan_aligned would produce for the same selection, by
    construction (the integration contract test_leafperm pins)."""
    from dryad_tpu.engine.pallas_hist import tile_plan

    N = rec_nat.shape[0]
    L = int(num_slots)
    T = _TILE_ROWS
    buf, tile_leaf, _ = tile_plan(sel, N, L, T)
    nh = buf.shape[0] // T
    assert nh <= n_buf_tiles, (nh, n_buf_tiles)
    rec_lay = jnp.where((buf < N)[:, None],
                        rec_nat[jnp.minimum(buf, N - 1)], jnp.uint8(0))
    rec_lay = jnp.pad(rec_lay, ((0, (n_buf_tiles - nh) * T), (0, 0)))
    livec = jnp.cumsum(live.astype(jnp.int32))
    tl_full = jnp.concatenate([
        tile_leaf, jnp.full((n_buf_tiles - nh,), L - 1, jnp.int32)])
    tile_run = jnp.maximum(livec[tl_full] - 1, 0).astype(jnp.int32)
    run_slot = jnp.full((L,), L, jnp.int32).at[
        jnp.where(live, livec - 1, L)].set(
            jnp.arange(L, dtype=jnp.int32), mode="drop")
    return rec_lay, tile_run, run_slot


@jax.named_scope("dryad.layout")
def advance_runs(run_slot: jnp.ndarray, run_do: jnp.ndarray,
                 run_right: jnp.ndarray, base_l: jnp.ndarray,
                 base_r: jnp.ndarray, n_buf_tiles: int,
                 sentinel: int | None = None):
    """Next level's (tile_run, run_slot) after ``level_moves``.

    ``run_do`` (L,) marks runs whose slot split this level; ``run_right``
    their right child's slot id.  Kept segments: every left segment of a
    live run (new run index = OLD index — left children keep the parent's
    slot) and the right segment of each splitting run (new runs R..R+S-1
    in run order).  Marking each kept segment's first tile and counting
    marks per tile yields the ascending tile->run map; everything between
    kept starts (empty mandatory segments, slack, the trailing buffer) is
    absorbed into the preceding run.

    ``sentinel`` is the "unused run" slot value (default: the run
    capacity L, the levelwise convention where slot ids < L).  The
    batched leaf-wise grower stores heap NODE ids (which exceed its run
    capacity) and passes sentinel = HN; when a kept run's slot id must
    CHANGE across the level (leaf-wise: the left child's node is 2n, not
    n), pre-apply that update to ``run_slot`` before calling — this
    helper only reads liveness from it and writes the appended right
    runs."""
    L = run_slot.shape[0]
    sent = L if sentinel is None else sentinel
    R = jnp.sum((run_slot < sent).astype(jnp.int32))
    ridx = jnp.arange(L, dtype=jnp.int32)
    marks = jnp.zeros((n_buf_tiles,), jnp.int32)
    marks = marks.at[jnp.where(ridx < R, base_l[:L], n_buf_tiles)].add(
        1, mode="drop")
    marks = marks.at[jnp.where(run_do, base_r[:L], n_buf_tiles)].add(
        1, mode="drop")
    tile_run = jnp.maximum(jnp.cumsum(marks) - 1, 0).astype(jnp.int32)
    rank = jnp.cumsum(run_do.astype(jnp.int32)) - run_do.astype(jnp.int32)
    run_slot = run_slot.at[jnp.where(run_do, R + rank, L)].set(
        run_right.astype(jnp.int32), mode="drop")
    return tile_run, run_slot


# ---------------------------------------------------------------------------
# numpy reference (the bitwise oracle for tests)
# ---------------------------------------------------------------------------

def pack_run_records(do, feature, thresh, dleft=None, is_cat=None):
    """(P, 2) uint32 per-run split records in the growers' packed format
    (``move_level``'s ``run_rec``) from per-run arrays — for the probes,
    scripts and tests that drive the move without a grower (traceable:
    a probe's thresholds may ride its carried scalar)."""
    def u32(x):
        return jnp.zeros_like(w0) if x is None else jnp.asarray(x).astype(
            jnp.uint32)

    w0 = jnp.asarray(do).astype(jnp.uint32) << 31
    w0 = w0 | (u32(dleft) << 30) | (u32(is_cat) << 29) | (u32(thresh) << 16)
    return jnp.stack([w0, u32(feature)], axis=1)


def layout_sides_np(rec: np.ndarray, tile_run: np.ndarray,
                    run_rec: np.ndarray, run_catmask=None, *,
                    bin_dtype=np.uint8, learn_missing: bool = False,
                    T: int = _TILE_ROWS) -> np.ndarray:
    """Reference side of every layout row (0 left, 1 right, 2 neither)
    from its tile's run record — ``packed_route``'s rules in numpy."""
    itemsize = np.dtype(bin_dtype).itemsize
    n = rec.shape[0]
    rr = np.asarray(run_rec)[np.repeat(np.asarray(tile_run), T)]
    w0, f = rr[:, 0], rr[:, 1].astype(np.int64)
    b0 = 9 + f * itemsize
    rows = np.arange(n)
    bins = rec[rows, b0].astype(np.int64)
    if itemsize == 2:
        bins += rec[rows, b0 + 1].astype(np.int64) << 8
    gl = bins <= ((w0 >> 16) & 0x1FFF)
    if learn_missing:
        gl &= ((w0 >> 30) & 1).astype(bool) | (bins > 0)
    if run_catmask is not None:
        cm = np.asarray(run_catmask)
        cat_row = cm[np.repeat(np.asarray(tile_run), T),
                     np.minimum(bins, cm.shape[1] - 1)]
        gl = np.where(((w0 >> 29) & 1).astype(bool), cat_row, gl)
    right = ((w0 >> 31) != 0) & ~gl
    return np.where(rec[:, 8] == 1, right.astype(np.int32), 2).astype(
        np.int32)


def permute_records_np(rec: np.ndarray, tile_slot: np.ndarray,
                       side: np.ndarray, n_parents: int, n_out_tiles: int,
                       T: int = _TILE_ROWS):
    """Reference: stable per-(segment, side) order into the
    [left | slack | right | slack] layout with _ALIGN-rounded per-tile
    contributions — mirrors level_moves exactly.

    Returns (out, tile_slot_new, row_seg_new): the permuted buffer plus
    the NEXT level's tile→segment map and per-row segment ids (−1 for
    sentinels), segments numbered [left children 0..P−1, then right
    children P..2P−1] in parent order."""
    A = _ALIGN
    n_tiles = tile_slot.shape[0]
    WB = rec.shape[1]
    P = n_parents
    # padded per-segment capacities (sum of rounded per-tile sizes)
    pad_l = np.zeros(P, np.int64)
    pad_r = np.zeros(P, np.int64)
    for i in range(n_tiles):
        s = tile_slot[i]
        sd = side[i * T:(i + 1) * T]
        pad_l[s] += -(-int((sd == 0).sum()) // A) * A
        pad_r[s] += -(-int((sd == 1).sum()) // A) * A
    lt_l = np.maximum(-(-pad_l // T), 1)
    lt_r = np.maximum(-(-pad_r // T), 1)
    base_l = np.concatenate([[0], np.cumsum(lt_l)]).astype(np.int64)
    off_r = base_l[-1] + 1
    base_r = off_r + np.concatenate([[0], np.cumsum(lt_r)]).astype(np.int64)
    out = np.zeros((n_out_tiles * T, WB), np.uint8)
    row_seg = np.full(n_out_tiles * T, -1, np.int64)
    tile_slot_new = np.full(n_out_tiles, -1, np.int64)
    for s in range(P):
        tile_slot_new[base_l[s]: base_l[s + 1]] = s
        tile_slot_new[base_r[s]: base_r[s + 1]] = P + s
    # slack (and trailing bound) tiles hold only sentinels: absorb them
    # into the PRECEDING segment so tile→segment stays a sequence of
    # consecutive runs (level_moves' prefix bookkeeping requires it); an
    # extra all-sentinel tile contributes a rounded-zero size — harmless
    for i in range(n_out_tiles):
        if tile_slot_new[i] < 0:
            tile_slot_new[i] = tile_slot_new[i - 1] if i else 0
    fill_l = np.zeros(P, np.int64)
    fill_r = np.zeros(P, np.int64)
    for i in range(n_tiles):
        s = tile_slot[i]
        nl = nr = 0
        for j in range(T):
            sd = side[i * T + j]
            if sd == 0:
                pos = base_l[s] * T + fill_l[s] + nl
                out[pos] = rec[i * T + j]
                row_seg[pos] = s
                nl += 1
            elif sd == 1:
                pos = base_r[s] * T + fill_r[s] + nr
                out[pos] = rec[i * T + j]
                row_seg[pos] = P + s
                nr += 1
        fill_l[s] += -(-nl // A) * A
        fill_r[s] += -(-nr // A) * A
    return out, tile_slot_new, row_seg
