"""Batched leaf-wise growth: depth-capped full expansion + exact best-first
selection (SURVEY.md §2 #8 at scale).

The sequential leaf-wise grower (grower.py::grow_tree — the reference's
one-split-at-a-time control flow) pays one full-N masked histogram pass per
split: O(N·L) work per tree, ~L/depth times the depthwise cost at 255
leaves (VERDICT r2 missing #2).  This module removes that asymptotic
penalty using an exact equivalence:

    Split gains are ORDER-INDEPENDENT.  Splitting leaf A never changes
    leaf B's rows, histogram, or gain — so the sequential best-first
    procedure is a deterministic selection over a gain tree whose values
    do not depend on the order in which it is explored.

Therefore leaf-wise growth with a depth cap D factorizes into:

1. **Expansion** — grow ALL valid splits level-synchronously to depth D
   (the depthwise machinery: one segmented smaller-children histogram
   pass per level, subtraction for the larger sibling), recording every
   node's best split, gain, stats and monotone bounds into a binary-heap
   table (node 1 = root, children 2n / 2n+1).  Cost: O(N·D) — the same
   per-level passes the depthwise grower pays.
2. **Selection** — replay the exact slot-machine sequence of
   grow_tree on the PRECOMPUTED gains: L-1 trips of argmax over slot
   gains (first-max tie-break, left child keeps the parent slot, right
   child takes slot k+1, node ids in execution order).  O(L²) scalar
   work, microseconds.

The selected tree is identical to the sequential grower's, node ids and
all, whenever both compute identical gains (they histogram with different
programs, so near-tie fp flips fall under the documented CPU↔TPU
tolerance class).  The equivalence needs a finite depth cap: an unbounded
tree cannot be pre-expanded, so ``grow_any`` routes here only for
``0 < max_depth`` (set, or unbounded_depth=auto's cap) inside the peak-
residency envelope (config.py: 11 x pinned + rows x per_row <= 12 GiB).

Distribution contract matches levelwise.py: call under ``shard_map`` with
rows sharded; the fused psum inside the histogram builders is the only
collective; the selection runs replicated-identically on every shard.

Layout-wired expansion (r10): when ``leafwise_layout_supported`` admits
the config, the expansion fori carries the leaf-ordered record layout
(engine/leafperm.py) exactly as levelwise does — anchored at the root
(the natural-order record buffer, out-of-bag rows as sentinels), sides
derived from the layout records via the same packed-word arithmetic as
the natural-order partition, rows moved by the stable per-tile MXU
compaction, smaller children histogrammed as contiguous tile runs.  The
run bookkeeping stores heap NODE ids (``run_slot`` -> node): a split
keeps the parent's run for the LEFT child (node 2n) and appends a run
for the right (2n+1), so runs still ascend with tile position and
``leafperm.advance_runs`` applies with sentinel HN.  The per-expansion-
level sort + full-N record gather are gone from this path; the
expansion≡sequential equivalence and the psum-only collective contract
are untouched (test_leafwise_fast / test_leafperm_sharded).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from dryad_tpu.config import Params
from dryad_tpu.engine.grower import (
    _monotone_array,
    child_bounds,
    finalize_leaf_values,
    pack_cat_bitset,
    root_stats,
)
from dryad_tpu.engine import levelwise
from dryad_tpu.engine.histogram import build_hist, build_hist_segmented
from dryad_tpu.engine.split import NEG_INF, find_best_split
from dryad_tpu.policy.gates import gate_value

from dryad_tpu.config import (  # noqa: F401  (re-exported API)
    MAX_FAST_DEPTH as _MAX_FAST_DEPTH,
    effective_depth_params,
    leafwise_fast_supported,
)


def supports(p: Params, num_features: int, total_bins: int,
             num_rows: int | None = None, n_shards: int = 1) -> bool:
    """Fast leaf-wise needs a finite, memory-feasible expansion depth.

    One rule, by peak residency ON ONE DEVICE: LEAFWISE_PEAK_FACTOR x the PINNED
    (Pf, 3, F, B) float32 buffer (hist_small/large/l/r, the 2P-wide children
    concat for the split finder, the kernels' padded output) plus what the
    rows stage, within LEAFWISE_TOTAL_BYTES_BUDGET; config.py has the
    constants and the compiler's numbers they envelop.  Configs beyond keep
    the sequential grower.  (The shape logic lives jax-free in config's
    ``leafwise_fast_supported`` so the CPU backend's max_depth=-1 policy,
    config.effective_depth_params, can consult it without touching jax; a
    config that disables hist_subtraction is rejected there too, because the
    expansion derives every larger sibling by subtraction.)  ``num_rows``
    is the GLOBAL row count and ``n_shards`` the mesh's size: the rows
    counted are a shard's, ``ceil(num_rows / n_shards)``, never the local
    shape a traced shard sees (see config.leafwise_fast_supported)."""
    return leafwise_fast_supported(p, num_features, total_bins, num_rows,
                                   n_shards)


def phase_plan(depth_cap: int):
    """(d_switch, P_narrow, P_full) for the two-phase expansion loop — the
    ONE definition of the leafwise phase boundary, shared with
    train._comm_stats so the observability accounting mirrors the grower's
    actual per-level candidate widths (ADVICE r4 / r5 review)."""
    P_full = 1 << max(depth_cap - 1, 0)
    P_narrow = min(8, P_full)
    d_switch = 4 if (depth_cap > 4 and P_full > 8) else depth_cap
    return d_switch, P_narrow, P_full


# Run-capacity cap for the layout-wired expansion: the deepest move can
# produce one segment per level-D heap node, so the dense run bookkeeping
# is (2^D,)-wide and level_moves mandates >= 2*2^D + 2 tiles per level
# (one per run index per region) — the same structural cost class as
# levelwise's 512-leaf bound (2L+2 tiles).  The verdict, re-derived from
# chip numbers in PR 29 (v5e, 10M x 28, 255 leaves, max_depth=-1 = cap
# 12; it stopped at 1024 before, argued from ~164 ms/level recoverable):
#   * recoverable: the plan path's level costs 296-305 ms at 10M (sort
#     21, three row-index gathers 193, the staged record gather 82),
#     and build_hist_nat reads all rows at each of levels 0-3 (127 ms);
#   * mandated: 2^D = 4096 is 8194 tiles of 64 KB a level, 11 ms a
#     level in the move kernel (1.35 us a tile), and the layout buffer
#     is wired_tiles_bound(19532, 4096) = 31,689 tiles, 2.08 GB;
#   * measured: an iteration of the benchmark's leaf-wise cell
#     4.78 -> 2.99 s of device time, the chunk program's
#     temporaries 6.30 -> 7.78 GB.
# So 4096 segments ride the layout.  Caps 13 and 14 (_MAX_FAST_DEPTH)
# would carry 2.7 and 3.9 GB buffers and no cell measures them: they keep
# the legacy plan path (a written verdict, not a TODO).  The gate cannot
# consult N (same-program rule), so a small table at cap 11 or 12 pays
# the mandated tiles whatever it holds: on 300k rows x 28 at cap 12,
# 474 ms a tree against 233 for the plan path, both on the host's clock
# (scripts/smoke_tpu.py --gate, v5e, PR 29).  The number is assigned
# once, in policy/gates.py THRESHOLDS ("leafwise_layout"/"max_segments");
# this name reads it for the tests and carries the verdict.
_MAX_WIRED_SEGMENTS = gate_value("leafwise_layout", "max_segments")


def leafwise_layout_supported(p: Params, num_features: int, total_bins: int,
                              bin_itemsize: int,
                              platform: str | None = None) -> bool:
    """Static gate for the layout-wired batched leaf-wise expansion.

    Rides levelwise's ``deep_layout_supported`` (one gate surface: same
    record-width / bin / packed-word / backend exclusions and the
    ``deep_layout="legacy"`` opt-out; its num_leaves <= 512 bound is
    conservative here — leaf-wise runs are capped by expansion width,
    not the leaf budget, but a second knob would just invite drift) plus
    the expansion-width cap above.  Row-count free, like everything that
    picks a histogram program (CLAUDE.md same-program rule)."""
    from dryad_tpu.engine.levelwise import deep_layout_supported

    if not deep_layout_supported(p, num_features, total_bins, bin_itemsize,
                                 platform):
        return False
    # the expansion derives larger siblings by subtraction (supports()
    # rejects non-subtraction configs before this gate is consulted)
    if not p.hist_subtraction:
        return False
    from dryad_tpu.policy.gates import resolve

    return resolve("leafwise_layout",
                   {"max_depth": p.max_depth}) == "layout"


def grow_tree_leafwise_batched(
    params: Params,
    total_bins: int,
    Xb: jnp.ndarray,
    g: jnp.ndarray,
    h: jnp.ndarray,
    bag_mask: jnp.ndarray,
    feat_mask: jnp.ndarray,
    is_cat_feat: jnp.ndarray,
    *,
    has_cat: bool = False,
    axis_name: str | None = None,
    platform: str | None = None,
    learn_missing: bool = False,
    root_hist: jnp.ndarray | None = None,
    bundled_mask: jnp.ndarray | None = None,
) -> dict[str, Any]:
    p = params
    N, F = Xb.shape
    B = int(total_bins)
    L = p.effective_num_leaves
    M = p.max_nodes
    D = p.max_depth
    assert 0 < D <= _MAX_FAST_DEPTH
    HN = 1 << (D + 1)                 # heap slots (1-based; 0 unused)
    Pf = 1 << max(D - 1, 0)           # widest expansion level

    from dryad_tpu.engine.histogram import resolve_backend

    # wired gate FIRST (r10): a layout-wired expansion never touches the
    # plan-path record table or the natural-order tiles — skip both
    use_layout = leafwise_layout_supported(p, F, B, Xb.dtype.itemsize,
                                           platform)

    records = None
    nat_tiles = None
    if not use_layout and resolve_backend(p.hist_backend, segmented=True,
                                          platform=platform) == "pallas":
        from dryad_tpu.engine import pallas_hist

        if pallas_hist.supports(B):
            records = pallas_hist.make_records(Xb, g, h)
            # shallow-level natural-order pass, gated on the GLOBAL
            # matrix size (pallas_hist.maybe_natural_tiles documents why)
            nat_tiles = pallas_hist.maybe_natural_tiles(Xb, B, axis_name)

    def _nat_slots():
        from dryad_tpu.engine import pallas_hist

        return pallas_hist._NAT_SLOTS

    mono = _monotone_array(p, F)

    def best(hist, G, H, C, allow, lo, hi):
        return find_best_split(
            hist, G, H, C,
            lambda_l2=p.lambda_l2,
            min_child_weight=p.min_child_weight,
            min_data_in_leaf=p.min_data_in_leaf,
            min_split_gain=p.min_split_gain,
            feat_mask=feat_mask,
            is_cat_feat=is_cat_feat,
            allow=allow,
            has_cat=has_cat,
            monotone=mono,
            lo=lo,
            hi=hi,
            learn_missing=learn_missing,
            bundled_mask=bundled_mask,
        )

    # ---- histogram-reduction arm (r16) — levelwise.py's twin wiring:
    # feature-parallel reduce-scatter per expansion-level builder call,
    # sliced scan over the owned feature partition, one per-level
    # all_gather combine; the root keeps the fused psum + full scan
    # (root_stats reads feature 0's bins).  The selection replay below is
    # collective-free either way.
    from dryad_tpu.config import hist_reduce_resolved
    from dryad_tpu.engine import distributed as _dist
    from dryad_tpu.engine.split import find_best_split_sliced

    n_shards = _dist.axis_shards(axis_name)
    hr_mode = hist_reduce_resolved(p, F, B, n_shards)
    feat_par = hr_mode == "feature"
    FH = _dist.feature_slice_width(F, n_shards) if feat_par else F
    if feat_par:
        f_off = _dist.feature_shard_offset(axis_name, F)
        fmask_s = _dist.feature_shard_slice(feat_mask, axis_name)
        iscat_s = _dist.feature_shard_slice(is_cat_feat, axis_name)
        mono_s = (_dist.feature_shard_slice(mono, axis_name)
                  if mono is not None else None)
        bund_s = (_dist.feature_shard_slice(bundled_mask, axis_name)
                  if bundled_mask is not None else None)

        def best_sliced(hist, G, H, C, lo, hi):
            return find_best_split_sliced(
                hist, G, H, C,
                feat_offset=f_off,
                num_features_total=F,
                lambda_l2=p.lambda_l2,
                min_child_weight=p.min_child_weight,
                min_data_in_leaf=p.min_data_in_leaf,
                feat_mask=fmask_s,
                is_cat_feat=iscat_s,
                has_cat=has_cat,
                monotone=mono_s,
                lo=lo,
                hi=hi,
                learn_missing=learn_missing,
                bundled_mask=bund_s,
            )

    def level_scan(ch_hist, ch_G, ch_H, ch_C, allow, ch_lo, ch_hi):
        if not feat_par:
            return jax.vmap(best)(ch_hist, ch_G, ch_H, ch_C, allow,
                                  ch_lo, ch_hi)
        loc = jax.vmap(best_sliced)(ch_hist, ch_G, ch_H, ch_C, ch_lo, ch_hi)
        return _dist.combine_best_splits(
            loc, axis_name, allow=allow,
            min_split_gain=p.min_split_gain, has_cat=has_cat)

    # ---- root ----------------------------------------------------------------
    # ALL rows are routed (bag gates histograms only); derived from
    # bag_mask so the init inherits the shard's varying-manual-axes under
    # shard_map (a plain constant would make downstream vma types diverge —
    # same trick as grower.py / levelwise.py)
    with jax.named_scope("dryad.route"):
        row_node = jnp.where(bag_mask, 1, 1).astype(jnp.int32)
    hist0 = root_hist if root_hist is not None else build_hist(
        Xb, g, h, bag_mask, B,
        rows_per_chunk=p.rows_per_chunk, axis_name=axis_name,
        precision=p.hist_precision, backend=p.hist_backend,
        platform=platform)
    with jax.named_scope("dryad.split_scan"):
        G0, H0, C0 = root_stats(hist0)
        ninf, pinf = jnp.float32(-jnp.inf), jnp.float32(jnp.inf)
        root = best(hist0, G0, H0, C0,
                    (jnp.int32(0) < D) & (C0 >= 2 * p.min_data_in_leaf),
                    ninf, pinf)
        Bc = root.cat_mask.shape[0]

        # heap-node tables (index = heap id; unwritten slots keep the defaults)
        nd_gain = jnp.full((HN,), NEG_INF, jnp.float32).at[1].set(root.gain)
        nd_feature = jnp.full((HN,), -1, jnp.int32).at[1].set(root.feature)
        nd_thresh = jnp.zeros((HN,), jnp.int32).at[1].set(root.threshold)
        nd_GL = jnp.zeros((HN,), jnp.float32).at[1].set(root.g_left)
        nd_HL = jnp.zeros((HN,), jnp.float32).at[1].set(root.h_left)
        nd_CL = jnp.zeros((HN,), jnp.float32).at[1].set(root.c_left)
        nd_G = jnp.zeros((HN,), jnp.float32).at[1].set(G0)
        nd_H = jnp.zeros((HN,), jnp.float32).at[1].set(H0)
        nd_C = jnp.zeros((HN,), jnp.float32).at[1].set(C0)
        nd_dleft = jnp.ones((HN,), bool).at[1].set(root.default_left)
        nd_catmask = jnp.zeros((HN, Bc), bool).at[1].set(root.cat_mask)
        nd_lo = jnp.full((HN,), ninf, jnp.float32)
        nd_hi = jnp.full((HN,), pinf, jnp.float32)

        # feature arm: the expansion buffer carries each shard's OWNED slice
        hist0_loc = (_dist.feature_shard_slice(hist0, axis_name, axis=1)
                     if feat_par else hist0)
        hists = jnp.zeros((Pf, 3, FH, B), jnp.float32).at[0].set(hist0_loc)

    exp_st = {
        "row_node": row_node, "hists": hists,
        "nd_gain": nd_gain, "nd_feature": nd_feature, "nd_thresh": nd_thresh,
        "nd_GL": nd_GL, "nd_HL": nd_HL, "nd_CL": nd_CL,
        "nd_G": nd_G, "nd_H": nd_H, "nd_C": nd_C,
        "nd_dleft": nd_dleft, "nd_catmask": nd_catmask,
        "nd_lo": nd_lo, "nd_hi": nd_hi,
    }

    # ---- wired (leaf-ordered layout) static plan (r10) -----------------------
    # Run capacity NR = 2^D: the deepest move yields one segment per
    # level-D heap node (leafwise_layout_supported caps it).  The shapes
    # below come from the LOCAL row count, like every shard-local buffer.
    from dryad_tpu.engine import leafperm

    d_switch, P_narrow, _ = phase_plan(D)
    NR = 1 << D
    half_bound_ok = axis_name is None and N < (1 << 24)
    n_buf_tiles = n_sel_narrow = n_sel_full = 0
    if use_layout:
        Tl = leafperm._TILE_ROWS
        n_buf_tiles = leafperm.wired_tiles_bound(-(-N // Tl), NR)
        # smaller children cover <= half the in-bag rows on a single
        # device (min(left,right) <= parent/2, parents disjoint) — the
        # same shared-bound rule as levelwise (see wired_sel_tiles_bound)
        n_sel_narrow = leafperm.wired_sel_tiles_bound(
            -(-N // Tl), n_buf_tiles, P_narrow, half=half_bound_ok)
        n_sel_full = leafperm.wired_sel_tiles_bound(
            -(-N // Tl), n_buf_tiles, Pf, half=half_bound_ok)
        # root-anchored layout: the natural-order record buffer IS the
        # root layout (run 0 -> heap node 1, sentinel HN elsewhere);
        # out-of-bag rows enter sentinel-flagged and are dropped by level
        # 0's move — no sort, no gather, no handoff
        rec_nat = leafperm.make_layout_records(Xb, g, h, valid=bag_mask)
        lay_rec, lay_tr, lay_ns = leafperm.natural_root_layout(
            rec_nat, NR, n_buf_tiles, first_slot=1, sentinel=HN,
            axis_name=axis_name)
        exp_st = dict(exp_st, lay_rec=lay_rec, lay_tile_run=lay_tr,
                      lay_run_slot=lay_ns)

    # ---- expansion: every valid split, level-synchronously -------------------
    def make_level_body(P, use_nat=False, use_layout=False, n_sel_tiles=0):
        def level_body(d, st):
            with jax.named_scope("dryad.split_scan"):
                base = jnp.left_shift(jnp.int32(1), d)         # level-d heap base
                W = base                                        # level width
                jarr = jnp.arange(P, dtype=jnp.int32)
                idx = jnp.minimum(base + jarr, HN - 1)
                do = (st["nd_gain"][idx] > NEG_INF) & (jarr < W)
                sf = st["nd_feature"][idx]
                thr = st["nd_thresh"][idx]
                GL, HL, CL = st["nd_GL"][idx], st["nd_HL"][idx], st["nd_CL"][idx]
                Gp, Hp, Cp = st["nd_G"][idx], st["nd_H"][idx], st["nd_C"][idx]
                GR, HR, CR = Gp - GL, Hp - HL, Cp - CL

            # ---- partition: a row moves iff its node has a valid split.
            # Expansion splits EVERY valid-gain node at its level, so a row
            # can only sit at a valid-gain node when that node is at the
            # current level — no level check needed.  Same packed-word +
            # masked-reduce scheme as levelwise.py (measured there).
            with jax.named_scope("dryad.route"):
                rn = st["row_node"]
                valid_n = st["nd_gain"] > NEG_INF
                rec_t = None
                if B <= (1 << 13):
                    cat_n = (is_cat_feat[jnp.maximum(st["nd_feature"], 0)]
                             if has_cat else jnp.zeros((HN,), bool))
                    w0_t = ((valid_n.astype(jnp.uint32) << 31)
                            | (st["nd_dleft"].astype(jnp.uint32) << 30)
                            | (cat_n.astype(jnp.uint32) << 29)
                            | (jnp.clip(st["nd_thresh"], 0, B - 1)
                               .astype(jnp.uint32) << 16))
                    rec_t = jnp.stack(
                        [w0_t, jnp.maximum(st["nd_feature"], 0).astype(jnp.uint32)],
                        axis=1)

                    def packed_route(nodes):
                        """Per-row routing off the packed per-NODE table:
                        (splits?, goes-left?).  The layout's kernels
                        (leafperm._tile_sides) apply the SAME integer/bool
                        rules to the same table per tile, so the
                        natural-order partition and the layout can never
                        disagree on a row (levelwise.packed_route's
                        convention)."""
                        rr = rec_t[nodes]                        # ONE gather
                        w0r = rr[:, 0]
                        rf = rr[:, 1].astype(jnp.int32)
                        bins_rf = levelwise.select_bins(Xb, rf)
                        gl = bins_rf <= ((w0r >> 16)
                                         & jnp.uint32(0x1FFF)).astype(jnp.int32)
                        if learn_missing:
                            gl &= ((w0r >> 30) & 1).astype(bool) | (bins_rf > 0)
                        if has_cat:
                            cat_row = st["nd_catmask"][
                                jnp.minimum(nodes, HN - 1),
                                jnp.minimum(bins_rf, Bc - 1)]
                            gl = jnp.where(((w0r >> 29) & 1).astype(bool),
                                           cat_row, gl)
                        return ((w0r >> 31) != 0), gl

                    row_do, go_left = packed_route(rn)
                else:
                    row_do = valid_n[rn]
                    rf = jnp.maximum(st["nd_feature"][rn], 0)
                    bins_rf = jnp.take_along_axis(
                        Xb, rf[:, None].astype(jnp.int32), axis=1)[:, 0]
                    bins_rf = bins_rf.astype(jnp.int32)
                    go_left = bins_rf <= st["nd_thresh"][rn]
                    if learn_missing:
                        go_left &= st["nd_dleft"][rn] | (bins_rf > 0)
                    if has_cat:
                        cat_row = st["nd_catmask"][rn, jnp.minimum(bins_rf, Bc - 1)]
                        go_left = jnp.where(is_cat_feat[rf], cat_row, go_left)
                row_node = jnp.where(
                    row_do, 2 * rn + jnp.where(go_left, 0, 1), rn)

            # ---- one batched histogram pass for all smaller children -----
            left_smaller = CL <= CR
            lay_new = None
            if use_layout:
                # WIRED level (r10): no per-level sort, no full-N record
                # gather, nothing row-sized in XLA.  The per-node table is
                # composed run -> record at the (NR,) level;
                # leafperm.move_level gathers it per TILE and its kernels
                # derive sides and in-tile ranks from the record tiles
                # (see levelwise.py's wired block).  The smaller children
                # read back as contiguous tile runs of the new layout.
                with jax.named_scope("dryad.layout"):
                    lay_tr = st["lay_tile_run"]
                    lay_ns = st["lay_run_slot"]           # run -> heap node
                    # sentinel runs (lay_ns = HN) compose to the zero pad
                    # row -> pass-through, and carry no valid rows anyway
                    rec_pad = jnp.concatenate(
                        [rec_t, jnp.zeros((1, 2), jnp.uint32)])
                    lay_rec, base_l, base_r = leafperm.move_level(
                        st["lay_rec"], lay_tr,
                        rec_pad[jnp.minimum(lay_ns, HN)],
                        st["nd_catmask"][jnp.minimum(lay_ns, HN - 1)]
                        if has_cat else None,
                        bin_dtype=Xb.dtype, learn_missing=learn_missing,
                        platform=platform, axis_name=axis_name)
                    # node -> run inverse BEFORE advancing (candidates are
                    # parents of this level's move); sentinel runs scatter
                    # past the (HN+1,) table so mode="drop" really drops them
                    node_run = jnp.full((HN + 1,), NR, jnp.int32).at[
                        jnp.where(lay_ns < HN, lay_ns, HN + 1)].set(
                            jnp.arange(NR, dtype=jnp.int32), mode="drop")
                    # a run's node carries a valid split only while that node
                    # is at the current level (the expansion splits it NOW) —
                    # left child keeps the run with node 2n, right child
                    # appends node 2n+1 (advance_runs' pre-update contract)
                    valid_tab = (rec_pad[:, 0] >> 31) != 0
                    run_do = valid_tab[jnp.minimum(lay_ns, HN)] & (lay_ns < HN)
                    ns2 = jnp.where(run_do, 2 * lay_ns, lay_ns)
                    lay_tr_new, lay_ns_new = leafperm.advance_runs(
                        ns2, run_do, 2 * lay_ns + 1, base_l, base_r,
                        lay_tr.shape[0], sentinel=HN)
                    lay_new = (lay_rec, lay_tr_new, lay_ns_new)
                    # smaller children = contiguous segments of the NEW layout
                    rj = node_run[idx]
                    rjc = jnp.minimum(rj, NR - 1)
                    lt_l = base_l[1:] - base_l[:-1]
                    lt_r = base_r[1:] - base_r[:-1]
                    sel_ok = do & (rj < NR)
                    seg_first = jnp.where(
                        sel_ok,
                        jnp.where(left_smaller, base_l[rjc], base_r[rjc]), 0)
                    seg_nt = jnp.where(
                        sel_ok,
                        jnp.where(left_smaller, lt_l[rjc], lt_r[rjc]), 0)
                hist_small = leafperm.hist_from_layout(
                    lay_rec, seg_first, seg_nt, P, B, F, Xb.dtype,
                    n_sel_tiles, axis_name=axis_name, platform=platform,
                    hist_reduce=hr_mode)
            else:
                with jax.named_scope("dryad.hist"):
                    small_heap = 2 * idx + jnp.where(left_smaller, 0, 1)
                    colof = jnp.full((HN,), P, jnp.int32).at[
                        jnp.where(do, small_heap, HN)].set(jarr, mode="drop")
                    smallsel = jnp.where(bag_mask, colof[row_node], P)
                    bound_ok = axis_name is None and N < (1 << 24)
                    if use_nat:
                        from dryad_tpu.engine import pallas_hist

                        hist_small = pallas_hist.build_hist_small(
                            nat_tiles, g, h, smallsel, P, B, F,
                            axis_name=axis_name, platform=platform,
                            hist_reduce=hr_mode)
                    else:
                        # exact per-column counts (smaller-child C off the
                        # parent histogram) admit the pad-injected aligned
                        # sort inside build_hist_segmented — see levelwise.py
                        small_cnt = (jnp.where(do,
                                               jnp.where(left_smaller, CL, CR),
                                               0.0).astype(jnp.int32)
                                     if bound_ok else None)
                        hist_small = build_hist_segmented(
                            Xb, g, h, smallsel, P, B,
                            rows_per_chunk=p.rows_per_chunk, axis_name=axis_name,
                            precision=p.hist_precision, backend=p.hist_backend,
                            rows_bound=(N // 2 + 1) if bound_ok else None,
                            platform=platform, records=records,
                            sel_counts=small_cnt,
                            # deep caps leave most expansion slots empty —
                            # exactly where staged gather prefixes pay (see
                            # levelwise.py)
                            stage_gather=L < Pf,
                            hist_reduce=hr_mode,
                        )
            with jax.named_scope("dryad.hist"):
                hist_large = st["hists"][jnp.minimum(jarr, Pf - 1)] - hist_small
                ls = left_smaller[:, None, None, None]
                hist_l = jnp.where(ls, hist_small, hist_large)
                hist_r = jnp.where(ls, hist_large, hist_small)
                # children hists land at level-(d+1) offsets 2j / 2j+1; the
                # final level's children (never split) fall off the buffer and
                # are dropped
                hists = st["hists"].at[
                    jnp.where(do, 2 * jarr, Pf)].set(hist_l, mode="drop")
                hists = hists.at[
                    jnp.where(do, 2 * jarr + 1, Pf)].set(hist_r, mode="drop")

            # ---- children stats + their best splits ----------------------
            with jax.named_scope("dryad.split_scan"):
                lo_p, hi_p = st["nd_lo"][idx], st["nd_hi"][idx]
                if mono is not None:
                    lo_l, hi_l, lo_r, hi_r = child_bounds(
                        mono, sf, GL, HL, GR, HR, jnp.float32(p.lambda_l2),
                        lo_p, hi_p)
                else:
                    lo_l = lo_r = lo_p
                    hi_l = hi_r = hi_p
                ch_heap = jnp.concatenate([2 * idx, 2 * idx + 1])
                ch_do = jnp.concatenate([do, do])
                ch_hist = jnp.concatenate([hist_l, hist_r])
                ch_G = jnp.concatenate([GL, GR])
                ch_H = jnp.concatenate([HL, HR])
                ch_C = jnp.concatenate([CL, CR])
                ch_lo = jnp.concatenate([lo_l, lo_r])
                ch_hi = jnp.concatenate([hi_l, hi_r])
                allow = ch_do & (d + 1 < D) & (ch_C >= 2 * p.min_data_in_leaf)
                res = level_scan(ch_hist, ch_G, ch_H, ch_C, allow, ch_lo, ch_hi)

                cidx = jnp.where(ch_do, ch_heap, HN)
                st_new = dict(st)
                st_new["row_node"] = row_node
                st_new["hists"] = hists
                st_new["nd_gain"] = st["nd_gain"].at[cidx].set(res.gain,
                                                               mode="drop")
                st_new["nd_feature"] = st["nd_feature"].at[cidx].set(
                    res.feature, mode="drop")
                st_new["nd_thresh"] = st["nd_thresh"].at[cidx].set(
                    res.threshold, mode="drop")
                st_new["nd_GL"] = st["nd_GL"].at[cidx].set(res.g_left, mode="drop")
                st_new["nd_HL"] = st["nd_HL"].at[cidx].set(res.h_left, mode="drop")
                st_new["nd_CL"] = st["nd_CL"].at[cidx].set(res.c_left, mode="drop")
                st_new["nd_G"] = st["nd_G"].at[cidx].set(ch_G, mode="drop")
                st_new["nd_H"] = st["nd_H"].at[cidx].set(ch_H, mode="drop")
                st_new["nd_C"] = st["nd_C"].at[cidx].set(ch_C, mode="drop")
                st_new["nd_dleft"] = st["nd_dleft"].at[cidx].set(
                    res.default_left, mode="drop")
                st_new["nd_catmask"] = st["nd_catmask"].at[cidx].set(
                    res.cat_mask, mode="drop")
                st_new["nd_lo"] = st["nd_lo"].at[cidx].set(ch_lo, mode="drop")
                st_new["nd_hi"] = st["nd_hi"].at[cidx].set(ch_hi, mode="drop")
            if use_layout:
                (st_new["lay_rec"], st_new["lay_tile_run"],
                 st_new["lay_run_slot"]) = lay_new
            return st_new
        return level_body

    exp_st = jax.lax.fori_loop(
        0, d_switch,
        make_level_body(P_narrow,
                        use_nat=nat_tiles is not None
                        and P_narrow <= _nat_slots(),
                        use_layout=use_layout, n_sel_tiles=n_sel_narrow),
        exp_st)
    if d_switch < D:
        exp_st = jax.lax.fori_loop(
            d_switch, D,
            make_level_body(Pf, use_nat=nat_tiles is not None
                            and Pf <= _nat_slots(),
                            use_layout=use_layout, n_sel_tiles=n_sel_full),
            exp_st)

    # ---- selection: replay grow_tree's slot machine on the gain tree ---------
    nd_gain = exp_st["nd_gain"]
    nd_feature = exp_st["nd_feature"]
    nd_thresh = exp_st["nd_thresh"]
    nd_dleft = exp_st["nd_dleft"]
    nd_catmask = exp_st["nd_catmask"]
    nd_G, nd_H = exp_st["nd_G"], exp_st["nd_H"]
    nd_C_sel = exp_st["nd_C"]
    nd_lo, nd_hi = exp_st["nd_lo"], exp_st["nd_hi"]

    with jax.named_scope("dryad.select"):
        sel_st = {
            "slot_heap": jnp.zeros((L,), jnp.int32).at[0].set(1),
            "slot_tree": jnp.full((L,), -1, jnp.int32).at[0].set(0),
            "slot_gain": jnp.full((L,), NEG_INF, jnp.float32).at[0].set(
                nd_gain[1]),
            "slot_depth": jnp.zeros((L,), jnp.int32),
            "feature": jnp.full((M,), -1, jnp.int32),
            "threshold": jnp.zeros((M,), jnp.int32),
            "gain": jnp.zeros((M,), jnp.float32),
            "cover": jnp.zeros((M,), jnp.float32).at[0].set(nd_C_sel[1]),
            "left": jnp.zeros((M,), jnp.int32),
            "right": jnp.zeros((M,), jnp.int32),
            "is_cat": jnp.zeros((M,), bool),
            "cat_nodes": jnp.zeros((M, Bc), bool),
            "node_dleft": jnp.ones((M,), bool),
            "selected": jnp.zeros((HN,), bool),
            "child_tree": jnp.zeros((HN,), jnp.int32),
            "num_nodes": jnp.int32(1),
            "max_depth": jnp.int32(0),
        }

    def do_split(k, s, st):
        n = st["slot_heap"][s]
        parent = st["slot_tree"][s]
        sf = nd_feature[n]
        cat_split = is_cat_feat[jnp.maximum(sf, 0)] if has_cat \
            else jnp.bool_(False)
        left_id = st["num_nodes"]
        right_id = left_id + 1
        depth_c = st["slot_depth"][s] + 1
        new_r = jnp.int32(k + 1)
        return {
            "slot_heap": st["slot_heap"].at[s].set(2 * n)
                                        .at[new_r].set(2 * n + 1),
            "slot_tree": st["slot_tree"].at[s].set(left_id)
                                        .at[new_r].set(right_id),
            "slot_gain": st["slot_gain"].at[s].set(nd_gain[2 * n])
                                        .at[new_r].set(nd_gain[2 * n + 1]),
            "slot_depth": st["slot_depth"].at[s].set(depth_c)
                                          .at[new_r].set(depth_c),
            "feature": st["feature"].at[parent].set(sf),
            "threshold": st["threshold"].at[parent].set(
                jnp.where(cat_split, 0, nd_thresh[n])),
            "gain": st["gain"].at[parent].set(st["slot_gain"][s]),
            "cover": st["cover"].at[left_id].set(nd_C_sel[2 * n])
                                .at[right_id].set(nd_C_sel[2 * n + 1]),
            "left": st["left"].at[parent].set(left_id),
            "right": st["right"].at[parent].set(right_id),
            "is_cat": st["is_cat"].at[parent].set(cat_split),
            "cat_nodes": st["cat_nodes"].at[parent].set(
                jnp.where(cat_split, nd_catmask[n],
                          jnp.zeros((Bc,), bool))),
            "node_dleft": st["node_dleft"].at[parent].set(
                nd_dleft[n] | cat_split),
            "selected": st["selected"].at[n].set(True),
            "child_tree": st["child_tree"].at[2 * n].set(left_id)
                                          .at[2 * n + 1].set(right_id),
            "num_nodes": st["num_nodes"] + 2,
            "max_depth": jnp.maximum(st["max_depth"], depth_c),
        }

    def sel_body(k, st):
        s = jnp.argmax(st["slot_gain"]).astype(jnp.int32)
        return jax.lax.cond(st["slot_gain"][s] > NEG_INF,
                            lambda st_: do_split(k, s, st_),
                            lambda st_: st_, st)

    with jax.named_scope("dryad.select"):
        sel_st = jax.lax.fori_loop(0, L - 1, sel_body, sel_st)

    # ---- finalize -------------------------------------------------------------
    with jax.named_scope("dryad.split_scan"):
        sh = jnp.clip(sel_st["slot_heap"], 0, HN - 1)
        value = finalize_leaf_values(
            p, M, sel_st["slot_tree"], nd_G[sh], nd_H[sh],
            jnp.zeros((M,), jnp.float32),
            slot_lo=nd_lo[sh] if mono is not None else None,
            slot_hi=nd_hi[sh] if mono is not None else None,
        )
        cat_bitset = pack_cat_bitset(sel_st["cat_nodes"], M)

        # map every heap node to its leaf in the SELECTED tree: walking down,
        # a node resolves to its own tree id where its parent was selected,
        # else inherits the parent's resolution (D static levels)
        leaf_of = jnp.zeros((HN,), jnp.int32)
        selected = sel_st["selected"]
        child_tree = sel_st["child_tree"]
        idx_all = jnp.arange(HN, dtype=jnp.int32)
        for d in range(1, D + 1):
            lvl = (idx_all >> d) == 1
            par = idx_all >> 1
            leaf_of = jnp.where(lvl,
                                jnp.where(selected[par], child_tree[idx_all],
                                          leaf_of[par]),
                                leaf_of)

    return {
        "feature": sel_st["feature"],
        "threshold": sel_st["threshold"],
        "left": sel_st["left"],
        "right": sel_st["right"],
        "value": value,
        "gain": sel_st["gain"],
        "is_cat": sel_st["is_cat"],
        "cat_bitset": cat_bitset,
        "default_left": sel_st["node_dleft"],
        "cover": sel_st["cover"],
        "max_depth": sel_st["max_depth"],
        # each row's leaf is key_leaf[row_key], the heap node it was routed
        # to; the train step gathers once a row (train._row_records)
        "row_key": exp_st["row_node"],
        "key_leaf": leaf_of,
        # what the expansion grew against what the selection kept (obs
        # counters dryad_leafwise_{expanded,selected}_splits_total)
        "expanded_splits": jnp.sum(nd_gain > NEG_INF, dtype=jnp.int32),
        "selected_splits": (sel_st["num_nodes"] - 1) // 2,
    }
