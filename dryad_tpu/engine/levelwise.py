"""Level-wise (depth-wise) tree grower — the TPU throughput path.

The generic grower (grower.py) mirrors the reference's one-split-at-a-time
control flow, which costs one full masked histogram pass per split — the
MXU pads the 3-row weight matrix to 128 rows, so per-split passes waste
~40x of the matrix unit.  Growing level-synchronously amortizes that: every
leaf of a level lands in one ``build_hist_multi`` call whose weight matrix
carries 3 columns per leaf, so a whole level of histograms costs roughly
ONE pass over the rows (SURVEY.md §7 step 6; the classic GPU engines get
the same effect from atomics — this is the MXU-shaped equivalent).

Semantics replicate ``cpu/trainer.py`` depth-wise growth exactly: within a
level, splits are applied in best-gain-first order (stable, first-slot
tie-break) until the ``num_leaves`` budget runs out; the left child keeps
the parent's slot, right children take consecutive slot ids in execution
order; child stats come from the parent-histogram prefix; the smaller child
is histogrammed directly, the larger derived by subtraction.

Distribution: identical contract to grower.py — call under ``shard_map``
with rows sharded; the single per-level fused psum inside
``build_hist_multi`` is the only collective.

Layout everywhere (r6 deep phase, r10 whole tree): when the gate admits
(``deep_layout_supported``) the tree carries the leaf-ordered record
layout (engine/leafperm.py) through the level fori_loop state from
LEVEL 0 — the natural-order record buffer is the root layout
(``leafperm.natural_root_layout``: one segment, out-of-bag rows as
sentinels), sides derive from the layout records, one stable per-tile
MXU compaction moves every row to its child segment, and the children's
histograms read the new layout as CONTIGUOUS tile runs.  The per-level
packed ``(slot<<24 | row)`` sort and the full-N record gather are GONE
(measured 51.4 vs 164 ms/level at 10M for the data movement they
replaced), and so is the r6 shallow->deep handoff sort+gather per tree
— nothing on the wired path ever sorts rows.  The plan-based path below
remains only for configs the layout cannot take (each exclusion's
verdict is written in ``deep_layout_supported``) and as the explicitly
requested ``deep_layout="legacy"`` comparison arm.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from dryad_tpu.config import Params
from dryad_tpu.engine.grower import (
    child_bounds,
    finalize_leaf_values,
    pack_cat_bitset,
    root_stats,
)
from dryad_tpu.engine.histogram import (
    build_hist,
    build_hist_multi,
    build_hist_segmented,
)
from dryad_tpu.engine.split import NEG_INF, find_best_split

# STRUCTURAL packed-word caps for the wired layout (r10): the side
# derivation rides the same packed per-slot word as the natural-order
# partition (13-bit threshold, 16-bit slot fields).  These are encoding
# widths, not measured crossovers — they stay here, never in the policy
# calibration table (a table can only narrow the CALIBRATED caps that
# deep_layout_supported routes through policy below).
_MAX_PACKED_BINS = 1 << 13
_MAX_PACKED_LEAVES = 1 << 16


def partition_prefers_reduce(num_features: int, itemsize: int) -> bool:
    """Partition formulation choice, shared by both level-synchronous
    growers: the masked reduce over the CONTIGUOUS (N, F) matrix vs the
    per-row column gather.

    The reduce's traffic is N·F·itemsize sequential bytes; the gather
    costs ~per-ACCESS (CLAUDE.md: ~30 ms per 10M accesses, bytes nearly
    free).  Crossover: reading F·itemsize bytes/row beats one random
    access while F·itemsize ≲ 20 KB of sequential traffic per row-access
    saved — far above any supported width.  r4 gated the reduce at
    F <= 256 on the 10M=28-feature measurement alone, sending
    Epsilon-shaped (400k × 2000) configs to the ~320 ms-class gather; r5
    widens the gate to 4 KB/row (u8: F <= 4096, u16: F <= 2048), measured
    on the Epsilon shape (exp_r5_eps.py: reduce 11.1 ms vs gather 18.6 ms
    per pass at 400k x 2000; the whole-run effect measured 10.2 ->
    7.1 s/iter warm).  r23: the row-byte budget lives in the policy
    table ("partition"/"reduce_max_row_bytes"); the committed default is
    the 4 KB above, bitwise-identical resolution."""
    from dryad_tpu.policy.gates import resolve

    return resolve("partition", {"num_features": num_features,
                                 "itemsize": itemsize}) == "reduce"


def select_bins(Xb: jnp.ndarray, rf: jnp.ndarray) -> jnp.ndarray:
    """Each row's bin id on its per-row feature ``rf`` — THE partition
    column-select, shared by both level-synchronous growers so the
    formulation (and the gate above) can never diverge between them (the
    r4 F<=256 gate had to be widened in two copies; review r5).  Masked
    reduce over the contiguous (N, F) matrix when the gate admits (at
    most one column matches per row), per-row gather otherwise."""
    F = Xb.shape[1]
    if partition_prefers_reduce(F, Xb.dtype.itemsize):
        iota_f = jnp.arange(F, dtype=jnp.int32)
        return jnp.max(
            jnp.where(rf[:, None] == iota_f[None, :], Xb,
                      jnp.zeros((), Xb.dtype)),
            axis=1).astype(jnp.int32)
    return jnp.take_along_axis(Xb, rf[:, None], axis=1)[:, 0].astype(
        jnp.int32)


def deep_layout_supported(p: Params, num_features: int, total_bins: int,
                          bin_itemsize: int,
                          platform: str | None = None) -> bool:
    """Static gate for the wired (leaf-ordered layout) level-wise grower
    — since r10 the layout is live from LEVEL 0 (root-anchored), so this
    gates the whole tree, not just the deep phase.

    A pure function of (params, feature/bin shape, platform) — NEVER of
    the row count, which under ``shard_map`` is the local shard and would
    let 1-shard and N-shard runs of the same data choose different
    histogram programs (the CLAUDE.md same-program rule).  Exclusion
    verdicts (r10 retirement pass — each is either LIFTED with parity
    tests or kept with the measurement that makes it irrelevant):

    * ``hist_subtraction=False`` — LIFTED (r10): the wired level
      histograms BOTH children in one 2P-column ``hist_from_layout``
      pass over the new layout's contiguous runs (every live row read
      exactly once — cheaper than the legacy small-pass + full
      ``build_hist_multi`` pair); parity pinned by
      ``test_wired_no_subtraction_matches_legacy``.
    * wide records (9 + F*itemsize > _REC_WB = 128 B) — KEPT, measured
      irrelevant: the layout's win is the deleted per-level sort +
      record gather, whose cost is access-bound and scales with N
      (~164 ms/level at 10M with 128 B records ≈ ~7 ms/level at
      Epsilon's 400k rows), while an Epsilon-shaped record (9 + 2000 B
      -> 16x the granule) multiplies every level's MOVED bytes ~16x —
      the compaction alone would cost more than the sort+gather it
      replaces (scaling exp_r5_perm's 51.4 ms/level by 0.4/10 rows x
      16x bytes ≈ 33 ms/level vs ~7 to win back).  Wide-feature shapes
      already dodge the per-row gather via the partition reduce
      (exp_r5_eps: 11.1 ms/pass at 400k x 2000), so there is no
      ~110 ms/level to recover on this path.
    * leaf budgets past 512 — KEPT, structural: the (L,)-dense run
      bookkeeping mandates >= 2L+2 tiles per level (every unused run
      index owns a mandatory tile per region — level_moves contract).
      At L=512 that is ~525k zero-sentinel rows per level, ~5% of the
      10M headline's movement; past 512 the mandated tiles grow
      linearly in L while the recoverable sort+gather stays fixed at
      ~164 ms/level, so the empty-segment movement stops being noise
      for ANY row count the HBM budget admits (and the gate cannot
      consult N — same-program rule above).
    * non-Pallas histogram backends / bins past the Pallas cap
      (``pallas_hist.supports``) — structural: the layout feeds the
      tile kernel; there is no XLA consumer of a tile-aligned layout.
    * bins > 8192 / leaves >= 65536 — structural: the side derivation
      rides the same packed per-slot word as the natural-order
      partition (13-bit threshold, 16-bit slot fields).
    * ``deep_layout="legacy"`` (explicit opt-out: smoke gate + bench
      comparison arms, and the escape hatch if wired drifts on device).
    """
    from dryad_tpu.engine import pallas_hist
    from dryad_tpu.engine.histogram import resolve_backend
    from dryad_tpu.policy.gates import resolve

    if p.deep_layout == "legacy":
        return False
    if resolve_backend(p.hist_backend, segmented=True,
                       platform=platform) != "pallas":
        return False
    if not pallas_hist.supports(total_bins):
        return False
    L = p.effective_num_leaves
    if not (total_bins <= _MAX_PACKED_BINS and L < _MAX_PACKED_LEAVES):
        return False
    # the CALIBRATED caps (leaf budget, record width) route through the
    # policy table; structural exclusions above never do
    return resolve("deep_layout",
                   {"num_leaves": L,
                    "record_bytes": 9 + num_features * bin_itemsize}
                   ) == "layout"


def phase_plan(depth_cap: int, num_leaves: int, nat_live: bool):
    """(d_switch, P_narrow, P_full) for the two-phase level loop — the ONE
    definition of the phase boundary, shared with train._comm_stats so the
    observability accounting mirrors the grower's actual program (ADVICE
    r4).  The switch sits at depth 5 (<= 16 candidates = _NAT_SLOTS) when
    the natural-order pass is live so level 4 rides it too, else at the
    measured depth-4 boundary."""
    P_full = min(1 << (depth_cap - 1), num_leaves - 1)
    d_cut = 5 if nat_live else 4
    d_switch = d_cut if (depth_cap > d_cut and P_full > (1 << (d_cut - 1))) \
        else depth_cap
    P_narrow = min(1 << (d_switch - 1), num_leaves - 1)
    return d_switch, P_narrow, P_full


def grow_tree_levelwise(
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
    depth_cap = p.max_depth
    assert depth_cap > 0, "levelwise growth requires max_depth > 0"

    # wired gate FIRST (r10): a layout-wired tree is wired from level 0
    # (root-anchored layout, no shallow->deep handoff) and never touches
    # the plan-path record table or the natural-order tiles — skip
    # building both (the record table alone is ~20 B/row of HBM)
    use_layout = deep_layout_supported(p, F, B, Xb.dtype.itemsize, platform)

    # one per-TREE record table [g, h, X] for the Pallas levels: every
    # level's segmented histogram then pays ONE row gather instead of an X
    # gather + a g/h gather (pallas_hist.make_records)
    from dryad_tpu.engine.histogram import resolve_backend

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

    def pallas_hist_NAT_SLOTS():
        from dryad_tpu.engine import pallas_hist

        return pallas_hist._NAT_SLOTS

    from dryad_tpu.engine.grower import _monotone_array

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

    # ---- histogram-reduction arm (r16): fused psum vs feature-parallel ------
    # reduce-scatter.  The gate (config.hist_reduce_resolved) is a pure
    # function of (params, F/B shape, shard count) — same-program rule.
    # On the feature arm every per-LEVEL builder reduce-scatters a static
    # contiguous feature partition (each shard owns Fs = ceil(F/n) fully
    # reduced columns, bitwise equal to the psum's slice), the split scan
    # runs on the owned slice only (find_best_split_sliced over sliced
    # masks), and one tiny per-level all_gather of packed records
    # (combine_best_splits) makes every shard pick the fused scan's
    # winner.  The ROOT stays on the fused psum + full scan: root_stats
    # reads feature 0's bins (only shard 0 would own them) and the root
    # is one slot — its payload is noise next to the P-wide levels.
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
        """One level's children split finding — per-arm: the fused full
        scan, or sliced scan + replicated combine (ONE all_gather for the
        whole candidate batch)."""
        if not feat_par:
            return jax.vmap(best)(ch_hist, ch_G, ch_H, ch_C, allow,
                                  ch_lo, ch_hi)
        loc = jax.vmap(best_sliced)(ch_hist, ch_G, ch_H, ch_C, ch_lo, ch_hi)
        return _dist.combine_best_splits(
            loc, axis_name, allow=allow,
            min_split_gain=p.min_split_gain, has_cat=has_cat)

    # ---- root (shared canonical construction) --------------------------------
    # ALL rows are partitioned (bag gates histograms only) so the final
    # row_slot yields each row's leaf without a separate traversal pass;
    # derived from bag_mask to inherit the shard's varying-manual-axes
    with jax.named_scope("dryad.route"):
        row_slot = jnp.where(bag_mask, 0, 0).astype(jnp.int32)
    hist0 = root_hist if root_hist is not None else build_hist(
        Xb, g, h, bag_mask, B,
        rows_per_chunk=p.rows_per_chunk, axis_name=axis_name,
        precision=p.hist_precision, backend=p.hist_backend,
        platform=platform)
    with jax.named_scope("dryad.split_scan"):
        G0, H0, C0 = root_stats(hist0)
        ninf, pinf = jnp.float32(-jnp.inf), jnp.float32(jnp.inf)
        root = best(hist0, G0, H0, C0,
                    (jnp.int32(0) < depth_cap) & (C0 >= 2 * p.min_data_in_leaf),
                    ninf, pinf)
        Bc = root.cat_mask.shape[0]

        slot_node = jnp.full((L,), -1, jnp.int32).at[0].set(0)
        slot_gain = jnp.full((L,), NEG_INF, jnp.float32).at[0].set(root.gain)
        slot_G = jnp.zeros((L,), jnp.float32).at[0].set(G0)
        slot_H = jnp.zeros((L,), jnp.float32).at[0].set(H0)
        slot_C = jnp.zeros((L,), jnp.float32).at[0].set(C0)
        slot_depth = jnp.zeros((L,), jnp.int32)
        slot_lo = jnp.full((L,), ninf, jnp.float32)
        slot_hi = jnp.full((L,), pinf, jnp.float32)
        sp_feature = jnp.full((L,), -1, jnp.int32).at[0].set(root.feature)
        sp_thresh = jnp.zeros((L,), jnp.int32).at[0].set(root.threshold)
        sp_GL = jnp.zeros((L,), jnp.float32).at[0].set(root.g_left)
        sp_HL = jnp.zeros((L,), jnp.float32).at[0].set(root.h_left)
        sp_CL = jnp.zeros((L,), jnp.float32).at[0].set(root.c_left)
        sp_catmask = jnp.zeros((L, Bc), bool).at[0].set(root.cat_mask)
        sp_dleft = jnp.ones((L,), bool).at[0].set(root.default_left)
        # feature arm: the carried histogram buffer holds each shard's OWNED
        # slice only (an n-fold HBM saving to boot); the replicated root hist
        # is sliced once here so level-0 subtraction stays slice-local
        hist0_loc = (_dist.feature_shard_slice(hist0, axis_name, axis=1)
                     if feat_par else hist0)
        hists = jnp.zeros((L, 3, FH, B), jnp.float32).at[0].set(hist0_loc)

        cover_arr = jnp.zeros((M,), jnp.float32).at[0].set(C0)
        feature = jnp.full((M,), -1, jnp.int32)
        threshold = jnp.zeros((M,), jnp.int32)
        gain_arr = jnp.zeros((M,), jnp.float32)
        left = jnp.zeros((M,), jnp.int32)
        right = jnp.zeros((M,), jnp.int32)
        is_cat_arr = jnp.zeros((M,), bool)
        cat_nodes = jnp.zeros((M, Bc), bool)
        node_dleft = jnp.ones((M,), bool)
        num_nodes = jnp.int32(1)
        splits_done = jnp.int32(0)
        max_depth = jnp.int32(0)

    # ---- levels: two fori_loop phases with level-appropriate widths ----------
    # A Python unroll over levels would multiply the XLA program by depth_cap
    # (pathological remote compile times); a single fori_loop must run EVERY
    # level at the deepest level's width P (the per-level cost of the
    # candidate machinery, tile plan and vmapped split scan all scale with
    # P).  Two phases split the difference: shallow levels run narrow, deep
    # levels at the full width — one extra traced body, most of the
    # narrow-level savings.  The switch sits at depth 5 (<= 16 candidates)
    # when the natural-order pass is live so level 4 rides it too
    # (_NAT_SLOTS = 16; sort+gather-free beats the plan path ~70 ms/level
    # at 10M), else at the measured depth-4 boundary.
    d_switch, P_narrow, P_full = phase_plan(depth_cap, L,
                                            nat_tiles is not None)

    # ---- wired (leaf-ordered layout) static plan -----------------------------
    # The gate is row-count free (same program at every shard count); the
    # SHAPES below come from the local row count, as every shard-local
    # buffer's do.  Since r10 the layout is live from level 0, so BOTH
    # phases get a selection bound at their own candidate width.
    from dryad_tpu.engine import leafperm

    # the ONE exact-f32-counts / single-device predicate, shared by the
    # wired plan's half bound and the legacy arm's bound_ok below — the
    # two must never drift (an unsafe half-sized n_sel_tiles silently
    # truncates histograms, hist_from_layout contract)
    half_bound_ok = axis_name is None and N < (1 << 24)
    n_buf_tiles = n_sel_narrow = n_sel_full = 0
    if use_layout:
        Tl = leafperm._TILE_ROWS
        n_buf_tiles = leafperm.wired_tiles_bound(-(-N // Tl), L)
        if p.hist_subtraction:
            # smaller children cover <= half the (in-bag) rows on a single
            # device (same argument as bound_ok below); under shard_map or
            # past 2^24 rows no bound applies and the whole-layout tile
            # count is the only safe cap (shared bound helper — see doc)
            n_sel_narrow = leafperm.wired_sel_tiles_bound(
                -(-N // Tl), n_buf_tiles, P_narrow, half=half_bound_ok)
            n_sel_full = leafperm.wired_sel_tiles_bound(
                -(-N // Tl), n_buf_tiles, P_full, half=half_bound_ok)
        else:
            # non-subtraction (r10 lift) histograms BOTH children in one
            # 2P-column pass — the selection covers every live row, so
            # only the whole-buffer bound applies
            n_sel_narrow = leafperm.wired_sel_tiles_bound(
                -(-N // Tl), n_buf_tiles, 2 * P_narrow, half=False)
            n_sel_full = leafperm.wired_sel_tiles_bound(
                -(-N // Tl), n_buf_tiles, 2 * P_full, half=False)

    st = {
        "row_slot": row_slot, "slot_node": slot_node, "slot_gain": slot_gain,
        "slot_G": slot_G, "slot_H": slot_H, "slot_C": slot_C,
        "slot_depth": slot_depth, "slot_lo": slot_lo, "slot_hi": slot_hi,
        "sp_feature": sp_feature,
        "sp_thresh": sp_thresh, "sp_GL": sp_GL, "sp_HL": sp_HL,
        "sp_CL": sp_CL, "sp_catmask": sp_catmask, "sp_dleft": sp_dleft,
        "hists": hists,
        "feature": feature, "threshold": threshold, "gain": gain_arr,
        "left": left, "right": right, "is_cat": is_cat_arr,
        "cat_nodes": cat_nodes, "node_dleft": node_dleft, "cover": cover_arr,
        "num_nodes": num_nodes,
        "splits_done": splits_done, "max_depth": max_depth,
    }
    def make_level_body(P, use_nat=False, use_layout=False, n_sel_tiles=0):
        def level_body(d, st):
            (row_slot, slot_node, slot_gain, slot_G, slot_H, slot_C, slot_depth,
             slot_lo, slot_hi,
             sp_feature, sp_thresh, sp_GL, sp_HL, sp_CL, sp_catmask, sp_dleft,
             hists,
             feature, threshold, gain_arr, left, right, is_cat_arr, cat_nodes,
             node_dleft, num_nodes, splits_done, max_depth) = (
                st["row_slot"], st["slot_node"], st["slot_gain"], st["slot_G"],
                st["slot_H"], st["slot_C"], st["slot_depth"],
                st["slot_lo"], st["slot_hi"], st["sp_feature"],
                st["sp_thresh"], st["sp_GL"], st["sp_HL"], st["sp_CL"],
                st["sp_catmask"], st["sp_dleft"],
                st["hists"], st["feature"], st["threshold"],
                st["gain"], st["left"], st["right"], st["is_cat"], st["cat_nodes"],
                st["node_dleft"], st["num_nodes"], st["splits_done"],
                st["max_depth"])
            with jax.named_scope("dryad.split_scan"):
                at_level = (slot_depth == d) & (slot_gain > NEG_INF) & (slot_node >= 0)
                # gain-descending order, stable => lowest slot id wins ties, exactly
                # the CPU trainer's repeated first-max argmax sequence
                # dryadlint: disable=wired-grower-sort -- (L,)-slot gain ranking, L <= 512; not a row sort (rows never sort on the wired path)
                order = jnp.argsort(jnp.where(at_level, -slot_gain, jnp.inf), stable=True)
                cand = order[:P].astype(jnp.int32)
                budget_left = (L - 1) - splits_done
                do = at_level[cand] & (jnp.arange(P) < budget_left)
                n_do = jnp.sum(do.astype(jnp.int32))

                sj = cand
                parent_node = slot_node[sj]
                sf = sp_feature[sj]
                thr = sp_thresh[sj]
                GL, HL, CL = sp_GL[sj], sp_HL[sj], sp_CL[sj]
                Gp, Hp, Cp = slot_G[sj], slot_H[sj], slot_C[sj]
                GR, HR, CR = Gp - GL, Hp - HL, Cp - CL
                cat_split = (is_cat_feat[jnp.maximum(sf, 0)] & do) if has_cat else jnp.zeros((P,), bool)

                # slot/node allocation in execution (gain) order, as the CPU does
                ks = splits_done + jnp.cumsum(do.astype(jnp.int32)) - do.astype(jnp.int32)
                right_slot = jnp.where(do, ks + 1, L).astype(jnp.int32)
                left_id = jnp.where(do, num_nodes + 2 * (ks - splits_done), 0).astype(jnp.int32)
                right_id = left_id + 1

                pidx = jnp.where(do, parent_node, M)
                feature = feature.at[pidx].set(sf, mode="drop")
                gain_arr = gain_arr.at[pidx].set(
                    jnp.where(do, slot_gain[sj], 0.0), mode="drop")
                threshold = threshold.at[pidx].set(jnp.where(cat_split, 0, thr), mode="drop")
                left = left.at[pidx].set(left_id, mode="drop")
                right = right.at[pidx].set(right_id, mode="drop")
                is_cat_arr = is_cat_arr.at[pidx].set(cat_split, mode="drop")
                cat_nodes = cat_nodes.at[pidx].set(
                    jnp.where(cat_split[:, None], sp_catmask[sj], False), mode="drop"
                )
                node_dleft = node_dleft.at[pidx].set(sp_dleft[sj] | cat_split,
                                                     mode="drop")
                # per-node cover (training row count) for pred_contrib: the
                # children's counts come off the parent-histogram prefix
                cover_arr = st["cover"].at[
                    jnp.where(do, left_id, M)].set(CL, mode="drop")
                cover_arr = cover_arr.at[
                    jnp.where(do, right_id, M)].set(CR, mode="drop")

            # ---- row partition: every splitting leaf in one vectorized pass -----
            # Two measured rules shape this block (exp_level_bisect.py, 10M):
            # a per-row column gather (take_along_axis into the (N, F)
            # matrix) costs ~320 ms/level — random element access — while a
            # masked reduce over the feature axis reads the matrix
            # CONTIGUOUSLY and costs ~30 ms; and each (N,)-gather from a
            # small per-slot table costs ~30 ms, so the five per-slot
            # lookups ride ONE packed two-word record gather instead.
            # Integer/bool results are bit-identical to the gather
            # formulation, so every parity invariant is untouched.
            with jax.named_scope("dryad.route"):
                rs = jnp.minimum(row_slot, L - 1)
                rec_t = None
                if B <= (1 << 13) and L < (1 << 16):
                    # cat_split above is already the per-candidate cat flag (its
                    # & do is a no-op here: records only scatter where do holds)
                    cat_c = cat_split if has_cat else jnp.zeros((P,), bool)
                    w0_c = ((jnp.uint32(1) << 31)
                            | (sp_dleft[sj].astype(jnp.uint32) << 30)
                            | (cat_c.astype(jnp.uint32) << 29)
                            | (jnp.clip(thr, 0, B - 1).astype(jnp.uint32) << 16)
                            | right_slot.astype(jnp.uint32))
                    rec_t = jnp.zeros((L + 1, 2), jnp.uint32).at[
                        jnp.where(do, sj, L + 1)].set(
                            jnp.stack([w0_c,
                                       jnp.maximum(sf, 0).astype(jnp.uint32)],
                                      axis=1), mode="drop")

                    def packed_route(slot_idx):
                        """Per-row split routing off the packed per-slot table:
                        (splits?, goes-left?, packed word).  The layout's
                        kernels (leafperm._tile_sides) apply the SAME
                        integer/bool rules to the same table per tile, so
                        the natural-order partition and the layout can never
                        disagree on a row."""
                        rr = rec_t[jnp.minimum(slot_idx, L)]      # ONE gather
                        w0r = rr[:, 0]
                        rf = rr[:, 1].astype(jnp.int32)
                        bins_rf = select_bins(Xb, rf)
                        thr_r = ((w0r >> 16)
                                 & jnp.uint32(0x1FFF)).astype(jnp.int32)
                        gl = bins_rf <= thr_r
                        if learn_missing:
                            with jax.named_scope("dryad.missing"):
                                gl &= ((w0r >> 30) & 1).astype(bool) | (bins_rf > 0)
                        if has_cat:
                            with jax.named_scope("dryad.cat"):
                                cat_row = sp_catmask[jnp.minimum(slot_idx, L - 1),
                                                     jnp.minimum(bins_rf, Bc - 1)]
                                gl = jnp.where(((w0r >> 29) & 1).astype(bool),
                                               cat_row, gl)
                        return ((w0r >> 31) != 0), gl, w0r

                    do_n, left_n, w0r = packed_route(rs)
                    row_do = do_n & (row_slot < L)
                    row_slot = jnp.where(
                        row_do & ~left_n,
                        (w0r & jnp.uint32(0xFFFF)).astype(jnp.int32), row_slot)
                else:
                    # exotic shapes (bins > 8192 or leaves >= 65536) exceed the
                    # packed-word budget: keep the gather formulation (static
                    # per-config choice, so every shard still runs one program)
                    slot_do = jnp.zeros((L,), bool).at[
                        jnp.where(do, sj, L)].set(True, mode="drop")
                    slot_right = jnp.full((L,), L, jnp.int32).at[
                        jnp.where(do, sj, L)].set(right_slot, mode="drop")
                    row_do = slot_do[rs] & (row_slot < L)
                    rf = jnp.maximum(sp_feature[rs], 0)
                    bins_rf = jnp.take_along_axis(
                        Xb, rf[:, None].astype(jnp.int32), axis=1)[:, 0]
                    bins_rf = bins_rf.astype(jnp.int32)
                    go_left = bins_rf <= sp_thresh[rs]
                    if learn_missing:
                        with jax.named_scope("dryad.missing"):
                            go_left &= sp_dleft[rs] | (bins_rf > 0)
                    if has_cat:
                        with jax.named_scope("dryad.cat"):
                            cat_row = sp_catmask[rs, jnp.minimum(bins_rf, Bc - 1)]
                            go_left = jnp.where(is_cat_feat[rf], cat_row, go_left)
                    row_slot = jnp.where(row_do & ~go_left, slot_right[rs],
                                         row_slot)

            # ---- one batched histogram pass for all smaller children ------------
            left_smaller = CL <= CR
            if use_layout:
                # WIRED level (r6 deep phase, r10 everywhere): no
                # per-level sort, no full-N record gather, and nothing
                # row-sized in XLA.  Every row of a layout tile belongs to
                # one run, so one slot, so one split: the packed per-slot
                # table the route block built is composed run -> record at
                # the (L,) level and handed to leafperm.move_level, which
                # gathers it per TILE, counts each tile's left/right rows
                # in a small kernel, places the tiles with tile-sized
                # prefix work, and derives sides and stable in-tile ranks
                # inside the move kernel from the record tile it already
                # holds (packed_route's integer rules, so layout and
                # natural-order partition agree on every row).  The
                # children read back as contiguous tile runs.
                with jax.named_scope("dryad.layout"):
                    lay_tr = st["lay_tile_run"]
                    lay_rs = st["lay_run_slot"]
                    # dead runs (lay_rs = L) compose to rec_t[L] = zeros:
                    # pass-through — they carry no valid rows anyway
                    # (absorbed segments hold only sentinels)
                    lay_rec, base_l, base_r, tiles = leafperm.move_level(
                        st["lay_rec"], lay_tr, rec_t[jnp.minimum(lay_rs, L)],
                        sp_catmask[jnp.minimum(lay_rs, L - 1)]
                        if has_cat else None,
                        bin_dtype=Xb.dtype, learn_missing=learn_missing,
                        platform=platform, axis_name=axis_name)
                    # slot -> run inverse BEFORE advancing (candidates are
                    # parents of this level's move); dead runs scatter to
                    # L + 1 — OUT of the (L+1,) table so mode="drop" really
                    # drops them (index L is in range and would overwrite the
                    # sentinel cell the rj clamp below relies on)
                    slot_run = jnp.full((L + 1,), L, jnp.int32).at[
                        jnp.where(lay_rs < L, lay_rs, L + 1)].set(
                            jnp.arange(L, dtype=jnp.int32), mode="drop")
                    slot_do_t = (rec_t[:, 0] >> 31) != 0   # (L+1,) dense tables
                    slot_right_t = (rec_t[:, 0]
                                    & jnp.uint32(0xFFFF)).astype(jnp.int32)
                    lrs_c = jnp.minimum(lay_rs, L)
                    run_do = slot_do_t[lrs_c] & (lay_rs < L)
                    run_right = slot_right_t[lrs_c]
                    lay_tr_new, lay_rs_new = leafperm.advance_runs(
                        lay_rs, run_do, run_right, base_l, base_r,
                        lay_tr.shape[0])
                    # children = contiguous segments of the NEW layout
                    rj = slot_run[jnp.minimum(sj, L)]
                    rjc = jnp.minimum(rj, L - 1)
                    lt_l = base_l[1:] - base_l[:-1]
                    lt_r = base_r[1:] - base_r[:-1]
                    sel_ok = do & (rj < L)
                if p.hist_subtraction:
                    with jax.named_scope("dryad.layout"):
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
                    with jax.named_scope("dryad.hist"):
                        hist_large = hists[sj] - hist_small
                        ls = left_smaller[:, None, None, None]
                        hist_l = jnp.where(ls, hist_small, hist_large)
                        hist_r = jnp.where(ls, hist_large, hist_small)
                else:
                    # non-subtraction lift (r10): BOTH children in ONE
                    # 2P-column pass — columns [left 0..P-1 | right
                    # P..2P-1], every live row read exactly once (the
                    # legacy arm pays a small pass + a full
                    # build_hist_multi)
                    with jax.named_scope("dryad.layout"):
                        segf2 = jnp.concatenate([
                            jnp.where(sel_ok, base_l[rjc], 0),
                            jnp.where(sel_ok, base_r[rjc], 0)])
                        segn2 = jnp.concatenate([
                            jnp.where(sel_ok, lt_l[rjc], 0),
                            jnp.where(sel_ok, lt_r[rjc], 0)])
                    h2 = leafperm.hist_from_layout(
                        lay_rec, segf2, segn2, 2 * P, B, F, Xb.dtype,
                        n_sel_tiles, axis_name=axis_name, platform=platform,
                        hist_reduce=hr_mode)
                    with jax.named_scope("dryad.hist"):
                        hist_l, hist_r = h2[:P], h2[P:]
                st = dict(st, lay_rec=lay_rec, lay_tile_run=lay_tr_new,
                          lay_run_slot=lay_rs_new,
                          lay_tiles=st["lay_tiles"] + tiles)
            else:
                with jax.named_scope("dryad.hist"):
                    small_slot = jnp.where(left_smaller, sj, right_slot)
                    large_slot = jnp.where(left_smaller, right_slot, sj)
                    # non-do candidates scatter to L+1 (out of bounds, dropped);
                    # out-of-bag rows are excluded by the explicit bag_mask gate
                    # below — row_slot itself stays in [0, L-1] for every row
                    # now that the partition routes the whole dataset
                    colof = jnp.full((L + 1,), P, jnp.int32).at[
                        jnp.where(do, small_slot, L + 1)].set(
                            jnp.arange(P, dtype=jnp.int32), mode="drop")
                    # bag gates the histogram selection; out-of-bag rows are
                    # partitioned but never accumulated
                    smallsel = jnp.where(bag_mask,
                                         colof[jnp.minimum(row_slot, L)], P)
                    # Single device, smaller children cover at most half the
                    # rows (min(left,right) <= parent/2, parents disjoint) ->
                    # half the tile grid.  Under shard_map the smaller child is
                    # chosen on GLOBAL counts and one shard's share of it may
                    # exceed half that shard, so no bound applies there; ditto
                    # above 2^24 rows, where the fp32 histogram counts backing
                    # the smaller-child choice stop being exact.
                    bound_ok = half_bound_ok
                    if use_nat:
                        from dryad_tpu.engine import pallas_hist

                        hist_small = pallas_hist.build_hist_small(
                            nat_tiles, g, h, smallsel, P, B, F,
                            axis_name=axis_name, platform=platform,
                            hist_reduce=hr_mode)
                    else:
                        # exact per-column counts (smaller-child C off the
                        # parent histogram, integer-exact in f32 below 2**24)
                        # admit the pad-injected aligned sort inside
                        # build_hist_segmented — the plan's alignment gather
                        # drops out; single-device only, where the counts
                        # describe the whole selection
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
                            # staged prefixes only pay when the leaf budget caps
                            # deep levels (fills provably collapse); a full tree
                            # keeps every prefix ~100% and the extra gather
                            # branches only bloat (remote) compile
                            stage_gather=(L - 1) < (1 << (depth_cap - 1)),
                            hist_reduce=hr_mode,
                        )
                    if p.hist_subtraction:
                        hist_large = hists[sj] - hist_small
                    else:
                        largesel = jnp.full((L + 1,), P, jnp.int32).at[
                            jnp.where(do, large_slot, L + 1)].set(
                                jnp.arange(P, dtype=jnp.int32), mode="drop")
                        hist_large = build_hist_multi(
                            Xb, g, h,
                            jnp.where(bag_mask,
                                      largesel[jnp.minimum(row_slot, L)], P),
                            P, B,
                            rows_per_chunk=p.rows_per_chunk, axis_name=axis_name,
                            precision=p.hist_precision, hist_reduce=hr_mode,
                        )
                    ls = left_smaller[:, None, None, None]
                    hist_l = jnp.where(ls, hist_small, hist_large)
                    hist_r = jnp.where(ls, hist_large, hist_small)
            with jax.named_scope("dryad.hist"):
                hists = hists.at[jnp.where(do, sj, L)].set(hist_l, mode="drop")
                hists = hists.at[jnp.where(do, right_slot, L)].set(hist_r, mode="drop")

            # ---- children stats + their best splits (vmapped finder) ------------
            with jax.named_scope("dryad.split_scan"):
                lo_p, hi_p = slot_lo[sj], slot_hi[sj]
                if mono is not None:
                    lo_l, hi_l, lo_r, hi_r = child_bounds(
                        mono, sf, GL, HL, GR, HR, jnp.float32(p.lambda_l2), lo_p, hi_p)
                else:
                    lo_l = lo_r = lo_p
                    hi_l = hi_r = hi_p

                ch_slot = jnp.concatenate([sj, right_slot])
                ch_do = jnp.concatenate([do, do])
                ch_node = jnp.concatenate([left_id, right_id])
                ch_hist = jnp.concatenate([hist_l, hist_r])
                ch_G = jnp.concatenate([GL, GR])
                ch_H = jnp.concatenate([HL, HR])
                ch_C = jnp.concatenate([CL, CR])
                ch_lo = jnp.concatenate([lo_l, lo_r])
                ch_hi = jnp.concatenate([hi_l, hi_r])
                allow = ch_do & (d + 1 < depth_cap) & (ch_C >= 2 * p.min_data_in_leaf)
                res = level_scan(ch_hist, ch_G, ch_H, ch_C, allow, ch_lo, ch_hi)

                cidx = jnp.where(ch_do, ch_slot, L)
                slot_node = slot_node.at[cidx].set(ch_node, mode="drop")
                slot_gain = slot_gain.at[cidx].set(res.gain, mode="drop")
                slot_G = slot_G.at[cidx].set(ch_G, mode="drop")
                slot_H = slot_H.at[cidx].set(ch_H, mode="drop")
                slot_C = slot_C.at[cidx].set(ch_C, mode="drop")
                slot_depth = slot_depth.at[cidx].set(d + 1, mode="drop")
                slot_lo = slot_lo.at[cidx].set(ch_lo, mode="drop")
                slot_hi = slot_hi.at[cidx].set(ch_hi, mode="drop")
                sp_feature = sp_feature.at[cidx].set(res.feature, mode="drop")
                sp_thresh = sp_thresh.at[cidx].set(res.threshold, mode="drop")
                sp_GL = sp_GL.at[cidx].set(res.g_left, mode="drop")
                sp_HL = sp_HL.at[cidx].set(res.h_left, mode="drop")
                sp_CL = sp_CL.at[cidx].set(res.c_left, mode="drop")
                sp_catmask = sp_catmask.at[cidx].set(res.cat_mask, mode="drop")
                sp_dleft = sp_dleft.at[cidx].set(res.default_left, mode="drop")

                splits_done = splits_done + n_do
                num_nodes = num_nodes + 2 * n_do
                max_depth = jnp.where(n_do > 0, (d + 1).astype(jnp.int32), max_depth)

            out = {
                "row_slot": row_slot, "slot_node": slot_node,
                "slot_gain": slot_gain, "slot_G": slot_G, "slot_H": slot_H,
                "slot_C": slot_C, "slot_depth": slot_depth,
                "slot_lo": slot_lo, "slot_hi": slot_hi,
                "sp_feature": sp_feature, "sp_thresh": sp_thresh, "sp_GL": sp_GL,
                "sp_HL": sp_HL, "sp_CL": sp_CL, "sp_catmask": sp_catmask,
                "sp_dleft": sp_dleft,
                "hists": hists, "feature": feature, "threshold": threshold,
                "gain": gain_arr, "left": left, "right": right,
                "is_cat": is_cat_arr, "cat_nodes": cat_nodes,
                "node_dleft": node_dleft, "cover": cover_arr,
                "num_nodes": num_nodes, "splits_done": splits_done,
                "max_depth": max_depth,
            }
            if use_layout:
                out["lay_rec"] = st["lay_rec"]
                out["lay_tile_run"] = st["lay_tile_run"]
                out["lay_run_slot"] = st["lay_run_slot"]
                out["lay_tiles"] = st["lay_tiles"]
            return out
        return level_body

    if use_layout:
        # ---- root-anchored layout (r10): live from level 0 ------------------
        # The natural-order record buffer IS the root layout (one
        # segment, no sort, no gather); out-of-bag rows enter as
        # sentinel-flagged records and are dropped by level 0's move.
        # The shallow->deep handoff sort+gather per tree is GONE — the
        # natural-order row_slot (still maintained above for the final
        # ``row_key``) keeps routing out-of-bag rows.
        rec_nat = leafperm.make_layout_records(Xb, g, h, valid=bag_mask)
        lay_rec, lay_tr, lay_rs = leafperm.natural_root_layout(
            rec_nat, L, n_buf_tiles, axis_name=axis_name)
        st = dict(st, lay_rec=lay_rec, lay_tile_run=lay_tr,
                  lay_run_slot=lay_rs,
                  lay_tiles=leafperm.zero_tile_counts(axis_name))
    st = jax.lax.fori_loop(
        0, d_switch,
        make_level_body(P_narrow,
                        use_nat=nat_tiles is not None
                        and P_narrow <= pallas_hist_NAT_SLOTS(),
                        use_layout=use_layout, n_sel_tiles=n_sel_narrow),
        st)
    if d_switch < depth_cap:
        st = jax.lax.fori_loop(
            d_switch, depth_cap,
            make_level_body(P_full,
                            use_nat=not use_layout
                            and nat_tiles is not None
                            and P_full <= pallas_hist_NAT_SLOTS(),
                            use_layout=use_layout, n_sel_tiles=n_sel_full),
            st)

    # ---- finalize leaf values + node bitsets (shared helpers) ----------------
    value = finalize_leaf_values(
        p, M, st["slot_node"], st["slot_G"], st["slot_H"],
        jnp.zeros((M,), jnp.float32),
        slot_lo=st["slot_lo"] if mono is not None else None,
        slot_hi=st["slot_hi"] if mono is not None else None,
    )
    cat_bitset = pack_cat_bitset(st["cat_nodes"], M)
    # the layout's tiles the moves visited (train._LAYOUT_STATS)
    stats = (leafperm.tile_count_stats(st["lay_tiles"], axis_name)
             if use_layout else {})

    return {
        **stats,
        "feature": st["feature"],
        "threshold": st["threshold"],
        "left": st["left"],
        "right": st["right"],
        "value": value,
        "gain": st["gain"],
        "is_cat": st["is_cat"],
        "cat_bitset": cat_bitset,
        "default_left": st["node_dleft"],
        "cover": st["cover"],
        "max_depth": st["max_depth"],
        # each row's leaf is key_leaf[row_key], from the partition state (no
        # re-traversal); the train step composes that look-up with the
        # leaf's value and gathers once a row (train._row_records)
        "row_key": st["row_slot"],
        "key_leaf": jnp.maximum(st["slot_node"], 0),
    }
