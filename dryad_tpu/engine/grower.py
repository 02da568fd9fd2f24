"""Device leaf-wise tree grower — compiled replacement for the reference's
host-side grower + CUDA row-partition kernel (BASELINE.json:5; SURVEY.md §2
#7-8).

XLA traces once and forbids data-dependent shapes, so the reference's
dynamic per-leaf row lists become a **slot machine** (SURVEY.md §7 step 2):

* ``row_slot`` (N,) — every row carries the id of the leaf *slot* it lives
  in (slot L = out-of-bag sentinel).  The CUDA partition kernel's row
  shuffling becomes a vectorized ``where`` on this array.
* L leaf slots, each holding its node id, stats (G/H/C), depth, cached best
  split, and its full histogram — preallocated, validity-masked.
* the grow loop is a ``lax.fori_loop`` with exactly L-1 trips; a trip whose
  best gain is -inf is a compiled no-op (``lax.cond``), mirroring the CPU
  trainer's early break.

Semantics mirror ``cpu/trainer.py::_TreeGrower`` step for step: the left
child keeps the parent's slot, the right child takes slot k+1; child stats
come from the parent histogram prefix; the smaller child's histogram is
built directly and the larger obtained by subtraction (LightGBM trick —
halves histogram work); ties broken by first index.

Distribution (SURVEY.md §2 #13-14): under ``shard_map`` with rows sharded,
every device runs this same program on its shard; this SEQUENTIAL grower's
only cross-device exchange is the fused grad/hess/count histogram psum
inside ``build_hist`` — exactly where the reference placed its NCCL
allreduce (it ignores ``Params.hist_reduce``; the level-synchronous
growers own the r16 feature-parallel arm).  G/H/C stats are derived from
the (replicated) histogram, so all devices take identical split decisions
without further collectives.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from dryad_tpu.booster import CAT_WORDS
from dryad_tpu.config import Params
from dryad_tpu.engine.histogram import build_hist
from dryad_tpu.engine.split import NEG_INF, find_best_split

_BIG_DEPTH = jnp.int32(2**30)


def wired_layout(params, num_features: int, total_bins: int,
                 bin_itemsize: int, global_rows: int, n_shards: int = 1,
                 platform=None) -> bool:
    """Whether the grower ``grow_any`` picks carries the leaf-ordered
    layout, and so returns the tiles its moves visited
    (``leafperm.tile_count_stats``): its routing, then that grower's
    static wired gate."""
    from dryad_tpu.engine import leafwise_fast
    from dryad_tpu.engine.levelwise import deep_layout_supported

    if params.growth == "depthwise" and params.max_depth > 0:
        return deep_layout_supported(params, num_features, total_bins,
                                     bin_itemsize, platform)
    return (params.growth == "leafwise"
            and leafwise_fast.supports(params, num_features, total_bins,
                                       global_rows, n_shards)
            and leafwise_fast.leafwise_layout_supported(
                params, num_features, total_bins, bin_itemsize, platform))


def grow_any(params, total_bins, Xb, g, h, bag_mask, feat_mask, is_cat_feat,
             *, has_cat=False, axis_name=None, platform=None,
             learn_missing=False, root_hist=None, bundled_mask=None,
             global_rows=None):
    """Route to the fastest grower for the growth policy.

    Depth-wise growth takes the level-synchronous path (one batched
    histogram pass per level — levelwise.py); leaf-wise keeps the exact
    one-split-at-a-time reference semantics below.  ``root_hist`` skips
    the root histogram pass when the caller already has it (multiclass
    shared-plan roots — histogram.build_hist_classes).
    """
    if params.growth == "depthwise" and params.max_depth > 0:
        from dryad_tpu.engine.levelwise import grow_tree_levelwise

        return grow_tree_levelwise(
            params, total_bins, Xb, g, h, bag_mask, feat_mask, is_cat_feat,
            has_cat=has_cat, axis_name=axis_name, platform=platform,
            learn_missing=learn_missing, root_hist=root_hist,
            bundled_mask=bundled_mask,
        )
    if params.growth == "leafwise":
        from dryad_tpu.engine import leafwise_fast

        # GLOBAL rows and the shard count (both static at trace time): the
        # batched-vs-sequential choice is the envelope's, which reckons a
        # device's share of the global rows — the same two numbers
        # train_device resolved the cap with, never the local shape (under
        # shard_map Xb is the local shard).  Sharded callers pass the
        # UNPADDED global N (local*n_shards counts the mesh pad, which could
        # flip the envelope at the boundary); single-device direct callers
        # carry no pad.
        n_shards = int(jax.lax.psum(1, axis_name)) if axis_name else 1
        if global_rows is None:
            global_rows = Xb.shape[0] * n_shards
        if leafwise_fast.supports(params, Xb.shape[1], int(total_bins),
                                  global_rows, n_shards):
            # depth-capped leaf-wise: exact best-first selection over a
            # level-synchronous full expansion — O(N·depth) instead of the
            # sequential grower's O(N·leaves) (gains are order-independent,
            # so the selected tree is the sequential one).  Unbounded depth
            # (max_depth <= 0) keeps the sequential path below.
            return leafwise_fast.grow_tree_leafwise_batched(
                params, total_bins, Xb, g, h, bag_mask, feat_mask,
                is_cat_feat, has_cat=has_cat, axis_name=axis_name,
                platform=platform, learn_missing=learn_missing,
                root_hist=root_hist, bundled_mask=bundled_mask,
            )
        if params.max_depth > 0 and params.hist_subtraction:
            # deterministic fallback with a visible, SPECIFIC reason
            # (VERDICT r3 #7) — the sequential grower is exact, just
            # O(N·leaves).  hist_subtraction=False is a deliberate,
            # documented config choice (the expansion derives larger
            # siblings by subtraction), so it does not warn.
            import warnings

            from dryad_tpu.config import MAX_FAST_DEPTH

            reason = ("max_depth above the batched grower's cap "
                      f"({MAX_FAST_DEPTH})"
                      if params.max_depth > MAX_FAST_DEPTH
                      else "peak-memory envelope "
                           "(config.leafwise_fast_supported)")
            warnings.warn(
                f"batched leaf-wise grower unavailable: {reason} — "
                "falling back to the sequential grower",
                stacklevel=2)
    return grow_tree(
        params, total_bins, Xb, g, h, bag_mask, feat_mask, is_cat_feat,
        has_cat=has_cat, axis_name=axis_name, platform=platform,
        learn_missing=learn_missing, root_hist=root_hist,
        bundled_mask=bundled_mask,
    )


def _monotone_array(p: Params, F: int):
    """(F,) int32 constraint array, or None when unconstrained (static)."""
    if not p.monotone_constraints or not any(p.monotone_constraints):
        return None
    mono = [0] * F
    for i, m in enumerate(p.monotone_constraints[:F]):
        mono[i] = int(m)
    return jnp.asarray(mono, jnp.int32)


@jax.named_scope("dryad.split_scan")
def child_bounds(mono, sf, GL, HL, GR, HR, lam, lo_p, hi_p):
    """Monotone output bounds for the two children of a split (LightGBM
    "basic" mode): the midpoint of the clamped child outputs separates the
    subtrees across a ±1 split feature; m=0 splits inherit the parent
    bounds.  Shared by both device growers; cpu/trainer.py mirrors the same
    f32 arithmetic.  Works elementwise on scalars or (P,) candidate rows."""
    wl = jnp.clip(-(GL / (HL + lam)), lo_p, hi_p)
    wr = jnp.clip(-(GR / (HR + lam)), lo_p, hi_p)
    mid = jnp.float32(0.5) * (wl + wr)
    m = mono[jnp.maximum(sf, 0)]
    lo_l = jnp.where(m < 0, mid, lo_p)
    hi_l = jnp.where(m > 0, mid, hi_p)
    lo_r = jnp.where(m > 0, mid, lo_p)
    hi_r = jnp.where(m < 0, mid, hi_p)
    return lo_l, hi_l, lo_r, hi_r


@jax.named_scope("dryad.split_scan")
def root_stats(hist0: jnp.ndarray):
    """Canonical leaf totals = feature-0 histogram sums (cpu/trainer.py
    contract) — shared by both growers so the derivation can never diverge."""
    return hist0[0, 0].sum(), hist0[1, 0].sum(), hist0[2, 0].sum()


@jax.named_scope("dryad.split_scan")
def finalize_leaf_values(p: Params, M: int, slot_node, slot_G, slot_H,
                         value: jnp.ndarray, slot_lo=None, slot_hi=None) -> jnp.ndarray:
    """Newton leaf values with shrinkage, fp32, scattered to leaf nodes.

    ``slot_lo``/``slot_hi`` (monotone output bounds) clamp the raw Newton
    value before shrinkage; pass None when unconstrained so the compiled
    program is unchanged."""
    raw = -(slot_G / (slot_H + jnp.float32(p.lambda_l2)))
    if slot_lo is not None:
        raw = jnp.clip(raw, slot_lo, slot_hi)
    vals = raw * jnp.float32(p.effective_learning_rate)
    idx = jnp.where(slot_node >= 0, slot_node, M)
    return value.at[idx].set(vals, mode="drop")


@jax.named_scope("dryad.split_scan")
def pack_cat_bitset(cat_mask_nodes: jnp.ndarray, M: int) -> jnp.ndarray:
    """(M, B) bool membership masks -> (M, CAT_WORDS) uint32 node bitsets,
    bit layout b -> word b>>5, bit b&31 (matches cpu/histogram.py)."""
    catm = cat_mask_nodes
    width = CAT_WORDS * 32
    if catm.shape[1] < width:
        catm = jnp.pad(catm, ((0, 0), (0, width - catm.shape[1])))
    bits = catm[:, :width].reshape(M, CAT_WORDS, 32).astype(jnp.uint32)
    return (bits << jnp.arange(32, dtype=jnp.uint32)).sum(axis=2, dtype=jnp.uint32)


def grow_tree(
    params: Params,
    total_bins: int,
    Xb: jnp.ndarray,          # (N, F) uint8/uint16 — local row shard
    g: jnp.ndarray,           # (N,) f32
    h: jnp.ndarray,           # (N,) f32
    bag_mask: jnp.ndarray,    # (N,) bool — bagging subsample
    feat_mask: jnp.ndarray,   # (F,) bool — colsample
    is_cat_feat: jnp.ndarray, # (F,) bool
    *,
    has_cat: bool = False,
    axis_name: str | None = None,
    platform: str | None = None,
    learn_missing: bool = False,
    root_hist: jnp.ndarray | None = None,
    bundled_mask: jnp.ndarray | None = None,
) -> dict[str, Any]:
    """Grow one tree; returns SoA tree arrays (max_nodes,) + max_depth.

    Pure function of its inputs — jit it (single device) or call it inside
    ``shard_map`` (rows sharded over ``axis_name``).
    """
    p = params
    N, F = Xb.shape
    B = int(total_bins)
    L = p.effective_num_leaves
    M = p.max_nodes
    depth_cap = p.max_depth if p.max_depth > 0 else L
    depthwise = p.growth == "depthwise"

    mono = _monotone_array(p, F)

    def best(hist, G, H, C, depth, lo=None, hi=None):
        allow = (depth < depth_cap) & (C >= 2 * p.min_data_in_leaf)
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

    def hist_of(mask):
        # bag gates HISTOGRAMS only; the row partition routes every row so
        # the final row_slot directly yields each row's leaf (no separate
        # post-grow traversal — at 10M rows that gather loop cost ~5 s/tree)
        return build_hist(
            Xb, g, h, mask & bag_mask, B,
            rows_per_chunk=p.rows_per_chunk, axis_name=axis_name,
            precision=p.hist_precision, backend=p.hist_backend,
            platform=platform,
        )

    # NOTE (measured): routing the small child through the bounded segmented
    # kernel (tile plan at N/2) is ~30% SLOWER here than the masked XLA pass
    # — the per-split stable sort in the tile plan dominates.  Leaf-wise
    # growth keeps the masked histogram; depthwise amortizes the sort per
    # level and is the TPU throughput path.

    # ---- root ---------------------------------------------------------------
    # ALL rows partitioned (see hist_of); derived from bag_mask so the init
    # inherits the varying-manual-axes of the shard under shard_map (a plain
    # constant would make the grow-loop cond branches' vma types diverge)
    with jax.named_scope("dryad.route"):
        row_slot = jnp.where(bag_mask, 0, 0).astype(jnp.int32)
    hist0 = root_hist if root_hist is not None else hist_of(row_slot == 0)
    with jax.named_scope("dryad.split_scan"):
        G0, H0, C0 = root_stats(hist0)
        ninf, pinf = jnp.float32(-jnp.inf), jnp.float32(jnp.inf)
        root = best(hist0, G0, H0, C0, jnp.int32(0), ninf, pinf)

        st = {
            "row_slot": row_slot,
            "slot_node": jnp.full((L,), -1, jnp.int32).at[0].set(0),
            "slot_gain": jnp.full((L,), NEG_INF, jnp.float32).at[0].set(root.gain),
            "slot_G": jnp.zeros((L,), jnp.float32).at[0].set(G0),
            "slot_H": jnp.zeros((L,), jnp.float32).at[0].set(H0),
            "slot_C": jnp.zeros((L,), jnp.float32).at[0].set(C0),
            "slot_depth": jnp.zeros((L,), jnp.int32),
            "slot_lo": jnp.full((L,), ninf, jnp.float32),
            "slot_hi": jnp.full((L,), pinf, jnp.float32),
            "sp_feature": jnp.full((L,), -1, jnp.int32).at[0].set(root.feature),
            "sp_thresh": jnp.zeros((L,), jnp.int32).at[0].set(root.threshold),
            "sp_GL": jnp.zeros((L,), jnp.float32).at[0].set(root.g_left),
            "sp_HL": jnp.zeros((L,), jnp.float32).at[0].set(root.h_left),
            "sp_CL": jnp.zeros((L,), jnp.float32).at[0].set(root.c_left),
            "sp_catmask": jnp.zeros((L, root.cat_mask.shape[0]), bool).at[0].set(root.cat_mask),
            "sp_dleft": jnp.ones((L,), bool).at[0].set(root.default_left),
            "hists": jnp.zeros((L, 3, F, B), jnp.float32).at[0].set(hist0),
            "feature": jnp.full((M,), -1, jnp.int32),
            "threshold": jnp.zeros((M,), jnp.int32),
            "left": jnp.zeros((M,), jnp.int32),
            "right": jnp.zeros((M,), jnp.int32),
            "value": jnp.zeros((M,), jnp.float32),
            "gain": jnp.zeros((M,), jnp.float32),
            "cover": jnp.zeros((M,), jnp.float32).at[0].set(C0),
            "is_cat": jnp.zeros((M,), bool),
            "cat_mask_nodes": jnp.zeros((M, root.cat_mask.shape[0]), bool),
            "node_dleft": jnp.ones((M,), bool),
            "num_nodes": jnp.int32(1),
            "max_depth": jnp.int32(0),
        }

    # ---- grow loop ----------------------------------------------------------
    def pick_slot(s_gain, s_depth):
        finite = s_gain > NEG_INF
        if depthwise:
            # split the shallowest level first, best gain within it
            dmin = jnp.min(jnp.where(finite, s_depth, _BIG_DEPTH))
            masked = jnp.where(finite & (s_depth == dmin), s_gain, NEG_INF)
            return jnp.argmax(masked).astype(jnp.int32)
        return jnp.argmax(s_gain).astype(jnp.int32)

    def do_split(k, s, st):
        with jax.named_scope("dryad.split_scan"):
            parent = st["slot_node"][s]
            sf = st["sp_feature"][s]
            thr = st["sp_thresh"][s]
            catm = st["sp_catmask"][s]
            cat_split = is_cat_feat[sf] if has_cat else jnp.bool_(False)

        with jax.named_scope("dryad.route"):
            bins_f = jnp.take(Xb, sf, axis=1).astype(jnp.int32)
            num_left = bins_f <= thr
            dl = st["sp_dleft"][s]
            if learn_missing:
                with jax.named_scope("dryad.missing"):
                    num_left &= dl | (bins_f > 0)
            if has_cat:
                with jax.named_scope("dryad.cat"):
                    go_left = jnp.where(cat_split,
                                        catm[jnp.minimum(bins_f, catm.shape[0] - 1)],
                                        num_left)
            else:
                go_left = num_left
            in_slot = st["row_slot"] == s

        with jax.named_scope("dryad.split_scan"):
            GL, HL, CL = st["sp_GL"][s], st["sp_HL"][s], st["sp_CL"][s]
            Gp, Hp, Cp = st["slot_G"][s], st["slot_H"][s], st["slot_C"][s]
            GR, HR, CR = Gp - GL, Hp - HL, Cp - CL

            left_id = st["num_nodes"]
            right_id = left_id + 1
            new_r = jnp.int32(k + 1)

            gain_arr = st["gain"].at[parent].set(st["slot_gain"][s])
            cover_arr = st["cover"].at[left_id].set(CL).at[right_id].set(CR)
            feature = st["feature"].at[parent].set(sf)
            threshold = st["threshold"].at[parent].set(jnp.where(cat_split, 0, thr))
            left = st["left"].at[parent].set(left_id)
            right = st["right"].at[parent].set(right_id)
            is_cat_arr = st["is_cat"].at[parent].set(cat_split)
            cat_nodes = st["cat_mask_nodes"].at[parent].set(
                jnp.where(cat_split, catm, jnp.zeros_like(catm))
            )
            node_dleft = st["node_dleft"].at[parent].set(dl | cat_split)

        # row partition/apply: left child keeps slot s, right child takes k+1
        with jax.named_scope("dryad.route"):
            row_slot = jnp.where(in_slot & ~go_left, new_r, st["row_slot"])

        # smaller child's histogram direct; larger by subtraction
        with jax.named_scope("dryad.hist"):
            left_smaller = CL <= CR
            if p.hist_subtraction:
                small_slot = jnp.where(left_smaller, s, new_r)
                shist = hist_of(row_slot == small_slot)
                ohist = st["hists"][s] - shist
                hist_l = jnp.where(left_smaller, shist, ohist)
                hist_r = jnp.where(left_smaller, ohist, shist)
            else:
                hist_l = hist_of(row_slot == s)
                hist_r = hist_of(row_slot == new_r)
            hists = st["hists"].at[s].set(hist_l).at[new_r].set(hist_r)

        with jax.named_scope("dryad.split_scan"):
            depth_c = st["slot_depth"][s] + 1
            lo_p, hi_p = st["slot_lo"][s], st["slot_hi"][s]
            if mono is not None:
                lo_l, hi_l, lo_r, hi_r = child_bounds(
                    mono, sf, GL, HL, GR, HR, jnp.float32(p.lambda_l2), lo_p, hi_p)
            else:
                lo_l = lo_r = lo_p
                hi_l = hi_r = hi_p
            res_l = best(hist_l, GL, HL, CL, depth_c, lo_l, hi_l)
            res_r = best(hist_r, GR, HR, CR, depth_c, lo_r, hi_r)

            def put(a, vl, vr):
                return a.at[s].set(vl).at[new_r].set(vr)

            return {
                "row_slot": row_slot,
                "slot_node": put(st["slot_node"], left_id, right_id),
                "slot_gain": put(st["slot_gain"], res_l.gain, res_r.gain),
                "slot_G": put(st["slot_G"], GL, GR),
                "slot_H": put(st["slot_H"], HL, HR),
                "slot_C": put(st["slot_C"], CL, CR),
                "slot_depth": put(st["slot_depth"], depth_c, depth_c),
                "slot_lo": put(st["slot_lo"], lo_l, lo_r),
                "slot_hi": put(st["slot_hi"], hi_l, hi_r),
                "sp_feature": put(st["sp_feature"], res_l.feature, res_r.feature),
                "sp_thresh": put(st["sp_thresh"], res_l.threshold, res_r.threshold),
                "sp_GL": put(st["sp_GL"], res_l.g_left, res_r.g_left),
                "sp_HL": put(st["sp_HL"], res_l.h_left, res_r.h_left),
                "sp_CL": put(st["sp_CL"], res_l.c_left, res_r.c_left),
                "sp_catmask": put(st["sp_catmask"], res_l.cat_mask, res_r.cat_mask),
                "sp_dleft": put(st["sp_dleft"], res_l.default_left, res_r.default_left),
                "hists": hists,
                "feature": feature,
                "threshold": threshold,
                "left": left,
                "right": right,
                "value": st["value"],
                "gain": gain_arr,
                "cover": cover_arr,
                "is_cat": is_cat_arr,
                "cat_mask_nodes": cat_nodes,
                "node_dleft": node_dleft,
                "num_nodes": st["num_nodes"] + 2,
                "max_depth": jnp.maximum(st["max_depth"], depth_c),
            }

    def body(k, st):
        with jax.named_scope("dryad.split_scan"):
            s = pick_slot(st["slot_gain"], st["slot_depth"])
        return jax.lax.cond(
            st["slot_gain"][s] > NEG_INF,
            lambda st_: do_split(k, s, st_),
            lambda st_: st_,
            st,
        )

    st = jax.lax.fori_loop(0, L - 1, body, st)

    # ---- finalize leaf values + node bitsets (shared helpers) ---------------
    value = finalize_leaf_values(
        p, M, st["slot_node"], st["slot_G"], st["slot_H"], st["value"],
        slot_lo=st["slot_lo"] if mono is not None else None,
        slot_hi=st["slot_hi"] if mono is not None else None,
    )
    cat_bitset = pack_cat_bitset(st["cat_mask_nodes"], M)

    return {
        "feature": st["feature"],
        "threshold": st["threshold"],
        "left": st["left"],
        "right": st["right"],
        "value": value,
        "gain": st["gain"],
        "cover": st["cover"],
        "is_cat": st["is_cat"],
        "cat_bitset": cat_bitset,
        "default_left": st["node_dleft"],
        "max_depth": st["max_depth"],
        # each row's leaf is key_leaf[row_key], straight from the partition
        # state; the train step gathers once a row (train._row_records)
        "row_key": st["row_slot"],
        "key_leaf": jnp.maximum(st["slot_node"], 0),
    }
