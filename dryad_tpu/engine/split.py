"""Device split-gain scan — TPU equivalent of the reference's CUDA split
kernel (BASELINE.json:5; SURVEY.md §2 #6).

Vectorized over the whole (feature, bin) grid at once: per-feature prefix
sums of the histogram (cumsum), the Newton gain formula on both sides, a
validity mask (min_data_in_leaf / min_child_weight / feature sampling), and
one flat argmax with first-index tie-breaking — semantics identical to
``dryad_tpu.cpu.histogram.find_best_split`` (the parity oracle), modulo fp32
vs f64 accumulation (documented tolerance, SURVEY.md §7 hard part c).

Categorical features use the LightGBM-style sorted-subset scan: bins ordered
by g/(h + smooth), the best prefix of that order becomes the left membership
set, returned as a (B,) bool mask (the host converts it to the node bitset).

Feature-parallel variant (r16, ``Params.hist_reduce="feature"``): under the
reduce-scatter arm each shard owns a contiguous feature slice of the fully
reduced histogram, so the scan factorizes into ``find_best_split_sliced``
(the SAME per-(feature, bin) arithmetic as ``find_best_split``, restricted
to the owned slice, WITHOUT the final ok-gating, plus a packed global tie
key) and ``combine_local_splits`` (argmax-of-argmaxes over the gathered
per-shard records, ok applied once to the global winner).  The tie key is
the fused scan's flattened argmax index itself — ``plane*F*B + f*B + t``
(plane-major, feature-major within a plane) — so max-gain / min-key
combination reproduces the fused first-max order EXACTLY; the 1-shard
"feature" program is the degenerate full slice.  The two scan bodies must
stay arithmetically in sync (the histogram.py twin-bodies precedent);
``test_hist_reduce.py`` pins the contract on seeded equal-gain grids.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = float("-inf")  # plain float: a jnp scalar here would init the backend at import
CAT_SMOOTH = 10.0  # matches cpu/histogram.py find_best_split default


class SplitResult(NamedTuple):
    gain: jnp.ndarray       # f32 scalar; -inf when no valid split exists
    feature: jnp.ndarray    # i32
    threshold: jnp.ndarray  # i32: numerical bin id / categorical prefix length
    g_left: jnp.ndarray     # f32
    h_left: jnp.ndarray     # f32
    c_left: jnp.ndarray     # f32
    cat_mask: jnp.ndarray   # (B,) bool — left membership set (cat splits only)
    default_left: jnp.ndarray  # bool — missing (bin 0) goes left at this split


@jax.named_scope("dryad.split_scan")
def find_best_split(
    hist: jnp.ndarray,          # (3, F, B) f32
    G: jnp.ndarray,
    H: jnp.ndarray,
    C: jnp.ndarray,
    *,
    lambda_l2: float,
    min_child_weight: float,
    min_data_in_leaf: int,
    min_split_gain: float,
    feat_mask: jnp.ndarray,      # (F,) bool
    is_cat_feat: jnp.ndarray,    # (F,) bool
    allow: jnp.ndarray,          # scalar bool: depth/min-data pre-check
    has_cat: bool = True,        # static: skip the sorted-subset machinery
    monotone: jnp.ndarray | None = None,  # (F,) int32 in {-1, 0, +1}
    lo: jnp.ndarray | None = None,  # scalar f32: node output lower bound
    hi: jnp.ndarray | None = None,  # scalar f32: node output upper bound
    learn_missing: bool = False,    # static: scan missing-left AND missing-right
    bundled_mask: jnp.ndarray | None = None,  # (F,) bool: EFB bundle columns
) -> SplitResult:
    hg, hh, hc = hist[0], hist[1], hist[2]
    F, B = hg.shape
    iota = jnp.arange(B, dtype=jnp.int32)

    if has_cat:
        # categorical scan order: bins sorted by g/(h+smooth); empty bins last
        ratio = jnp.where(hc > 0, hg / (hh + CAT_SMOOTH), jnp.inf)
        cat_order = jnp.argsort(ratio, axis=1, stable=True).astype(jnp.int32)
        order = jnp.where(is_cat_feat[:, None], cat_order, iota[None, :])
        hg_o = jnp.take_along_axis(hg, order, axis=1)
        hh_o = jnp.take_along_axis(hh, order, axis=1)
        hc_o = jnp.take_along_axis(hc, order, axis=1)
    else:
        hg_o, hh_o, hc_o = hg, hh, hc

    GL = jnp.cumsum(hg_o, axis=1)
    HL = jnp.cumsum(hh_o, axis=1)
    CL = jnp.cumsum(hc_o, axis=1)

    def gain_of(GLx, HLx, CLx):
        """Masked gain grid for one scan direction given its left-side sums."""
        GRx, HRx, CRx = G - GLx, H - HLx, C - CLx
        valid = (
            (CLx >= min_data_in_leaf)
            & (CRx >= min_data_in_leaf)
            & (HLx >= min_child_weight)
            & (HRx >= min_child_weight)
            & feat_mask[:, None]
        )
        if monotone is not None:
            # LightGBM-"basic" monotone mode (mirrors cpu/histogram.py):
            # child outputs are clamped to the node's inherited [lo, hi]
            # bounds, the gain is computed with the clamped outputs
            # (objective reduction -(G w + (H+λ)w²/2), which collapses to
            # G²/(2(H+λ)) unclamped), and a ±1 feature may only split where
            # the clamped right value is >=/<= the clamped left value.
            # Descendants inherit tightened bounds from the grower, so deep
            # subtrees cannot cross a constrained ancestor's split —
            # unconstrained (0) features pass regardless of NaN child values.
            lam = jnp.float32(lambda_l2)
            wl = jnp.clip(-GLx / (HLx + lam), lo, hi)
            wr = jnp.clip(-GRx / (HRx + lam), lo, hi)
            wp = jnp.clip(-G / (H + lam), lo, hi)
            mcol = monotone.astype(jnp.float32)[:, None]
            valid &= (mcol == 0) | (mcol * (wr - wl) >= 0)
            red_l = -(GLx * wl + 0.5 * (HLx + lam) * wl * wl)
            red_r = -(GRx * wr + 0.5 * (HRx + lam) * wr * wr)
            red_p = -(G * wp + 0.5 * (H + lam) * wp * wp)
            gain = red_l + red_r - red_p
        else:
            parent_score = G * G / (H + lambda_l2)
            gain = 0.5 * (GLx * GLx / (HLx + lambda_l2)
                          + GRx * GRx / (HRx + lambda_l2) - parent_score)
        return jnp.where(valid, gain, NEG_INF)

    gain = gain_of(GL, HL, CL)
    if learn_missing:
        # second scan with the missing bin (ordered position 0 for numerical
        # features — the identity order keeps bin 0 first) moved to the RIGHT
        # child: left = bins 1..t.  Categorical features learn the missing
        # direction through subset membership already, so only the
        # missing-left plane applies to them.  The missing-left plane comes
        # FIRST in the flattened argmax, so on data with no missing values
        # (bin-0 stats all zero → both planes identical) the tie-break picks
        # missing-left and trees are unchanged.
        g0, h0, c0 = hg_o[:, :1], hh_o[:, :1], hc_o[:, :1]
        CL_r = CL - c0
        gain_r = gain_of(GL - g0, HL - h0, CL_r)
        # a right child holding ONLY missing rows mirrors the plane-0 t=0
        # split (sides swapped, bitwise-equal gain only in exact arithmetic);
        # exclude it so fp noise cannot flip the CPU/TPU argmax between the
        # two representations of the same partition
        gain_r = jnp.where((C - CL_r) > c0, gain_r, NEG_INF)
        if has_cat:
            gain_r = jnp.where(is_cat_feat[:, None], NEG_INF, gain_r)
        if bundled_mask is not None:
            # EFB bundle columns: bin 0 means "all members default", never
            # "missing" — a learned missing-right direction there would be
            # fiction (mirrors cpu/histogram.py exactly)
            gain_r = jnp.where(bundled_mask[:, None], NEG_INF, gain_r)
        flat2 = jnp.argmax(jnp.stack([gain.ravel(), gain_r.ravel()]).ravel())
        flat2 = flat2.astype(jnp.int32)
        dleft = flat2 < F * B
        flat = flat2 % (F * B)
        best_gain = jnp.where(dleft, gain.ravel()[flat], gain_r.ravel()[flat])
    else:
        flat = jnp.argmax(gain.ravel()).astype(jnp.int32)  # first-max tie-break
        dleft = jnp.bool_(True)
        best_gain = gain.ravel()[flat]
    f = flat // B
    t = flat % B
    ok = allow & jnp.isfinite(best_gain) & (best_gain > min_split_gain)

    if has_cat:
        # left membership for categorical: bins whose rank in `order` is <= t
        inv_order = jnp.zeros((B,), jnp.int32).at[order[f]].set(iota)
        cat_mask = (inv_order <= t) & is_cat_feat[f] & ok
    else:
        cat_mask = jnp.zeros((1,), bool)

    g_left, h_left, c_left = GL[f, t], HL[f, t], CL[f, t]
    if learn_missing:
        g_left = jnp.where(dleft, g_left, g_left - hg_o[f, 0])
        h_left = jnp.where(dleft, h_left, h_left - hh_o[f, 0])
        c_left = jnp.where(dleft, c_left, c_left - hc_o[f, 0])

    return SplitResult(
        gain=jnp.where(ok, best_gain, NEG_INF),
        feature=jnp.where(ok, f, -1).astype(jnp.int32),
        threshold=t.astype(jnp.int32),
        g_left=g_left,
        h_left=h_left,
        c_left=c_left,
        cat_mask=cat_mask,
        default_left=dleft | ~ok,
    )


class LocalSplit(NamedTuple):
    """One shard's RAW (pre-ok) winner over its owned feature slice —
    what the feature-parallel combine all-gathers.  ``key`` is the global
    flattened scan index of the winner (plane*F*B + f_global*B + t), so a
    max-gain / min-key reduction over shards reproduces the fused scan's
    first-max tie order bitwise."""

    gain: jnp.ndarray         # f32 raw winner gain (-inf: nothing valid)
    key: jnp.ndarray          # i32 global tie key
    feature: jnp.ndarray      # i32 GLOBAL feature id of the local winner
    threshold: jnp.ndarray    # i32 bin id / categorical prefix length
    g_left: jnp.ndarray       # f32 (plane-adjusted, like the fused scan)
    h_left: jnp.ndarray       # f32
    c_left: jnp.ndarray       # f32
    default_left: jnp.ndarray  # bool — raw plane flag (True: missing left)
    cat_mask: jnp.ndarray     # (B,) raw left membership (pre-ok)


@jax.named_scope("dryad.split_scan")
def find_best_split_sliced(
    hist: jnp.ndarray,          # (3, Fs, B) f32 — the OWNED slice, reduced
    G: jnp.ndarray,
    H: jnp.ndarray,
    C: jnp.ndarray,
    *,
    feat_offset: jnp.ndarray,    # traced i32: first owned GLOBAL feature
    num_features_total: int,     # static F (the tie key's plane stride)
    lambda_l2: float,
    min_child_weight: float,
    min_data_in_leaf: int,
    feat_mask: jnp.ndarray,      # (Fs,) bool — sliced (padding False)
    is_cat_feat: jnp.ndarray,    # (Fs,) bool — sliced
    has_cat: bool = True,
    monotone: jnp.ndarray | None = None,   # (Fs,) sliced
    lo: jnp.ndarray | None = None,
    hi: jnp.ndarray | None = None,
    learn_missing: bool = False,
    bundled_mask: jnp.ndarray | None = None,  # (Fs,) sliced
) -> LocalSplit:
    """``find_best_split`` restricted to a feature slice: identical
    per-(feature, bin) gain arithmetic, local first-max argmax, NO
    ok-gating (``combine_local_splits`` applies ok ONCE to the global
    winner, exactly where the fused scan applies it), plus the packed
    global tie key.  KEEP THE TWO BODIES IN SYNC with find_best_split —
    the bitwise fused ≡ feature contract rides on it (the histogram.py
    twin-bodies precedent; pinned by test_hist_reduce.py)."""
    hg, hh, hc = hist[0], hist[1], hist[2]
    F, B = hg.shape
    iota = jnp.arange(B, dtype=jnp.int32)

    if has_cat:
        ratio = jnp.where(hc > 0, hg / (hh + CAT_SMOOTH), jnp.inf)
        cat_order = jnp.argsort(ratio, axis=1, stable=True).astype(jnp.int32)
        order = jnp.where(is_cat_feat[:, None], cat_order, iota[None, :])
        hg_o = jnp.take_along_axis(hg, order, axis=1)
        hh_o = jnp.take_along_axis(hh, order, axis=1)
        hc_o = jnp.take_along_axis(hc, order, axis=1)
    else:
        hg_o, hh_o, hc_o = hg, hh, hc

    GL = jnp.cumsum(hg_o, axis=1)
    HL = jnp.cumsum(hh_o, axis=1)
    CL = jnp.cumsum(hc_o, axis=1)

    def gain_of(GLx, HLx, CLx):
        GRx, HRx, CRx = G - GLx, H - HLx, C - CLx
        valid = (
            (CLx >= min_data_in_leaf)
            & (CRx >= min_data_in_leaf)
            & (HLx >= min_child_weight)
            & (HRx >= min_child_weight)
            & feat_mask[:, None]
        )
        if monotone is not None:
            lam = jnp.float32(lambda_l2)
            wl = jnp.clip(-GLx / (HLx + lam), lo, hi)
            wr = jnp.clip(-GRx / (HRx + lam), lo, hi)
            wp = jnp.clip(-G / (H + lam), lo, hi)
            mcol = monotone.astype(jnp.float32)[:, None]
            valid &= (mcol == 0) | (mcol * (wr - wl) >= 0)
            red_l = -(GLx * wl + 0.5 * (HLx + lam) * wl * wl)
            red_r = -(GRx * wr + 0.5 * (HRx + lam) * wr * wr)
            red_p = -(G * wp + 0.5 * (H + lam) * wp * wp)
            gain = red_l + red_r - red_p
        else:
            parent_score = G * G / (H + lambda_l2)
            gain = 0.5 * (GLx * GLx / (HLx + lambda_l2)
                          + GRx * GRx / (HRx + lambda_l2) - parent_score)
        return jnp.where(valid, gain, NEG_INF)

    gain = gain_of(GL, HL, CL)
    if learn_missing:
        g0, h0, c0 = hg_o[:, :1], hh_o[:, :1], hc_o[:, :1]
        CL_r = CL - c0
        gain_r = gain_of(GL - g0, HL - h0, CL_r)
        gain_r = jnp.where((C - CL_r) > c0, gain_r, NEG_INF)
        if has_cat:
            gain_r = jnp.where(is_cat_feat[:, None], NEG_INF, gain_r)
        if bundled_mask is not None:
            gain_r = jnp.where(bundled_mask[:, None], NEG_INF, gain_r)
        flat2 = jnp.argmax(jnp.stack([gain.ravel(), gain_r.ravel()]).ravel())
        flat2 = flat2.astype(jnp.int32)
        dleft = flat2 < F * B
        flat = flat2 % (F * B)
        best_gain = jnp.where(dleft, gain.ravel()[flat], gain_r.ravel()[flat])
    else:
        flat = jnp.argmax(gain.ravel()).astype(jnp.int32)  # first-max
        dleft = jnp.bool_(True)
        best_gain = gain.ravel()[flat]
    f = flat // B
    t = flat % B

    if has_cat:
        inv_order = jnp.zeros((B,), jnp.int32).at[order[f]].set(iota)
        cat_raw = (inv_order <= t) & is_cat_feat[f]
    else:
        cat_raw = jnp.zeros((1,), bool)

    g_left, h_left, c_left = GL[f, t], HL[f, t], CL[f, t]
    if learn_missing:
        g_left = jnp.where(dleft, g_left, g_left - hg_o[f, 0])
        h_left = jnp.where(dleft, h_left, h_left - hh_o[f, 0])
        c_left = jnp.where(dleft, c_left, c_left - hc_o[f, 0])

    f_global = f + feat_offset.astype(jnp.int32)
    # the GLOBAL flattened argmax index the fused scan would have picked:
    # plane-major (missing-left plane first), feature-major within a plane
    # — min-key over equal-gain shards == the fused first-max tie-break
    span = jnp.int32(num_features_total * B)
    key = (jnp.where(dleft, 0, span) + f_global * B + t).astype(jnp.int32)
    return LocalSplit(
        gain=best_gain,
        key=key,
        feature=f_global.astype(jnp.int32),
        threshold=t.astype(jnp.int32),
        g_left=g_left,
        h_left=h_left,
        c_left=c_left,
        default_left=dleft,
        cat_mask=cat_raw,
    )


_I32_MAX = 2**31 - 1

#: packed LocalSplit word layout (pack_local_split / combine_local_splits):
#: gain, key, feature, threshold, g_left, h_left, c_left, default_left
LOCAL_SPLIT_WORDS = 8


def pack_local_split(rec: LocalSplit) -> jnp.ndarray:
    """LocalSplit scalars -> one (..., 8) uint32 word block, so a whole
    level's combine pays ONE record all-gather (plus the categorical rows
    when present) instead of one per field.  Bitcasts are lossless — the
    combine's unpacked fields are bitwise the scan's."""
    import jax

    def fbits(x):
        return jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                            jnp.uint32)

    return jnp.stack([
        fbits(rec.gain),
        rec.key.astype(jnp.uint32),
        rec.feature.astype(jnp.uint32),      # raw winner ids are >= 0
        rec.threshold.astype(jnp.uint32),
        fbits(rec.g_left),
        fbits(rec.h_left),
        fbits(rec.c_left),
        rec.default_left.astype(jnp.uint32),
    ], axis=-1)


def combine_local_splits(words: jnp.ndarray, cat_rows, *, allow,
                         min_split_gain: float, has_cat: bool) -> SplitResult:
    """Argmax-of-argmaxes over gathered per-shard records -> SplitResult.

    ``words`` is the gathered ``pack_local_split`` block with a leading
    shard axis — (n, 8) scalar records or (n, C, 8) vmapped batches;
    ``cat_rows`` the gathered raw (n, ..., B) categorical membership rows
    (None when the config has no categorical features).  Winner = max
    gain, ties to the MINIMUM tie key, which is the fused scan's own
    flattened index — so on a degenerate 1-shard gather this IS the fused
    selection, and on n shards equal-gain candidates resolve in the
    identical plane-major / feature-major order.  The ok-gating (allow,
    finiteness, min_split_gain) runs HERE, once, on the global winner —
    gating per-shard first would let a lower-gain shard win after a
    higher-gain winner failed min_split_gain, which the fused scan never
    does."""
    import jax

    gains = jax.lax.bitcast_convert_type(words[..., 0], jnp.float32)
    keys = words[..., 1].astype(jnp.int32)
    best_gain = jnp.max(gains, axis=0)
    tie = jnp.where(gains == best_gain[None], keys, jnp.int32(_I32_MAX))
    win = jnp.argmin(tie, axis=0).astype(jnp.int32)

    def pick(x):
        idx = win.reshape((1,) + win.shape + (1,) * (x.ndim - 1 - win.ndim))
        return jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (1,) + x.shape[1:]), axis=0)[0]

    w = pick(words)                               # (..., 8) winner block
    f = w[..., 2].astype(jnp.int32)
    t = w[..., 3].astype(jnp.int32)
    g_left = jax.lax.bitcast_convert_type(w[..., 4], jnp.float32)
    h_left = jax.lax.bitcast_convert_type(w[..., 5], jnp.float32)
    c_left = jax.lax.bitcast_convert_type(w[..., 6], jnp.float32)
    dleft = w[..., 7] != 0

    ok = allow & jnp.isfinite(best_gain) & (best_gain > min_split_gain)
    if has_cat and cat_rows is not None:
        cat_mask = pick(cat_rows) & ok[..., None]
    else:
        # the fused scan's no-cat placeholder shape: (..., 1) False
        cat_mask = jnp.zeros(win.shape + (1,), bool)
    return SplitResult(
        gain=jnp.where(ok, best_gain, NEG_INF),
        feature=jnp.where(ok, f, -1).astype(jnp.int32),
        threshold=t.astype(jnp.int32),
        g_left=g_left,
        h_left=h_left,
        c_left=c_left,
        cat_mask=cat_mask,
        default_left=dleft | ~ok,
    )
