"""The categorical subset scan's order is one multi-operand sort: G, H, C and
the bin index ride the stable sort keyed on the ratio, where an ``argsort``
and three ``take_along_axis`` gathers ran before.  Held here bit for bit
against that formulation, written out below, over vmapped random
histograms with ties in the ratio, empty bins, all-empty rows, and mixed
categorical and numeric features, through both scan bodies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dryad_tpu.engine import split
from dryad_tpu.engine.split import CAT_SMOOTH

C, F, B = 48, 8, 64
IS_CAT = np.array([True, True, False, True, True, True, False, True])
MONOTONE = np.array([0, 1, -1, 0, 1, 0, 1, -1], np.int32)


def _gathers_order(hg, hh, hc, is_cat_feat):
    """The scan's order as it stood: ``argsort`` of the ratio, the identity
    for numeric features, three gathers."""
    iota = jnp.arange(hg.shape[1], dtype=jnp.int32)
    ratio = jnp.where(hc > 0, hg / (hh + CAT_SMOOTH), jnp.inf)
    cat_order = jnp.argsort(ratio, axis=1, stable=True).astype(jnp.int32)
    order = jnp.where(is_cat_feat[:, None], cat_order, iota[None, :])
    return (jnp.take_along_axis(hg, order, axis=1), jnp.take_along_axis(hh, order, axis=1),
            jnp.take_along_axis(hc, order, axis=1), order)


def _gathers_left(order, is_cat_feat, f, t, iota):
    """The membership row as it stood: row f by an index."""
    inv_order = jnp.zeros((order.shape[1],), jnp.int32).at[order[f]].set(iota)
    return (inv_order <= t) & is_cat_feat[f]


def _hists(seed):
    """(C, 3, F, B): counts with a third of the bins empty, every fifth
    child's feature 4 empty whole and child 7 empty whole, G on a grid of
    quarters so that ratios tie, bin 0 (missing) filled on half the rows.
    Returns the histograms and each child's (G, H, C) from feature 0."""
    rng = np.random.default_rng(seed)
    hc = np.floor(rng.exponential(6.0, (C, F, B)))
    hc[rng.random((C, F, B)) < 0.33] = 0.0
    hc[::5, 4] = 0.0
    hc[:, :, 0] *= rng.random((C, F)) < 0.5
    # a numeric feature (2 on every fourth child, 6 on the next) splits cleanly,
    # its missing bin on the right side's sign: numeric winners, missing right
    sign = np.where(np.arange(B) < B // 2, -1.0, 1.0)
    sign[0] = 1.0
    for feat, parity in ((2, 0), (6, 1)):
        strong = np.arange(C) % 4 == parity
        hc[strong, feat] = np.maximum(hc[strong, feat], 5.0)
        hc[strong, feat, 0] = 40.0
    hg = np.round(rng.normal(0.0, 2.0, (C, F, B))) * 0.25 * (hc > 0)
    for feat, parity in ((2, 0), (6, 1)):    # each direction its monotone sign allows
        strong = np.arange(C) % 4 == parity
        hg[strong, feat] = 0.5 * MONOTONE[feat] * -sign * hc[strong, feat]
    hc[7] = hg[7] = 0.0
    hh = 0.25 * hc
    # every feature of a child sums to the same node totals, as histograms do
    hc[:, :, 1] += hc.sum(axis=2).max(axis=1, keepdims=True) - hc.sum(axis=2)
    hist = np.stack([hg, hh, hc], axis=1).astype(np.float32)
    return hist, hist[:, :, 0].sum(axis=2)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def sl_of(arm):
    """The features an arm scans: all, or the slice ``off .. off + 4``."""
    return slice(None) if arm == "fused" else slice(int(arm[6:]), int(arm[6:]) + 4)


def _scan(arm, monotone, learn_missing, hist, tot):
    """One vmapped scan: the fused body, or the sliced one over features
    ``off .. off + 4`` (arm ``sliced<off>``)."""
    lo = jnp.full((C,), -0.5, jnp.float32)
    hi = jnp.full((C,), 0.5, jnp.float32)
    mono = jnp.asarray(MONOTONE) if monotone else None
    if arm == "fused":
        def one(h, G, H, Cn, l, u):
            return split.find_best_split(
                h, G, H, Cn, lambda_l2=1.0, min_child_weight=0.5, min_data_in_leaf=2,
                min_split_gain=0.0, feat_mask=jnp.ones((F,), bool),
                is_cat_feat=jnp.asarray(IS_CAT), allow=jnp.bool_(True), monotone=mono,
                lo=l, hi=u, learn_missing=learn_missing)
    else:
        off = int(arm[len("sliced"):])
        sl = slice(off, off + 4)
        hist = hist[:, :, sl]

        def one(h, G, H, Cn, l, u):
            return split.find_best_split_sliced(
                h, G, H, Cn, feat_offset=jnp.int32(off), num_features_total=F,
                lambda_l2=1.0, min_child_weight=0.5, min_data_in_leaf=2,
                feat_mask=jnp.ones((4,), bool), is_cat_feat=jnp.asarray(IS_CAT[sl]),
                monotone=None if mono is None else mono[sl], lo=l, hi=u,
                learn_missing=learn_missing)
    fn = jax.jit(jax.vmap(one))
    return fn(jnp.asarray(hist), *(jnp.asarray(tot[:, i]) for i in range(3)), lo, hi), hist


@pytest.mark.parametrize("arm", ["fused", "sliced0", "sliced4"])
@pytest.mark.parametrize("monotone", [False, True])
@pytest.mark.parametrize("learn_missing", [False, True])
def test_the_sorted_scan_equals_the_gathers_bit_for_bit(arm, monotone, learn_missing,
                                                        monkeypatch):
    hist, tot = _hists(3900 + 7 * monotone + learn_missing)
    new, sliced = _scan(arm, monotone, learn_missing, hist, tot)
    with monkeypatch.context() as m:
        m.setattr(split, "_cat_order", _gathers_order)
        m.setattr(split, "_cat_left", _gathers_left)
        old, _ = _scan(arm, monotone, learn_missing, hist, tot)
    for name in new._fields:
        assert np.array_equal(_bits(getattr(new, name)), _bits(getattr(old, name))), name
    # the fixture reaches what it is for: categorical and numeric winners,
    # children with no valid split, and at least one missing-right plane
    feat = np.asarray(new.feature) if arm == "fused" else np.asarray(new.feature) - int(arm[6:])
    won = feat >= 0 if arm == "fused" else np.isfinite(np.asarray(new.gain))
    assert IS_CAT[sl_of(arm)][feat[won]].any() and (~IS_CAT[sl_of(arm)][feat[won]]).any()
    if arm == "fused":
        assert not won.all()
    if learn_missing:
        assert not np.asarray(new.default_left)[won].all()
    # the order itself, every feature of every child: the sort's iota is the
    # argsort's order, numeric rows the identity
    args = [jnp.asarray(sliced[:, i]) for i in range(3)]
    cat = jnp.asarray(IS_CAT[sl_of(arm)])
    got = jax.vmap(split._cat_order, in_axes=(0, 0, 0, None))(*args, cat)
    want = jax.vmap(_gathers_order, in_axes=(0, 0, 0, None))(*args, cat)
    for g, w in zip(got, want):
        assert np.array_equal(_bits(g), _bits(w))
    order = np.asarray(got[3])
    assert (order[:, ~IS_CAT[sl_of(arm)]] == np.arange(B)).all()
