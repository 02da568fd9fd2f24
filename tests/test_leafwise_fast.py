"""Batched (expansion+selection) leaf-wise grower vs the sequential slot
machine: identical trees, node numbering included.

Gains are order-independent, so the batched grower must reproduce the
sequential one EXACTLY whenever both see the same histogram values; these
fixtures are tie-free so fp noise cannot flip argmaxes.
"""

import numpy as np
import pytest

import jax.numpy as jnp

# r19: slow — the wired batched-leafwise parity fixtures pay the
# run-bookkeeping tiles in interpret-mode Python (STATUS Round-10 note);
# part of the tier-1 870 s re-budget (ci.sh runs `-m 'not slow'`).
pytestmark = pytest.mark.slow

from dryad_tpu.config import make_params
from dryad_tpu.engine.grower import grow_any, grow_tree
from dryad_tpu.engine.leafwise_fast import (
    effective_depth_params,
    grow_tree_leafwise_batched,
    supports,
)


def _fixture(n=20_000, f=8, b=32, seed=3, cat=False):
    rng = np.random.default_rng(seed)
    Xb = jnp.asarray(rng.integers(1, b, size=(n, f), dtype=np.uint8))
    yv = rng.normal(size=n)
    g = jnp.asarray((yv + rng.normal(size=n) * 0.1).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.5, 1.5, size=n).astype(np.float32))
    bag = jnp.asarray(rng.random(n) < 0.85)
    fmask = jnp.ones((f,), bool)
    iscat = jnp.zeros((f,), bool)
    if cat:
        iscat = iscat.at[0].set(True).at[3].set(True)
    return Xb, g, h, bag, fmask, iscat


def _row_leaf(tree):
    """Each row's leaf as the train step reads it: the grower's key -> leaf
    table at the row's partition key."""
    table = np.asarray(tree["key_leaf"])
    return table[np.clip(np.asarray(tree["row_key"]), 0, len(table) - 1)]


def _assert_same_tree(seq, bat):
    for key in ("feature", "threshold", "left", "right", "default_left",
                "is_cat", "cat_bitset"):
        np.testing.assert_array_equal(np.asarray(seq[key]),
                                      np.asarray(bat[key]), err_msg=key)
    # leaf stats ride different histogram programs (masked XLA pass vs
    # segmented tiles) -> ulp-level value differences; structure is exact
    np.testing.assert_allclose(np.asarray(seq["value"]),
                               np.asarray(bat["value"]), rtol=1e-4,
                               atol=2e-6)
    np.testing.assert_array_equal(_row_leaf(seq), _row_leaf(bat))
    assert int(seq["max_depth"]) == int(bat["max_depth"])


@pytest.mark.parametrize("leaves,depth,lm", [(31, 5, False), (15, 8, False),
                                             (63, 6, True)])
def test_batched_equals_sequential(leaves, depth, lm):
    Xb, g, h, bag, fmask, iscat = _fixture()
    p = make_params(dict(objective="l2", num_leaves=leaves, max_depth=depth,
                         growth="leafwise", min_data_in_leaf=20))
    seq = grow_tree(p, 32, Xb, g, h, bag, fmask, iscat, learn_missing=lm)
    bat = grow_tree_leafwise_batched(p, 32, Xb, g, h, bag, fmask, iscat,
                                     learn_missing=lm)
    _assert_same_tree(seq, bat)


def test_batched_equals_sequential_categorical():
    Xb, g, h, bag, fmask, iscat = _fixture(cat=True)
    p = make_params(dict(objective="l2", num_leaves=31, max_depth=6,
                         growth="leafwise", min_data_in_leaf=20))
    seq = grow_tree(p, 32, Xb, g, h, bag, fmask, iscat, has_cat=True)
    bat = grow_tree_leafwise_batched(p, 32, Xb, g, h, bag, fmask, iscat,
                                     has_cat=True)
    _assert_same_tree(seq, bat)


def test_batched_equals_sequential_monotone():
    Xb, g, h, bag, fmask, iscat = _fixture()
    p = make_params(dict(objective="l2", num_leaves=31, max_depth=6,
                         growth="leafwise", min_data_in_leaf=20,
                         monotone_constraints=[1, 0, -1, 0, 0, 0, 0, 0]))
    seq = grow_tree(p, 32, Xb, g, h, bag, fmask, iscat)
    bat = grow_tree_leafwise_batched(p, 32, Xb, g, h, bag, fmask, iscat)
    _assert_same_tree(seq, bat)


def test_batched_equals_sequential_cat_and_missing():
    """Combined categorical + learn_missing routing (ADVICE r3 #4): the
    packed-word partition applies the missing-direction AND before the
    categorical override — the interaction most likely to regress silently.
    Bin 0 plays 'missing' on the numeric features; categorical subset
    splits must override the missing plane entirely."""
    rng = np.random.default_rng(11)
    n, f, b = 20_000, 8, 32
    Xb_np = rng.integers(1, b, size=(n, f), dtype=np.uint8)
    # missing-heavy numeric columns + two categorical columns
    miss = rng.random((n, f)) < 0.25
    miss[:, 0] = False
    miss[:, 3] = False
    Xb_np[miss] = 0
    Xb = jnp.asarray(Xb_np)
    yv = rng.normal(size=n)
    g = jnp.asarray((yv + rng.normal(size=n) * 0.1).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.5, 1.5, size=n).astype(np.float32))
    bag = jnp.asarray(rng.random(n) < 0.9)
    fmask = jnp.ones((f,), bool)
    iscat = jnp.zeros((f,), bool).at[0].set(True).at[3].set(True)
    p = make_params(dict(objective="l2", num_leaves=31, max_depth=6,
                         growth="leafwise", min_data_in_leaf=20))
    seq = grow_tree(p, b, Xb, g, h, bag, fmask, iscat, has_cat=True,
                    learn_missing=True)
    bat = grow_tree_leafwise_batched(p, b, Xb, g, h, bag, fmask, iscat,
                                     has_cat=True, learn_missing=True)
    _assert_same_tree(seq, bat)


def _wired_params(extra=None, **kw):
    """A config the layout gate ADMITS on forced-CPU CI: interpret-mode
    Pallas (hist_backend="pallas") + a depth within the run-capacity cap."""
    base = dict(objective="l2", num_leaves=31, max_depth=6,
                growth="leafwise", min_data_in_leaf=20,
                hist_backend="pallas")
    base.update(extra or {})
    base.update(kw)
    return make_params(base)


def test_wired_gate_admits_fixture():
    """The fixtures below must actually exercise the layout-wired
    expansion — if the gate stops admitting them, this file would
    silently test the legacy path.  Also pins the gate's own edges:
    legacy opt-out, the run-capacity depth cap, and the XLA backend."""
    from dryad_tpu.engine.leafwise_fast import leafwise_layout_supported

    p = _wired_params()
    assert leafwise_layout_supported(p, 8, 32, 1, "cpu")
    assert not leafwise_layout_supported(
        p.replace(deep_layout="legacy"), 8, 32, 1, "cpu")
    # run-capacity cap: 2^max_depth must fit the dense run bookkeeping
    assert leafwise_layout_supported(
        _wired_params(num_leaves=512, max_depth=12), 8, 32, 1, "cpu")
    assert not leafwise_layout_supported(
        _wired_params(num_leaves=512, max_depth=13), 8, 32, 1, "cpu")
    # CPU 'auto' resolves to XLA -> no tile layout to feed
    assert not leafwise_layout_supported(
        _wired_params(hist_backend="auto"), 8, 32, 1, "cpu")


@pytest.mark.parametrize("leaves,depth,lm", [(31, 5, False), (15, 7, False),
                                             (63, 6, True)])
def test_wired_batched_equals_sequential(leaves, depth, lm):
    """Layout-wired expansion (r10) ≡ sequential leaf-wise, tree for tree
    incl. node numbering — the same equivalence the legacy expansion pins,
    now with sides derived from the carried layout records and histograms
    read as contiguous tile runs."""
    from dryad_tpu.engine.leafwise_fast import leafwise_layout_supported

    Xb, g, h, bag, fmask, iscat = _fixture()
    p = _wired_params(num_leaves=leaves, max_depth=depth)
    assert leafwise_layout_supported(p, Xb.shape[1], 32, 1, "cpu")
    seq = grow_tree(p, 32, Xb, g, h, bag, fmask, iscat, learn_missing=lm)
    bat = grow_tree_leafwise_batched(p, 32, Xb, g, h, bag, fmask, iscat,
                                     learn_missing=lm, platform="cpu")
    _assert_same_tree(seq, bat)


def test_wired_batched_equals_legacy_batched():
    """Wired vs legacy batched expansion on the tie-free fixture: bitwise
    tree structures AND each row's key and key -> leaf table (both derive
    sides from the same packed arithmetic; only the histogram/movement
    programs differ)."""
    Xb, g, h, bag, fmask, iscat = _fixture()
    p_w = _wired_params()
    bat_w = grow_tree_leafwise_batched(p_w, 32, Xb, g, h, bag, fmask, iscat,
                                       platform="cpu")
    bat_l = grow_tree_leafwise_batched(p_w.replace(deep_layout="legacy"),
                                       32, Xb, g, h, bag, fmask, iscat,
                                       platform="cpu")
    for key in ("feature", "threshold", "left", "right", "default_left",
                "is_cat", "cat_bitset", "row_key", "key_leaf"):
        np.testing.assert_array_equal(np.asarray(bat_w[key]),
                                      np.asarray(bat_l[key]), err_msg=key)
    np.testing.assert_allclose(np.asarray(bat_w["value"]),
                               np.asarray(bat_l["value"]), rtol=1e-4,
                               atol=2e-6)


def test_wired_batched_cat_and_missing_equals_sequential():
    """The wired side derivation's categorical-bitset and learned-missing
    branches (packed_route bits 29/30 against heap-node tables) — the
    interaction most likely to regress silently, now over the carried
    layout records."""
    rng = np.random.default_rng(11)
    n, f, b = 20_000, 8, 32
    Xb_np = rng.integers(1, b, size=(n, f), dtype=np.uint8)
    miss = rng.random((n, f)) < 0.25
    miss[:, 0] = False
    miss[:, 3] = False
    Xb_np[miss] = 0
    Xb = jnp.asarray(Xb_np)
    yv = rng.normal(size=n)
    g = jnp.asarray((yv + rng.normal(size=n) * 0.1).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.5, 1.5, size=n).astype(np.float32))
    bag = jnp.asarray(rng.random(n) < 0.9)
    fmask = jnp.ones((f,), bool)
    iscat = jnp.zeros((f,), bool).at[0].set(True).at[3].set(True)
    p = _wired_params()
    seq = grow_tree(p, b, Xb, g, h, bag, fmask, iscat, has_cat=True,
                    learn_missing=True)
    bat = grow_tree_leafwise_batched(p, b, Xb, g, h, bag, fmask, iscat,
                                     has_cat=True, learn_missing=True,
                                     platform="cpu")
    _assert_same_tree(seq, bat)


def test_effective_depth_policy():
    """max_depth=-1 maps to min(ceil(log2(L))+4, 14) under 'auto' whenever
    the batched grower can take the config; 'exact' and infeasible shapes
    keep true-unbounded (VERDICT r3 #3)."""
    p = make_params(dict(objective="l2", num_leaves=255, growth="leafwise"))
    assert effective_depth_params(p, 28, 256).max_depth == 12
    p31 = make_params(dict(objective="l2", num_leaves=31, growth="leafwise"))
    assert effective_depth_params(p31, 8, 32).max_depth == 9
    # explicit cap: untouched
    p_cap = p.replace(max_depth=7)
    assert effective_depth_params(p_cap, 28, 256) is p_cap
    # opt-out: untouched
    p_exact = p.replace(unbounded_depth="exact")
    assert effective_depth_params(p_exact, 28, 256) is p_exact
    # depthwise: untouched (policy is leaf-wise only)
    p_dw = make_params(dict(objective="l2", num_leaves=255,
                            growth="depthwise"))
    assert effective_depth_params(p_dw, 28, 256) is p_dw
    # expansion budget exceeded at the capped depth -> sequential unbounded
    assert effective_depth_params(p, 2000, 256) is p
    # subtraction disabled -> batched grower unavailable -> untouched
    p_nosub = p.replace(hist_subtraction=False)
    assert effective_depth_params(p_nosub, 28, 256) is p_nosub


def test_default_config_rides_batched_grower():
    """End-to-end: the out-of-the-box leaf-wise config (max_depth=-1) must
    train identically to the explicit effective-depth config on BOTH
    backends (the policy is applied identically in cpu/trainer.py and
    engine/train.py)."""
    import dryad_tpu as dryad

    rng = np.random.default_rng(5)
    X = rng.normal(size=(4000, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 + rng.normal(size=4000) * 0.1 > 0.3)
    ds = dryad.Dataset(X, y.astype(np.float64), max_bins=32)
    p_auto = make_params(dict(objective="binary", num_trees=4,
                              num_leaves=31, growth="leafwise"))
    p_expl = p_auto.replace(max_depth=9)
    for backend in ("cpu", "tpu"):
        b_auto = dryad.train(p_auto, ds, backend=backend)
        b_expl = dryad.train(p_expl, ds, backend=backend)
        np.testing.assert_array_equal(b_auto.feature, b_expl.feature)
        np.testing.assert_array_equal(b_auto.threshold, b_expl.threshold)
        np.testing.assert_array_equal(
            b_auto.predict(X, raw_score=True),
            b_expl.predict(X, raw_score=True))
    # and CPU == device on the default config itself
    b_cpu = dryad.train(p_auto, ds, backend="cpu")
    b_dev = dryad.train(p_auto, ds, backend="tpu")
    np.testing.assert_array_equal(b_cpu.feature, b_dev.feature)
    np.testing.assert_array_equal(b_cpu.threshold, b_dev.threshold)


def test_grow_any_routes_by_depth():
    """max_depth set -> batched path; unset (-1) -> sequential (an unbounded
    tree cannot be pre-expanded)."""
    p_fast = make_params(dict(objective="l2", num_leaves=31, max_depth=6,
                              growth="leafwise"))
    p_seq = make_params(dict(objective="l2", num_leaves=31,
                             growth="leafwise"))
    assert supports(p_fast, 8, 32)
    assert not supports(p_seq, 8, 32)
    # huge expansion exceeds the hist-buffer budget -> sequential
    p_wide = make_params(dict(objective="l2", num_leaves=31, max_depth=14,
                              growth="leafwise"))
    assert not supports(p_wide, 2000, 256)
    # the routed result matches the sequential grower
    Xb, g, h, bag, fmask, iscat = _fixture(n=5000)
    seq = grow_tree(p_fast, 32, Xb, g, h, bag, fmask, iscat)
    routed = grow_any(p_fast, 32, Xb, g, h, bag, fmask, iscat)
    for key in ("feature", "threshold", "left", "right"):
        np.testing.assert_array_equal(np.asarray(seq[key]),
                                      np.asarray(routed[key]))


def test_memory_envelope_guard_pure_function():
    """The batched grower's envelope (VERDICT r3 #7) is a pure function of
    params + GLOBAL data shape: wide-feature deep caps reject on the pinned
    buffer, huge-N wide configs reject on peak residency, and the policy
    never consults the backend."""
    from dryad_tpu.config import (
        effective_depth_params, leafwise_fast_supported, make_params,
    )

    d12 = make_params(dict(num_leaves=4095, max_depth=12))
    assert not leafwise_fast_supported(d12, 2000, 256, 400_000)   # pinned
    d6 = make_params(dict(num_leaves=63, max_depth=6))
    assert leafwise_fast_supported(d6, 2000, 256, 400_000)
    assert not leafwise_fast_supported(d6, 2000, 256, 5_000_000)  # N-aware
    # max_depth=-1 auto policy consults the same envelope: the wide config
    # keeps true-unbounded sequential semantics instead of a doomed cap
    auto = make_params(dict(num_leaves=255))
    assert effective_depth_params(auto, 28, 256, 200_000).max_depth == 12
    assert effective_depth_params(auto, 2000, 256, 40_000_000).max_depth == -1


def test_envelope_fallback_trains_sequential():
    """An over-envelope depth-capped leaf-wise config must fall back to the
    sequential grower DETERMINISTICALLY (same trees as an in-envelope run
    forced sequential via hist_subtraction=False is not comparable — so we
    just pin: it trains, warns, and matches the CPU backend)."""
    import warnings

    import dryad_tpu as dryad
    from dryad_tpu.datasets import higgs_like

    X, y = higgs_like(1500, seed=31)
    ds = dryad.Dataset(X, y, max_bins=32)
    # depth 15 exceeds MAX_FAST_DEPTH -> batched grower rejects
    p = dict(objective="binary", num_trees=3, num_leaves=31, max_depth=15)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        b_dev = dryad.train(p, ds, backend="tpu")
    assert any("sequential grower" in str(x.message) for x in w)
    b_cpu = dryad.train(p, ds, backend="cpu")
    np.testing.assert_array_equal(b_dev.feature, b_cpu.feature)
    np.testing.assert_array_equal(b_dev.threshold, b_cpu.threshold)
