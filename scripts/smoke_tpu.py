"""On-device smoke checks that forced-CPU CI cannot cover (CLAUDE.md:
"new kernel shapes must be smoke-run on the real device once"; MXU
lowerings are fusion-sensitive, so program-level contracts need a check
on real hardware).

Run on the chip after touching histogram builders, growers, or predict
(``--gate`` adds the on-device train-parity pass; any drift exits
non-zero).  Without an accelerator nothing here means anything, so the
script refuses to start on a CPU-only jax instead of skipping its way
to an OK:
    PYTHONPATH=. python scripts/smoke_tpu.py --gate
"""

import numpy as np


def smoke_shared_vs_per_class():
    """build_hist_classes per-class slices == build_hist, bitwise, on the
    attached device (the shared multiclass root pass rides on this)."""
    import jax.numpy as jnp

    from dryad_tpu.engine.histogram import build_hist, build_hist_classes

    rng = np.random.default_rng(53)
    N, F, B, K = 200_000, 28, 256, 7
    Xb = jnp.asarray(rng.integers(0, B, size=(N, F), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=(N, K)).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, size=(N, K)).astype(np.float32))
    mask = jnp.asarray(rng.random(N) < 0.8)
    shared = np.asarray(build_hist_classes(Xb, g, h, mask, B,
                                           rows_per_chunk=32768))
    for k in range(K):
        single = np.asarray(build_hist(Xb, g[:, k], h[:, k], mask, B,
                                       rows_per_chunk=32768))
        np.testing.assert_array_equal(shared[k], single)
    print(f"shared-vs-per-class roots: bitwise equal for all {K} classes")


def smoke_pallas_vs_xla():
    """Pallas segmented histogram vs the XLA oracle on the device."""
    import jax.numpy as jnp

    from dryad_tpu.engine.histogram import build_hist_segmented

    rng = np.random.default_rng(59)
    N, F, B, P = 100_000, 12, 64, 16
    Xb = jnp.asarray(rng.integers(0, B, size=(N, F), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, N).astype(np.float32))
    sel = jnp.asarray(rng.integers(0, P + 1, N).astype(np.int32))
    got = np.asarray(build_hist_segmented(Xb, g, h, sel, P, B, backend="pallas"))
    want = np.asarray(build_hist_segmented(Xb, g, h, sel, P, B, backend="xla"))
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-5)
    print("pallas-vs-xla segmented histogram: agree to tolerance")


def smoke_pallas_u16_and_records():
    """Mosaic must lower the uint16 tile load (bins > 256) and the records
    fused-gather path on the real device — interpret-mode CI cannot catch
    lowering failures for these shapes."""
    import jax.numpy as jnp

    from dryad_tpu.engine.histogram import build_hist_segmented
    from dryad_tpu.engine.pallas_hist import make_records

    rng = np.random.default_rng(61)
    N, F, B, P = 100_000, 10, 512, 16       # uint16 bins, F % 4 != 0
    Xb = jnp.asarray(rng.integers(0, B, size=(N, F), dtype=np.uint16))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, N).astype(np.float32))
    sel = jnp.asarray(rng.integers(0, P + 1, N).astype(np.int32))
    got = np.asarray(build_hist_segmented(Xb, g, h, sel, P, B,
                                          backend="pallas"))
    rec = np.asarray(build_hist_segmented(Xb, g, h, sel, P, B,
                                          backend="pallas",
                                          records=make_records(Xb, g, h)))
    np.testing.assert_array_equal(got, rec)  # records path bitwise
    want = np.asarray(build_hist_segmented(Xb, g, h, sel, P, B,
                                           backend="xla"))
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-5)
    print("pallas u16 tiles + records path: lower and agree on device")


def smoke_pallas_wide_segment_count():
    """The batched leaf-wise expansion histograms up to P = 2^(D-1)
    segments (8192 at the depth-14 cap) — lower the widest grid on the
    real device once."""
    import jax.numpy as jnp

    from dryad_tpu.engine.histogram import build_hist_segmented

    rng = np.random.default_rng(67)
    N, F, B, P = 400_000, 8, 64, 8192
    Xb = jnp.asarray(rng.integers(0, B, size=(N, F), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, N).astype(np.float32))
    sel = jnp.asarray(rng.integers(0, P + 1, N).astype(np.int32))
    got = np.asarray(build_hist_segmented(Xb, g, h, sel, P, B,
                                          backend="pallas"))
    want = np.asarray(build_hist_segmented(Xb, g, h, sel, P, B,
                                           backend="xla"))
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-5)
    print(f"pallas segmented P={P}: lowers and agrees on device")


def smoke_pallas_natural_order():
    """The natural-order multi-slot kernel (shallow levels, <= 16 slots)
    — new Mosaic shapes (8-row weight block with the slot-id lane row,
    128-row in-VMEM expansion, i==0 output init)."""
    import jax.numpy as jnp

    from dryad_tpu.engine.histogram import build_hist_segmented
    from dryad_tpu.engine.pallas_hist import (
        _NAT_DROP, build_hist_nat, natural_tiles,
    )

    rng = np.random.default_rng(71)
    # B=256 exercises the FULL lane budget (Fc*Bp = 8192 -> a (128, 8192)
    # fp32 output block in VMEM), the shape gated production data uses
    N, F, B, P = 150_000, 32, 256, 8
    Xb = jnp.asarray(rng.integers(0, B, size=(N, F), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, N).astype(np.float32))
    sel = jnp.asarray(np.where(rng.integers(0, 2 * P, N) < P,
                               rng.integers(0, P, N), _NAT_DROP)
                      .astype(np.int32))
    got = np.asarray(build_hist_nat(natural_tiles(Xb, B), g, h, sel,
                                    total_bins=B, num_features=F))[:P]
    want = np.asarray(build_hist_segmented(
        Xb, g, h, jnp.minimum(sel, P), P, B, backend="xla"))
    np.testing.assert_array_equal(got[:, 2], want[:, 2])
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-5)
    print("pallas natural-order multi-slot: lowers and agrees on device")


def smoke_leafperm_move_oracle():
    """The fused level move (leafperm.move_level: counting pass + move
    kernel, sides and ranks derived in-kernel from per-tile split
    records) against the numpy oracle ON THE REAL DEVICE, bitwise, for
    every record shape the wired gate admits: u8 and u16 bins, learned
    missing direction, categorical bitsets, a pass-through and a dead run.
    Interpret mode cannot vouch for the NT selector product, the SMEM
    parameter words or the resident count block."""
    import jax.numpy as jnp

    from dryad_tpu.engine import leafperm

    T = leafperm._TILE_ROWS
    rng = np.random.default_rng(61)
    N, P = 40_000, 6
    for dtype, B, F, lm, cat in [(np.uint8, 256, 28, False, False),
                                 (np.uint8, 200, 100, True, True),
                                 (np.uint16, 1000, 59, True, False),
                                 (np.uint16, 1024, 20, False, True)]:
        Xb = rng.integers(0, B, (N, F)).astype(dtype)
        rec_nat = leafperm.make_layout_records(
            jnp.asarray(Xb),
            jnp.asarray(rng.normal(size=N).astype(np.float32)),
            jnp.asarray(rng.uniform(0.1, 1, N).astype(np.float32)))
        n_buf = leafperm.wired_tiles_bound(-(-N // T), P + 1)
        slot = rng.integers(0, P, N).astype(np.int32)
        rec_lay, tile_run, _ = leafperm.initial_layout(
            rec_nat, jnp.asarray(slot), jnp.ones((P + 1,), bool), P + 1,
            n_buf)
        # run 4 passes through, run P (empty, absorbed tiles) is dead
        run_rec = np.array(leafperm.pack_run_records(
            do=np.arange(P + 1) % 5 != 4,
            feature=rng.integers(0, F, P + 1),
            thresh=rng.integers(B // 4, 3 * B // 4, P + 1),
            dleft=rng.integers(0, 2, P + 1),
            is_cat=(np.arange(P + 1) % 2 == 1) if cat else None))
        run_rec[P] = 0
        cm = (rng.random((P + 1, B)) < 0.5) if cat else None
        rec_np, tr_np = np.asarray(rec_lay), np.asarray(tile_run)
        side = leafperm.layout_sides_np(rec_np, tr_np, run_rec, cm,
                                        bin_dtype=dtype, learn_missing=lm)
        out, _, _ = leafperm.move_level(
            rec_lay, tile_run, jnp.asarray(run_rec),
            None if cm is None else jnp.asarray(cm), bin_dtype=dtype,
            learn_missing=lm)
        want, _, _ = leafperm.permute_records_np(rec_np, tr_np, side, P + 1,
                                                 n_buf)
        np.testing.assert_array_equal(
            np.asarray(out), want,
            err_msg=f"{np.dtype(dtype).name} B={B} lm={lm} cat={cat}")
    print("leafperm fused move: bitwise vs oracle on device, 4 shapes")


def smoke_hist_in_place_vs_plan():
    """The in-place histogram kernel (``_hist_tiles_rec``: the layout's
    record tiles read where they lie, addressed by a prefetched tile index
    and unpacked in VMEM by a selector product) against the plan path ON
    THE REAL DEVICE, bitwise, on a pad-free layout: the cells' shape (28
    features at 256 bins), two feature chunks, and u16 bins.  Each
    selection holds an empty column, and the last run's absorbed trailing
    tiles are live and all sentinels, so the kernel's own empty-tile
    branch runs.  Interpret mode cannot vouch for Mosaic's lowering of
    the byte reassembly, the bitcast or that branch."""
    import jax.numpy as jnp

    from dryad_tpu.engine import leafperm
    from dryad_tpu.engine.histogram import build_hist_segmented

    T = leafperm._TILE_ROWS
    rng = np.random.default_rng(67)
    N, L = 60_000, 6
    for dtype, B, F in [(np.uint8, 256, 28), (np.uint8, 256, 40),
                        (np.uint16, 1000, 20)]:
        Xb = jnp.asarray(rng.integers(0, B, (N, F)).astype(dtype))
        g = jnp.asarray(rng.normal(size=N).astype(np.float32))
        h = jnp.asarray(rng.uniform(0.1, 1, N).astype(np.float32))
        slot = rng.integers(0, L + 1, N).astype(np.int32)   # L: out of bag
        n_buf = leafperm.wired_tiles_bound(-(-N // T), L)
        rec_lay, tile_run, _ = leafperm.initial_layout(
            leafperm.make_layout_records(Xb, g, h), jnp.asarray(slot),
            jnp.ones((L,), bool), L, n_buf)
        tr = np.asarray(tile_run)
        # columns: slots 4, 1, none, 5 (with the buffer's trailing tiles), 0
        cols = [4, 1, None, L - 1, 0]
        seg_first = [0 if s is None else int(np.nonzero(tr == s)[0][0])
                     for s in cols]
        seg_nt = [0 if s is None else int((tr == s).sum()) for s in cols]
        assert seg_nt[3] > -(-int((slot == L - 1).sum()) // T), seg_nt
        P = len(cols)
        got = np.asarray(leafperm.hist_from_layout(
            rec_lay, jnp.asarray(seg_first, jnp.int32),
            jnp.asarray(seg_nt, jnp.int32), P, B, F, dtype,
            sum(max(n, 1) for n in seg_nt)))
        colof = np.full(L + 1, P, np.int32)
        for j, s_ in enumerate(cols):
            if s_ is not None:
                colof[s_] = j
        want = np.asarray(build_hist_segmented(
            Xb, g, h, jnp.asarray(colof[slot]), P, B, backend="pallas"))
        np.testing.assert_array_equal(
            got, want, err_msg=f"{np.dtype(dtype).name} B={B} F={F}")
        assert want[:, 2, 0].sum() == np.isin(
            slot, [s_ for s_ in cols if s_ is not None]).sum()
    print("in-place layout histogram: bitwise vs plan path on device, "
          "3 shapes")


def smoke_leafperm_wired_parity():
    """Wired levelwise grower (leaf-ordered layout carried through the
    level fori state, root-anchored since r10 so EVERY level is wired)
    vs the legacy sort+gather path ON THE REAL DEVICE:
    bitwise-identical tree structures on the tie-free gate fixture, leaf
    values to fp32 tolerance (post-permute layouts regroup per-tile f32
    histogram sums at ulp level — the documented tolerance class).  The
    movement kernel's DMA layout is hardware-sensitive (granule-indexed
    windowed writes, zero-aliased output), so interpret-mode CI cannot
    stand in for this check; any drift here exits 1 like the other
    kernel smokes."""
    import numpy as np

    import dryad_tpu as dryad
    from dryad_tpu.config import make_params
    from dryad_tpu.datasets import higgs_like
    from dryad_tpu.engine.levelwise import deep_layout_supported, phase_plan
    from dryad_tpu.engine.train import train_device

    X, y = higgs_like(50_000, seed=43)
    ds = dryad.Dataset(X, y, max_bins=64)
    base = dict(objective="binary", num_trees=4, num_leaves=128,
                max_bins=64, growth="depthwise", max_depth=8)
    p_w = make_params(base)
    B = int(ds.mapper.total_bins)
    F = ds.X_binned.shape[1]
    assert deep_layout_supported(p_w, F, B, ds.X_binned.dtype.itemsize), \
        "gate fixture no longer admits the wired path"
    d_switch, _, _ = phase_plan(p_w.max_depth, p_w.effective_num_leaves,
                                True)
    assert d_switch < p_w.max_depth, "fixture exercises only one fori phase"
    b_w = train_device(p_w, ds)
    b_l = train_device(make_params(dict(base, deep_layout="legacy")), ds)
    for k in ("feature", "threshold", "left", "right", "is_cat"):
        np.testing.assert_array_equal(
            b_w.tree_arrays()[k], b_l.tree_arrays()[k],
            err_msg=f"wired vs legacy levelwise: {k!r}")
    np.testing.assert_allclose(b_w.value, b_l.value, atol=1e-5)
    print("leafperm wired levelwise: trees bitwise vs legacy on device")


def smoke_leafwise_wired_parity():
    """Layout-wired batched leaf-wise expansion vs the legacy expansion ON
    THE REAL DEVICE: bitwise-identical trees on the tie-free fixture, leaf
    values to fp32 tolerance (same tolerance class as the levelwise smoke
    above — post-permute layouts regroup per-tile f32 partial sums).  The
    leaf-wise wiring's hardware-only risks are its own: heap-node run
    bookkeeping with sentinel HN and run capacity 2^D drive the same DMA
    movement kernel through different scalar prefetch values, which
    interpret-mode CI cannot vouch for.

    Two fixtures: depth 8 (256 run slots), and the benchmark cell's own
    cap (PR 29): 255 leaves with ``max_depth=-1`` is cap 12, 4096 run
    slots, the last the ``leafwise_layout`` gate admits, on 300k rows,
    where the 8194 mandated tiles a level outnumber the table's 586.  A
    second, warm run of each arm there is timed: what a small table pays
    for the wired layout at this cap."""
    import time

    import numpy as np

    import dryad_tpu as dryad
    from dryad_tpu.config import effective_depth_params, make_params
    from dryad_tpu.datasets import higgs_like
    from dryad_tpu.engine.leafwise_fast import (
        leafwise_layout_supported, supports,
    )
    from dryad_tpu.engine.train import train_device

    for rows, seed, extra, depth, timed in (
            (50_000, 43, dict(num_leaves=128, max_depth=8), 8, False),
            (300_000, 53, dict(num_leaves=255, max_depth=-1,
                               min_child_weight=100.0), 12, True)):
        X, y = higgs_like(rows, seed=seed)
        ds = dryad.Dataset(X, y, max_bins=64)
        base = dict(objective="binary", num_trees=4, max_bins=64,
                    growth="leafwise", **extra)
        B = int(ds.mapper.total_bins)
        F = ds.X_binned.shape[1]
        p_w = effective_depth_params(make_params(base), F, B, rows)
        assert p_w.max_depth == depth and supports(p_w, F, B, rows), \
            "fixture no longer takes the batched expansion"
        assert leafwise_layout_supported(
            p_w, F, B, ds.X_binned.dtype.itemsize), \
            "gate fixture no longer admits the wired leaf-wise path"
        arms = {}
        for arm, params in (("wired", base),
                            ("legacy", dict(base, deep_layout="legacy"))):
            arms[arm] = train_device(make_params(params), ds)
            if timed:
                t0 = time.perf_counter()
                train_device(make_params(params), ds)
                ms = (time.perf_counter() - t0) * 1e3 / base["num_trees"]
                print(f"  leafwise depth cap {depth}, {rows} rows, {arm}: "
                      f"{ms:.1f} ms a tree (second run, host clock)")
        b_w, b_l = arms["wired"], arms["legacy"]
        for k in ("feature", "threshold", "left", "right", "is_cat"):
            np.testing.assert_array_equal(
                b_w.tree_arrays()[k], b_l.tree_arrays()[k],
                err_msg=f"wired vs legacy leafwise expansion, depth cap "
                        f"{depth}: {k!r}")
        np.testing.assert_allclose(b_w.value, b_l.value, atol=1e-5)
    print("leafwise wired expansion: trees bitwise vs legacy on device "
          "(depth 8; depth cap 12 = 4096 run slots)")


def smoke_hist_reduce_parity():
    """Feature-parallel reduction arm (r16, hist_reduce="feature") vs the
    fused arm ON THE REAL DEVICE: bitwise-identical trees (values
    included) on the tie-free fixture.  A single attached TPU runs the
    DEGENERATE feature program — full slice, packed-record combine, no
    collectives — which is exactly the program piece interpret-mode CI
    cannot vouch for: the sliced scan + bitcast pack/combine lower
    through different fusion shapes than the fused scan, and a lowering
    drift here would flip near-tie argmaxes on device.  (The collective
    halves — reduce-scatter bitwise vs psum slices, the all-gather
    combine — are pinned on the 8-virtual-device mesh in
    tests/test_hist_reduce.py; a multi-chip session should re-run that
    parity against real ICI once available.)"""
    import numpy as np

    import dryad_tpu as dryad
    from dryad_tpu.config import make_params
    from dryad_tpu.datasets import higgs_like
    from dryad_tpu.engine.train import train_device

    X, y = higgs_like(50_000, seed=47)
    ds = dryad.Dataset(X, y, max_bins=64)
    for growth, depth in (("depthwise", 8), ("leafwise", 8)):
        base = dict(objective="binary", num_trees=4, num_leaves=128,
                    max_bins=64, growth=growth, max_depth=depth)
        b_f = train_device(make_params(dict(base, hist_reduce="fused")), ds)
        b_x = train_device(make_params(dict(base, hist_reduce="feature")),
                           ds)
        for k in ("feature", "threshold", "left", "right", "is_cat",
                  "value", "gain"):
            np.testing.assert_array_equal(
                b_f.tree_arrays()[k], b_x.tree_arrays()[k],
                err_msg=f"hist_reduce fused vs feature ({growth}): {k!r}")
    print("hist-reduce fused vs feature: trees bitwise on device "
          "(both growers, degenerate 1-shard feature program)")


def smoke_predict_packed_parity():
    """Packed node-word traversal (r21) vs legacy ON THE REAL DEVICE:
    bitwise-identical raw scores across numeric/missing, categorical and
    multiclass models.  Interpret-mode CI pins the same identity on the
    CPU backend and the 8-virtual-device mesh; what only an attached TPU
    can vouch for is the LOWERING of the packed body — the uint32 limb
    shifts/masks and the single node-table gather fuse differently than
    the legacy seven-array reads, and a drift there would flip predict
    bits (the serve registry stages packed by default, so every fleet
    replica runs this program)."""
    import numpy as np

    import dryad_tpu as dryad
    from dryad_tpu.datasets import higgs_like
    from dryad_tpu.engine.predict import stage_trees, staged_layout

    X, y = higgs_like(20_000, seed=23)
    X = X.copy()
    X[::7, 3] = np.nan    # exercise default_left on device
    configs = [
        ("binary", dict(objective="binary", num_trees=6, num_leaves=31,
                        max_bins=64)),
        ("multiclass", dict(objective="multiclass", num_class=3,
                            num_trees=4, num_leaves=15, max_bins=64)),
    ]
    for name, p in configs:
        yy = (y if name == "binary"
              else (np.abs(X[:, 0]) * 7).astype(np.int32) % 3)
        ds = dryad.Dataset(X, yy, max_bins=64)
        booster = dryad.train(p, ds, backend="tpu")
        assert staged_layout(stage_trees(booster)[0]) == "packed", name
        booster.params = booster.params.replace(predict_layout="legacy")
        legacy = booster.predict_binned(ds.X_binned, raw_score=True,
                                        backend="tpu")
        booster.params = booster.params.replace(predict_layout="packed")
        packed = booster.predict_binned(ds.X_binned, raw_score=True,
                                        backend="tpu")
        np.testing.assert_array_equal(
            np.asarray(legacy), np.asarray(packed),
            err_msg=f"{name}: packed vs legacy predict on device")
    print(f"packed predict parity on device: {len(configs)} models — "
          "packed ≡ legacy bitwise (one node-word gather per level)")


def _warm_ms(fn, *args):
    """``fn(*args)`` once to compile, then once more on the host's clock:
    (result, milliseconds)."""
    import time

    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    got = jax.block_until_ready(fn(*args))
    return got, (time.perf_counter() - t0) * 1e3


def smoke_eval_walk_parity():
    """The training eval's walk (``train._fresh_tree``: the fresh tree's
    fields packed ON THE DEVICE, one node-word gather a level and
    ``select_bins``) vs the structure-of-arrays walk it replaced, on the
    real device, bitwise, at the cells' own sizes: 500,000 x 28 to depth
    12 and 100,000 x 2000 to depth 6.  Interpret-mode CI holds the same
    identity on the CPU at small sizes; what only the chip can vouch for
    is the lowering of the traced shifts, the (M, 2) gather and the masked
    reduce at these widths.  Both walks take the tree slot and the depth
    traced, as the trainer passes them; the times are one warm walk each
    on the host's clock (informational)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dryad_tpu.booster import CAT_WORDS
    from dryad_tpu.engine import train as engine_train
    from dryad_tpu.engine.predict import tree_leaves

    def random_tree(rng, M, F, B, depth_cap):
        """One random tree in the trainer's (1, M) tables, no deeper than
        ``depth_cap``, every splittable node split while nodes last."""
        out = jax.tree.map(np.array,
                           engine_train._empty_out_device(1, M, CAT_WORDS))
        open_nodes, used, depth = [(0, 0)], 1, 0
        while open_nodes and used + 2 <= M:
            node, d = open_nodes.pop(int(rng.integers(len(open_nodes))))
            out["feature"][0, node] = rng.integers(F)
            out["threshold"][0, node] = rng.integers(B)
            out["default_left"][0, node] = rng.random() < 0.5
            out["left"][0, node], out["right"][0, node] = used, used + 1
            if d + 1 < depth_cap:
                open_nodes += [(used, d + 1), (used + 1, d + 1)]
            used, depth = used + 2, max(depth, d + 1)
        out["value"][0] = rng.standard_normal(M)
        return out, depth

    @jax.jit
    def walk_packed(out, t, Xb, depth):
        tree = engine_train._fresh_tree(out, t, Xb.shape[1], 256, False)
        assert "node_word" in tree
        return tree_leaves(tree, Xb, depth)

    @jax.jit
    def walk_soa(out, t, Xb, depth):
        tree = {key: out[key][t] for key in engine_train._TREE_KEYS}
        return tree_leaves(tree, Xb, depth)

    rng = np.random.default_rng(33)
    for N, F, M, cap in ((500_000, 28, 511, 12), (100_000, 2000, 127, 6)):
        out_np, depth = random_tree(rng, M, F, 256, cap)
        assert depth == cap, (depth, cap)
        Xb = rng.integers(0, 256, (N, F), dtype=np.uint8)
        Xb[rng.random((N, F)) < 0.1] = 0        # the missing bin
        out = {k: jnp.asarray(v) for k, v in out_np.items()}
        args = (out, jnp.int32(0), jnp.asarray(Xb), jnp.int32(depth))
        packed, ms_p = _warm_ms(walk_packed, *args)
        soa, ms_s = _warm_ms(walk_soa, *args)
        np.testing.assert_array_equal(
            np.asarray(packed), np.asarray(soa),
            err_msg=f"eval walk {N} x {F} to depth {depth}")
        assert (out_np["feature"][0][np.asarray(packed)] < 0).all()
        print(f"eval walk {N} x {F} to depth {depth}: packed == "
              f"structure of arrays bitwise ({len(np.unique(packed))} "
              f"leaves reached); one walk {ms_p:.2f} ms against {ms_s:.2f}")


def smoke_score_update_parity():
    """The score update's record gather (``train._row_records``: one
    look-up a row in the composed ``(keys, 2)`` u32 table) vs the two 1-D
    gathers it replaced, ``value[key_leaf[row_key]]``, on the real device,
    bitwise, at the Higgs cells' own sizes: 10,000,000 keys into the 256
    leaf slots of the depth-wise grower and into the 8192 heap nodes of the
    batched leaf-wise one at cap 12, 511 tree nodes either way.  The values
    are arbitrary bit patterns (NaN payloads, signed zeros, denormals), so
    what the chip vouches for is that the bitcast round trip and the
    two-word gather hand back the f32's own bits.  The times are one warm
    look-up each on the host's clock (informational)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dryad_tpu.engine.train import _row_records

    @jax.jit
    def two_lookups(key_leaf, value, row_key):
        leaves = key_leaf[jnp.clip(row_key, 0, key_leaf.shape[0] - 1)]
        return value[leaves], leaves

    rng = np.random.default_rng(35)
    N, M = 10_000_000, 511
    for keys in (256, 8192):
        key_leaf = jnp.asarray(rng.integers(0, M, keys).astype(np.int32))
        value = jnp.asarray(rng.integers(0, 1 << 32, M, dtype=np.uint32)
                            .view(np.float32))
        # a few keys past the table's end: the clip is part of the look-up
        row_key = jnp.asarray(rng.integers(0, keys + 2, N).astype(np.int32))
        (v_rec, l_rec), ms_r = _warm_ms(jax.jit(_row_records), key_leaf,
                                        value, row_key)
        (v_two, l_two), ms_t = _warm_ms(two_lookups, key_leaf, value,
                                        row_key)
        np.testing.assert_array_equal(
            np.asarray(v_rec).view(np.uint32), np.asarray(v_two).view(np.uint32),
            err_msg=f"score update values, {keys} keys")
        np.testing.assert_array_equal(
            np.asarray(l_rec), np.asarray(l_two),
            err_msg=f"score update leaves, {keys} keys")
        print(f"score update {N} keys into {keys}: record gather == two 1-D "
              f"gathers bitwise; one look-up {ms_r:.2f} ms against {ms_t:.2f}")


def smoke_stage_profiler():
    """First per-stage device breakdown (r13): run the cheap tier of the
    stage-probe registry (engine/probes) on the attached device, each
    liveness-proven at runtime — a dead/hoisted stage raises instead of
    recording a 2x-fast lie.  Alongside the wired/legacy bench pairs this
    gives the next TPU-attached session its stage-level evidence in one
    command (ROADMAP standing satellite)."""
    from dryad_tpu.engine import probes

    for name in probes.SMOKE_PROBES:
        r = probes.run_probe(name, rows=200_000, K=3, reps=2)
        flag = "  SUSPECT" if r["spread"] > probes.SPREAD_SUSPECT else ""
        print(f"stage {name}: {r['ms']:.2f} ms spread {r['spread']:.3f} "
              f"(liveness-proven){flag}")


def smoke_train_parity():
    """Tiny end-to-end train on the ATTACHED device vs the CPU reference:
    identical tree structures and bitwise same-booster predict (the
    CLAUDE.md parity invariant).  Covers the chunked device program (no
    callback), bagging, and the leaf-renewal sort in one pass — a TPU-only
    lowering regression in any of them lands here instead of surfacing as
    a silently wrong bench number (VERDICT r4 weak #4)."""
    import dryad_tpu as dryad
    from dryad_tpu.datasets import higgs_like

    X, y = higgs_like(20_000, seed=31)
    ds = dryad.Dataset(X, y, max_bins=64)
    configs = [
        ("gbdt", dict(objective="binary", num_trees=8, num_leaves=31,
                      max_bins=64)),
        ("bagged", dict(objective="binary", num_trees=6, num_leaves=15,
                        max_bins=64, subsample=0.7, colsample=0.8)),
        ("l1-renewal", dict(objective="l1", num_trees=6, num_leaves=15,
                            max_bins=64)),
    ]
    for name, p in configs:
        bc = dryad.train(p, ds, backend="cpu")
        bt = dryad.train(p, ds, backend="tpu")
        np.testing.assert_array_equal(bc.feature, bt.feature,
                                      err_msg=f"{name}: tree structures")
        np.testing.assert_array_equal(bc.threshold, bt.threshold,
                                      err_msg=f"{name}: thresholds")
        pc = bc.predict_binned(ds.X_binned, raw_score=True, backend="cpu")
        pt = bc.predict_binned(ds.X_binned, raw_score=True, backend="tpu")
        np.testing.assert_array_equal(pc, np.asarray(pt),
                                      err_msg=f"{name}: predict bit-identity")
    print(f"train parity on device: {len(configs)} configs — structures "
          "identical, predict bitwise")


_ALL_SMOKES = [
    smoke_shared_vs_per_class,
    smoke_pallas_vs_xla,
    smoke_pallas_u16_and_records,
    smoke_pallas_wide_segment_count,
    smoke_pallas_natural_order,
    smoke_leafperm_move_oracle,
    smoke_hist_in_place_vs_plan,
    smoke_leafperm_wired_parity,
    smoke_leafwise_wired_parity,
    smoke_hist_reduce_parity,
    smoke_predict_packed_parity,
    smoke_eval_walk_parity,
    smoke_score_update_parity,
    smoke_stage_profiler,
]


def main(argv=None) -> int:
    """``--gate``: the driver-runnable on-device check (CLAUDE.md) — all
    kernel smokes + the train-parity pass; every failure is reported and
    the exit code is non-zero on ANY drift."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", action="store_true",
                    help="run all smokes + train parity; exit 1 on drift")
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print(f"GATE FAILED: no accelerator attached (platform "
              f"{dev.platform!r}) — every smoke here checks a device "
              "lowering")
        return 1
    print(f"device: {dev.platform} / {dev.device_kind} x{len(jax.devices())}")
    smokes = list(_ALL_SMOKES) + ([smoke_train_parity] if args.gate else [])
    failed = []
    for fn in smokes:
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — aggregate, report, exit 1
            failed.append((fn.__name__, e))
            print(f"FAIL {fn.__name__}: {e}")
    if failed:
        print(f"GATE FAILED: {len(failed)}/{len(smokes)} smokes drifted")
        return 1
    print(f"GATE OK: {len(smokes)} smokes clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
