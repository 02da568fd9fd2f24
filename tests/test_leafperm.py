"""Leaf-ordered permutation kernel (engine/leafperm.py): bitwise equality
with the numpy oracle in interpret mode, layout invariants, and the
multi-level refinement chain — with the _ALIGN-rounded per-tile
contributions Mosaic's HBM slicing requires."""

import numpy as np
import pytest

import jax.numpy as jnp

from dryad_tpu.engine import leafperm

T = leafperm._TILE_ROWS


WB = leafperm._REC_WB


def _mk_layout(rng, seg_counts):
    """Tile-aligned layout with contiguous-prefix segments (the level-0
    shape): distinctive record bytes, valid flag 1 at byte 8, zero
    sentinels."""
    lt = np.maximum(-(-np.asarray(seg_counts) // T), 1)
    n_tiles = int(lt.sum())
    rec = np.zeros((n_tiles * T, WB), np.uint8)
    tile_slot = np.repeat(np.arange(len(seg_counts)), lt).astype(np.int32)
    row_seg = np.full(n_tiles * T, -1, np.int32)
    base = np.concatenate([[0], np.cumsum(lt)])
    for s, cnt in enumerate(seg_counts):
        r0 = base[s] * T
        rec[r0:r0 + cnt] = rng.integers(1, 255, (cnt, WB), dtype=np.uint8)
        row_seg[r0:r0 + cnt] = s
    rec[:, 8] = row_seg >= 0
    return rec, tile_slot, row_seg


def _sides(rng, row_seg, p_right=0.5):
    return np.where(row_seg >= 0,
                    (rng.random(row_seg.size) < p_right).astype(np.int32),
                    2).astype(np.int32)


def _plant_sides(rec, side, n_seg):
    """Write the wanted side of every real row into feature 0's bin byte
    (bin 0 = left, 1 = right) and return the run records that split every
    segment on it (threshold 0): the move then derives exactly ``side``
    from the records, as it does from a grower's."""
    rec[:, 9] = side == 1
    return leafperm.pack_run_records(
        do=np.ones(n_seg), feature=np.zeros(n_seg), thresh=np.zeros(n_seg))


def _move(rec, tile_slot, run_rec, n_out_tiles):
    out, base_l, base_r = leafperm.move_level(
        jnp.asarray(rec), jnp.asarray(tile_slot), jnp.asarray(run_rec),
        n_out_tiles=n_out_tiles, bin_dtype=np.uint8)
    return np.asarray(out), base_l, base_r


def _run_level(rec, tile_slot, side, n_seg):
    run_rec = _plant_sides(rec, side, n_seg)
    bound = leafperm.tiles_bound(rec.shape[0], n_seg)
    got, _, base_r = _move(rec, tile_slot, run_rec, bound)
    n_out = int(base_r[-1]) + 1
    assert n_out <= bound, (n_out, bound)
    want, ts_new, rs_new = leafperm.permute_records_np(
        rec, tile_slot, side, n_seg, bound)
    return got, want, ts_new, rs_new, n_out


@pytest.mark.parametrize("seg_counts,p_right", [
    ([700, 3, 1200, 0, 513], 0.5),      # ragged, incl. empty segment
    ([2048], 0.0),                      # pass-through (all left)
    ([100, 100, 100], 1.0),             # all right
    ([1, 1, 1, 1], 0.5),                # tiny segments, all mandatory pads
])
def test_permute_matches_oracle(seg_counts, p_right):
    rng = np.random.default_rng(hash((tuple(seg_counts), p_right)) % 2**31)
    rec, tile_slot, row_seg = _mk_layout(rng, seg_counts)
    side = _sides(rng, row_seg, p_right)
    got, want, _, _, n_out = _run_level(rec, tile_slot, side,
                                        len(seg_counts))
    np.testing.assert_array_equal(got[: n_out * T], want[: n_out * T])


def _record_layout(rng, seg_counts, F, B, dtype, extra_tiles=0):
    """A tile-aligned layout of REAL layout records (make_layout_records
    of random bins in [0, B)), contiguous per segment, plus
    ``extra_tiles`` all-sentinel tiles absorbed into the last segment."""
    N = int(sum(seg_counts))
    Xb = rng.integers(0, B, (N, F)).astype(dtype)
    rec_nat = np.asarray(leafperm.make_layout_records(
        jnp.asarray(Xb), jnp.asarray(rng.normal(size=N).astype(np.float32)),
        jnp.asarray(rng.uniform(0.1, 1, N).astype(np.float32))))
    lt = np.maximum(-(-np.asarray(seg_counts) // T), 1)
    lt[-1] += extra_tiles
    base = np.concatenate([[0], np.cumsum(lt)])
    rec = np.zeros((int(base[-1]) * T, WB), np.uint8)
    off = np.concatenate([[0], np.cumsum(seg_counts)])
    for s, cnt in enumerate(seg_counts):
        rec[base[s] * T: base[s] * T + cnt] = rec_nat[off[s]: off[s + 1]]
    tile_run = np.repeat(np.arange(len(seg_counts)), lt).astype(np.int32)
    return rec, tile_run


def _assert_move_matches_oracle(rec, tile_run, run_rec, catmask=None,
                                n_out_tiles=None, **kw):
    """The fused move against permute_records_np with the sides computed
    in numpy from the per-tile split records: the WHOLE buffer, bitwise."""
    P = run_rec.shape[0]
    bound = n_out_tiles or leafperm.tiles_bound(rec.shape[0], P)
    side = leafperm.layout_sides_np(rec, tile_run, run_rec, catmask, **kw)
    out, base_l, base_r = leafperm.move_level(
        jnp.asarray(rec), jnp.asarray(tile_run), jnp.asarray(run_rec),
        None if catmask is None else jnp.asarray(catmask),
        n_out_tiles=bound, **kw)
    want, ts_new, _ = leafperm.permute_records_np(
        rec, tile_run, side, P, bound)
    np.testing.assert_array_equal(np.asarray(out), want)
    return want, ts_new, side


def _case_numeric(rng):
    rec, tr = _record_layout(rng, [700, 3, 1200, 0, 513], 28, 256, np.uint8)
    rr = leafperm.pack_run_records(
        do=np.ones(5), feature=[0, 27, 13, 5, 1],
        thresh=[128, 0, 255, 7, 60])
    _, _, side = _assert_move_matches_oracle(rec, tr, rr, bin_dtype=np.uint8)
    assert (side == 0).any() and (side == 1).any()


def _case_learn_missing(rng):
    # bin 0 is "missing": it follows the run's default-left bit whatever
    # the threshold — one run of each default, thresholds that keep bin 0
    # on the left of the plain rule
    rec, tr = _record_layout(rng, [900, 900], 6, 8, np.uint8)
    rr = leafperm.pack_run_records(
        do=[1, 1], feature=[2, 4], thresh=[3, 3], dleft=[1, 0])
    _, _, side = _assert_move_matches_oracle(
        rec, tr, rr, bin_dtype=np.uint8, learn_missing=True)
    bin0 = np.stack([rec[: 1024, 9 + 2], rec[1024:, 9 + 4]]) == 0
    valid = np.stack([rec[: 1024, 8], rec[1024:, 8]]) == 1
    s2 = side.reshape(2, 1024)
    assert (s2[0][bin0[0] & valid[0]] == 0).all()     # default left
    assert (s2[1][bin0[1] & valid[1]] == 1).all()     # default right
    assert (bin0 & valid).sum() > 50
    # and the flag off: bin 0 is an ordinary bin, left of threshold 3
    _, _, side = _assert_move_matches_oracle(rec, tr, rr, bin_dtype=np.uint8)
    assert (side.reshape(2, 1024)[1][bin0[1] & valid[1]] == 0).all()


def _case_categorical(rng):
    # run 0 numeric, runs 1-2 route by their bitset rows; 200 bins, so
    # the mask pads to the next lane multiple inside move_level
    B = 200
    rec, tr = _record_layout(rng, [600, 1100, 300], 9, B, np.uint8)
    rr = leafperm.pack_run_records(
        do=[1, 1, 1], feature=[1, 8, 3], thresh=[90, 0, 0],
        is_cat=[0, 1, 1], dleft=[0, 1, 1])
    cm = rng.random((3, B)) < 0.5
    for lm in (False, True):
        _, _, side = _assert_move_matches_oracle(
            rec, tr, rr, cm, bin_dtype=np.uint8, learn_missing=lm)
    bins1 = rec[1024: 1024 + 1100, 9 + 8]
    np.testing.assert_array_equal(side[1024: 1024 + 1100] == 0, cm[1][bins1])


def _case_u16(rng):
    # bins past 255 live in two record bytes (little-endian); thresholds
    # on both sides of the byte boundary
    rec, tr = _record_layout(rng, [800, 800, 500], 20, 1000, np.uint16)
    rr = np.array(leafperm.pack_run_records(
        do=[1, 1, 1], feature=[0, 19, 7], thresh=[255, 256, 700],
        dleft=[1, 0, 1]))
    cm = rng.random((3, 1000)) < 0.3
    _assert_move_matches_oracle(rec, tr, rr, bin_dtype=np.uint16)
    rr[2, 0] |= np.uint32(1) << 29                   # run 2 categorical
    _assert_move_matches_oracle(rec, tr, rr, cm, bin_dtype=np.uint16,
                                learn_missing=True)


def _case_pass_through_and_dead(rng):
    # run 1 does not split (do = 0: every valid row left, whatever its
    # other bits say); run 3 is DEAD (a zero record) and owns only
    # absorbed all-sentinel tiles, as advance_runs leaves them
    rec, tr = _record_layout(rng, [700, 900, 300], 5, 64, np.uint8,
                             extra_tiles=3)
    tr[-2:] = 3
    rr = leafperm.pack_run_records(
        do=[1, 0, 1, 0], feature=[0, 1, 2, 0], thresh=[30, 10, 50, 0],
        dleft=[0, 1, 0, 0])
    _, _, side = _assert_move_matches_oracle(rec, tr, rr, bin_dtype=np.uint8)
    run_of = np.repeat(tr, T)
    assert set(side[run_of == 1]) == {0, 2}
    assert set(side[run_of == 3]) == {2}


def _case_bagging_root(rng):
    # level 0 of a bagged tree: the natural-order buffer is the layout,
    # out-of-bag rows carry flag 0 and are dropped by this first move
    N, F, L = 3000, 12, 8
    Xb = rng.integers(0, 256, (N, F)).astype(np.uint8)
    bag = rng.random(N) < 0.7
    rec_nat = leafperm.make_layout_records(
        jnp.asarray(Xb), jnp.asarray(rng.normal(size=N).astype(np.float32)),
        jnp.asarray(np.ones(N, np.float32)), valid=jnp.asarray(bag))
    n_buf = leafperm.wired_tiles_bound(-(-N // T), L)
    rec, tr, _ = leafperm.natural_root_layout(rec_nat, L, n_buf)
    rr = leafperm.pack_run_records(
        do=np.arange(L) == 0, feature=np.full(L, 5), thresh=np.full(L, 99))
    want, _, side = _assert_move_matches_oracle(
        np.asarray(rec), np.asarray(tr), rr, n_out_tiles=n_buf,
        bin_dtype=np.uint8)
    assert (side[:N][~bag] == 2).all()
    assert int((want[:, 8] == 1).sum()) == int(bag.sum())
    assert int((side == 1).sum()) == int((bag & (Xb[:, 5] > 99)).sum())


def _case_three_level_chain(rng):
    # each level's output layout (the oracle's maps) feeds the next move,
    # with fresh features and thresholds; rows are never lost or doubled
    rec, tr = _record_layout(rng, [5000, 2000], 28, 256, np.uint8)
    n_rows = int((rec[:, 8] == 1).sum())
    P = 2
    for level in range(3):
        rr = leafperm.pack_run_records(
            do=np.arange(P) % 3 != 2, feature=rng.integers(0, 28, P),
            thresh=rng.integers(60, 200, P))
        want, ts_new, _ = _assert_move_matches_oracle(
            rec, tr, rr, bin_dtype=np.uint8)
        rec, tr, P = want, ts_new.astype(np.int32), 2 * P
        assert int((rec[:, 8] == 1).sum()) == n_rows, level


@pytest.mark.parametrize("case", [
    _case_numeric, _case_learn_missing, _case_categorical, _case_u16,
    _case_pass_through_and_dead, _case_bagging_root,
    _case_three_level_chain], ids=lambda f: f.__name__[6:])
def test_fused_move_matches_oracle(case):
    """The level move as the growers call it — sides, ranks and
    destinations derived inside the kernels from per-tile split records —
    is byte-for-byte the oracle's buffer."""
    case(np.random.default_rng(11))


def test_multi_level_chain():
    """Three refinement levels keep every real record exactly once, all
    pads zero, and the kernel bitwise-equal to the oracle at each level
    (the oracle's returned tile/segment maps drive the next level — the
    exact bookkeeping a grower integration would)."""
    rng = np.random.default_rng(7)
    rec, tile_slot, row_seg = _mk_layout(rng, [5000, 2000])

    def ident(rec):
        # a record's identity: every byte but the planted side (byte 9)
        keep = np.delete(rec, 9, axis=1)
        return {bytes(r) for r in keep if r.any()}

    orig = ident(rec)
    n_seg = 2
    for level in range(3):
        side = _sides(rng, row_seg, 0.4)
        got, want, ts_new, rs_new, n_out = _run_level(
            rec, tile_slot, side, n_seg)
        np.testing.assert_array_equal(got[: n_out * T], want[: n_out * T])
        rec = want[: n_out * T]
        tile_slot = ts_new[: n_out].astype(np.int32)
        row_seg = rs_new[: n_out * T].astype(np.int32)
        n_seg = 2 * n_seg
        assert ident(rec) == orig, \
            f"level {level}: record set changed"
        assert not rec[row_seg < 0].any(), f"level {level}: nonzero pads"


def test_stability_within_side():
    """Real rows keep their source order within (segment, side) — the
    grower's determinism (and CPU parity) rides on stable partition."""
    rng = np.random.default_rng(3)
    cnt = 1500
    rec, tile_slot, row_seg = _mk_layout(rng, [cnt])
    idx = np.arange(1, cnt + 1, dtype=np.uint32)     # nonzero tags
    rec[:cnt, :4] = idx.view(np.uint8).reshape(cnt, 4)
    side = _sides(rng, row_seg, 0.5)
    got, want, ts_new, rs_new, n_out = _run_level(rec, tile_slot, side, 1)
    np.testing.assert_array_equal(got[: n_out * T], want[: n_out * T])
    out = got[: n_out * T]
    rs = rs_new[: n_out * T]
    for seg in (0, 1):                               # left child, right child
        rows = out[rs == seg]
        tags = rows[:, :4].copy().view(np.uint32).ravel()
        assert (np.diff(tags) > 0).all(), f"segment {seg} order broken"
    all_tags = out[rs >= 0][:, :4].copy().view(np.uint32).ravel()
    np.testing.assert_array_equal(np.sort(all_tags), idx)


def test_alignment_of_all_writes():
    """Every destination offset is _ALIGN-divisible — the Mosaic HBM
    slicing constraint that forced the rounded layout (an arbitrary
    offset fails to lower: 'not divisible by the tiling (8)')."""
    rng = np.random.default_rng(9)
    rec, tile_slot, row_seg = _mk_layout(rng, [700, 3, 900])
    side = _sides(rng, row_seg, 0.37)
    counts = np.stack([(side.reshape(-1, T) == 0).sum(1),
                       (side.reshape(-1, T) == 1).sum(1)], axis=1)
    dstl, dstr, _, _, _ = leafperm.level_moves(
        jnp.asarray(tile_slot), jnp.asarray(counts, jnp.int32), 3)
    assert (np.asarray(dstl) % leafperm._ALIGN == 0).all()
    assert (np.asarray(dstr) % leafperm._ALIGN == 0).all()


def test_wired_level_preserves_plan_order():
    """INTEGRATION contract (the wired deep phase rides on this, not just
    the kernel): after the handoff conversion (initial_layout) and one
    full wired level (move_level -> advance_runs),
    every child segment holds its rows in the SAME stable row-id order
    the aligned tile plan would produce for that child's selection — the
    per-slot order convention shared by every histogram path."""
    rng = np.random.default_rng(33)
    N, L = 5000, 8
    slot_of = rng.integers(0, 4, N).astype(np.int32)   # slots 0..3 live
    bag = rng.random(N) < 0.8
    # records tagged with the row id so order is observable
    rec_nat = np.zeros((N, WB), np.uint8)
    rec_nat[:, :4] = np.arange(1, N + 1, dtype=np.uint32).view(
        np.uint8).reshape(N, 4)
    rec_nat[:, 8] = 1                                  # valid flag
    # one level: slots 0 and 2 split (right children -> slots 4, 5) on
    # feature 0's bin, planted per row: 1 = goes right
    u = rng.random(N)
    go_right = {0: u < 0.5, 2: u < 0.3}
    rec_nat[:, 9] = np.where(slot_of == 0, go_right[0],
                             np.where(slot_of == 2, go_right[2], 1))

    n_buf = leafperm.wired_tiles_bound(-(-N // T), L)
    sel = np.where(bag, slot_of, L).astype(np.int32)
    live = np.zeros(L, bool)
    live[:4] = True
    rec_lay, tile_run, run_slot = leafperm.initial_layout(
        jnp.asarray(rec_nat), jnp.asarray(sel), jnp.asarray(live), L, n_buf)
    assert [int(run_slot[r]) for r in range(4)] == [0, 1, 2, 3]

    # runs 1 and 3 pass through (their bin-1 rows stay left all the same)
    run_rec = leafperm.pack_run_records(
        do=np.isin(np.arange(L), [0, 2]), feature=np.zeros(L),
        thresh=np.zeros(L))
    out, base_l, base_r = _move(np.asarray(rec_lay), np.asarray(tile_run),
                                run_rec, n_buf)
    run_do = np.zeros(L, bool)
    run_do[[0, 2]] = True
    run_right = np.zeros(L, np.int32)
    run_right[0], run_right[2] = 4, 5
    tile_run2, run_slot2 = leafperm.advance_runs(
        run_slot, jnp.asarray(run_do), jnp.asarray(run_right),
        base_l, base_r, n_buf)
    # runs: old 0..3 keep slots 0..3 (left children / pass-through),
    # new runs 4,5 carry the right-child slots in run order
    assert [int(run_slot2[r]) for r in range(6)] == [0, 1, 2, 3, 4, 5]

    # expected per-slot membership after the split
    child_rows = {s: [] for s in range(6)}
    for r in range(N):
        if not bag[r]:
            continue
        s = slot_of[r]
        if s in go_right and go_right[s][r]:
            child_rows[{0: 4, 2: 5}[s]].append(r + 1)
        else:
            child_rows[s].append(r + 1)
    row_run2 = np.repeat(np.asarray(tile_run2), T)
    rs2 = np.asarray(run_slot2)[row_run2]
    tags2 = out[:, :4].copy().view(np.uint32).ravel()
    for s in range(6):
        got = tags2[(rs2 == s) & (tags2 > 0)]
        # stable row-id order per slot — exactly the aligned plan's order
        np.testing.assert_array_equal(got, np.asarray(child_rows[s]),
                                      err_msg=f"slot {s} order")


def test_hist_from_layout_post_permute_vs_plan():
    """Histograms off a POST-permute layout (interior _ALIGN sentinels
    shift rows across tile boundaries) vs the tile-plan path: counts
    EXACT (sums of 1.0), grad/hess to the documented ulp-class tolerance
    — the wired grower's per-level histogram contract."""
    from dryad_tpu.engine.histogram import build_hist_segmented

    import jax.numpy as jnp

    rng = np.random.default_rng(37)
    N, F, B, L = 6000, 10, 64, 4
    Xb = rng.integers(1, B, size=(N, F), dtype=np.uint8)
    g = rng.normal(size=N).astype(np.float32)
    h = rng.uniform(0.1, 1, N).astype(np.float32)
    slot_of = rng.integers(0, 2, N).astype(np.int32)   # slots 0,1 live

    rec_nat = leafperm.make_layout_records(
        jnp.asarray(Xb), jnp.asarray(g), jnp.asarray(h))
    n_buf = leafperm.wired_tiles_bound(-(-N // T), L)
    live = np.zeros(L, bool)
    live[:2] = True
    rec_lay, tile_run, run_slot = leafperm.initial_layout(
        rec_nat, jnp.asarray(slot_of), jnp.asarray(live), L, n_buf)

    # split slot 0 -> (0, 2) on feature 3 at bin 35 (~45% go right);
    # slot 1 passes through
    right = (slot_of == 0) & (Xb[:, 3] > 35)
    run_rec = leafperm.pack_run_records(
        do=np.arange(L) == 0, feature=np.full(L, 3), thresh=np.full(L, 35))
    out, base_l, base_r = leafperm.move_level(
        rec_lay, tile_run, jnp.asarray(run_rec), bin_dtype=np.uint8)

    # children: left of 0 (=slot 0), right of 0 (new), left of 1 (pass)
    lt_l = np.asarray(base_l[1:] - base_l[:-1])
    lt_r = np.asarray(base_r[1:] - base_r[:-1])
    seg_first = jnp.asarray([int(base_l[0]), int(base_r[0]),
                             int(base_l[1])], jnp.int32)
    seg_nt = jnp.asarray([int(lt_l[0]), int(lt_r[0]), int(lt_l[1])],
                         jnp.int32)
    bound = int(np.asarray(seg_nt).sum()) + 2
    got = np.asarray(leafperm.hist_from_layout(
        out, seg_first, seg_nt, 3, B, F, np.uint8, bound))

    sel = np.where(slot_of == 0, np.where(right, 1, 0), 2).astype(np.int32)
    want = np.asarray(build_hist_segmented(
        jnp.asarray(Xb), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(sel), 3, B, backend="pallas"))
    np.testing.assert_array_equal(got[:, 2], want[:, 2])  # counts exact
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


def test_hist_from_layout_bitwise_vs_plan():
    """Histograms straight from a leaf-ordered layout (contiguous tile
    runs, no sort/row-gather) are BITWISE equal to the tile-plan path on
    the same selection — the integration's parity anchor."""
    from dryad_tpu.engine.histogram import build_hist_segmented

    rng = np.random.default_rng(21)
    N, F, B, S = 6000, 12, 64, 4
    Xb = rng.integers(1, B, size=(N, F), dtype=np.uint8)
    g = rng.normal(size=N).astype(np.float32)
    h = rng.uniform(0.1, 1, N).astype(np.float32)
    seg_of = rng.integers(0, S, N).astype(np.int32)   # 4 segments

    rec_nat = np.asarray(leafperm.make_layout_records(
        jnp.asarray(Xb), jnp.asarray(g), jnp.asarray(h)))
    # build the layout: rows grouped by segment in ORIGINAL row order
    # (the plan path's stable sort produces the same per-slot order)
    lt = np.maximum(-(-np.bincount(seg_of, minlength=S) // T), 1)
    base = np.concatenate([[0], np.cumsum(lt)])
    rec = np.zeros(((base[-1]) * T, leafperm._REC_WB), np.uint8)
    fill = np.zeros(S, np.int64)
    for r in range(N):
        s = seg_of[r]
        rec[base[s] * T + fill[s]] = rec_nat[r]
        fill[s] += 1

    # select segments 2 and 0 out of order, PLUS a genuinely EMPTY
    # selection in the middle (its mandatory slot must zero-init its
    # output block and must NOT shift segment 0's tiles past the bound —
    # the review-caught truncation bug)
    sel_segs = [2, None, 0]
    seg_first = jnp.asarray(
        [int(base[s]) if s is not None else 0 for s in sel_segs], jnp.int32)
    seg_nt = jnp.asarray(
        [int(lt[s]) if s is not None else 0 for s in sel_segs], jnp.int32)
    bound = int(np.maximum(np.asarray(seg_nt), 1).sum())  # documented bound
    got = np.asarray(leafperm.hist_from_layout(
        jnp.asarray(rec), seg_first, seg_nt, 3, B, F, np.uint8, bound))

    colof = {2: 0, 0: 2}
    sel = np.asarray([colof.get(int(s), 3) for s in seg_of], np.int32)
    want = np.asarray(build_hist_segmented(
        jnp.asarray(Xb), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(sel), 3, B, backend="pallas"))
    np.testing.assert_array_equal(got, want)
    assert not got[1].any()                       # empty slot zero-inited


def _segment_layout(rec_nat, seg_rows, hole_after=None):
    """A layout buffer whose segment s holds the records of ``seg_rows[s]``
    in that order from its first tile on; ``hole_after[s] = k`` puts one
    all-sentinel tile after the segment's first k tiles.  Returns (rec,
    first tile of each segment, tiles of each segment)."""
    tiles, first, nt = [], [], []
    for s, rows in enumerate(seg_rows):
        n = max(-(-len(rows) // T), 1)
        seg = np.zeros((n * T, WB), np.uint8)
        seg[:len(rows)] = rec_nat[rows]
        seg = seg.reshape(n, T, WB)
        k = (hole_after or {}).get(s)
        if k is not None:
            seg = np.concatenate([seg[:k], np.zeros((1, T, WB), np.uint8),
                                  seg[k:]])
        first.append(sum(t.shape[0] for t in tiles))
        nt.append(seg.shape[0])
        tiles.append(seg)
    return np.concatenate(tiles).reshape(-1, WB), first, nt


def _case_hist_u16(rng):
    return dict(F=10, B=512, dtype=np.uint16, seg_counts=[700, 3, 1200],
                cols=[2, 0, 1])


def _case_hist_two_feature_chunks(rng):
    # 40 features at 256 bins: Fc = 32, so the grid has two feature chunks
    # and chunk 1 selects its own bytes of the same record block
    return dict(F=40, B=256, dtype=np.uint8, seg_counts=[600, 520],
                cols=[1, 0])


def _case_hist_empty_between_live(rng):
    # column 1 selects nothing (no tile: its plan slot is skipped from
    # tile-sized data), column 3 an empty child's mandatory tile (live and
    # all sentinels: the kernel's own branch zero-fills it)
    return dict(F=12, B=64, dtype=np.uint8, seg_counts=[900, 0, 400, 30],
                cols=[2, None, 0, 1, 3])


def _case_hist_sentinel_tile_inside(rng):
    # segment 1: one full tile, an ALL-SENTINEL tile, the rest; the plan
    # path groups the same rows into the same two tiles
    return dict(F=12, B=64, dtype=np.uint8, seg_counts=[300, T + 200, 40],
                cols=[0, 1, 2], hole_after={1: 1})


def _case_hist_out_of_bag(rng):
    # flag-0 records with real g, h and bins between the in-bag rows: they
    # regroup the tiles' partial sums, so g and h are dyadic (every sum
    # exact in float32) and the comparison stays bitwise
    return dict(F=12, B=64, dtype=np.uint8, seg_counts=[1300, 700],
                cols=[1, 0], bag_rate=0.7, dyadic=True)


def _case_hist_both_children(rng):
    # the non-subtraction call: 2P columns, [left 0..P-1 | right P..2P-1],
    # over every tile of the layout (no half bound)
    return dict(F=12, B=64, dtype=np.uint8,
                seg_counts=[500, 100, 0, 700, 650, 20],
                cols=[0, 3, 1, 4, 2, 5], whole=True)


@pytest.mark.parametrize("case", [
    _case_hist_u16, _case_hist_two_feature_chunks,
    _case_hist_empty_between_live, _case_hist_sentinel_tile_inside,
    _case_hist_out_of_bag, _case_hist_both_children],
    ids=lambda f: f.__name__[len("_case_hist_"):])
def test_hist_from_layout_in_place_vs_plan(case):
    """The in-place kernel (record tiles read where they lie, unpacked in
    VMEM) BITWISE against the plan path, which stages natural-order rows
    for the shared body: the shapes no cell runs."""
    from dryad_tpu.engine.histogram import build_hist_segmented

    rng = np.random.default_rng(131)
    c = case(rng)
    F, B, dtype, counts = c["F"], c["B"], c["dtype"], c["seg_counts"]
    N, S = int(sum(counts)), len(counts)
    Xb = rng.integers(0, B, size=(N, F)).astype(dtype)
    if c.get("dyadic"):
        g = (rng.integers(-1023, 1024, N) / 256).astype(np.float32)
        h = (rng.integers(1, 256, N) / 256).astype(np.float32)
    else:
        g = rng.normal(size=N).astype(np.float32)
        h = rng.uniform(0.1, 1, N).astype(np.float32)
    seg_of = rng.permutation(np.repeat(np.arange(S), counts)).astype(np.int32)
    bag = rng.random(N) < c.get("bag_rate", 1.0)
    rec_nat = np.asarray(leafperm.make_layout_records(
        jnp.asarray(Xb), jnp.asarray(g), jnp.asarray(h), jnp.asarray(bag)))
    rec, first, nt = _segment_layout(
        rec_nat, [np.nonzero(seg_of == s)[0] for s in range(S)],
        c.get("hole_after"))

    # cols[j] = the segment histogrammed into column j (None: an empty
    # selection, whose mandatory plan slot only zero-fills its column)
    cols = c["cols"]
    seg_first = jnp.asarray([0 if s is None else first[s] for s in cols],
                            jnp.int32)
    seg_nt = jnp.asarray([0 if s is None else nt[s] for s in cols],
                         jnp.int32)
    P = len(cols)
    bound = (leafperm.wired_sel_tiles_bound(0, rec.shape[0] // T, P, False)
             if c.get("whole")
             else int(np.maximum(np.asarray(seg_nt), 1).sum()))
    got = np.asarray(leafperm.hist_from_layout(
        jnp.asarray(rec), seg_first, seg_nt, P, B, F, dtype, bound))

    colof = np.full(S, P, np.int32)
    for j, s in enumerate(cols):
        if s is not None:
            colof[s] = j
    sel = np.where(bag, colof[seg_of], P).astype(np.int32)
    want = np.asarray(build_hist_segmented(
        jnp.asarray(Xb), jnp.asarray(g), jnp.asarray(h), jnp.asarray(sel),
        P, B, backend="pallas"))
    assert want[:, 2, 0].sum() == bag[np.isin(
        seg_of, [s for s in cols if s is not None])].sum()   # rows counted
    np.testing.assert_array_equal(got, want)
    for j, s in enumerate(cols):
        if s is None or counts[s] == 0:
            assert not got[j].any()


def test_hist_from_layout_stages_nothing_row_sized():
    """Outside the ``pallas_call``, ``hist_from_layout`` holds no equation
    over ``n_sel_tiles * T`` rows: the kernel reads the layout buffer as
    it lies (only its reshape to tiles, a bitcast, touches it), and the
    plan is tile-sized."""
    import jax

    n_in, n_sel, P, F, B = 8, 6, 2, 8, 16

    def fn(rec, sf, sn):
        # the chip's program: the interpreter's alone is handed gathered
        # tiles (see _hist_tiles_rec)
        return leafperm.hist_from_layout(rec, sf, sn, P, B, F, np.uint8,
                                         n_sel, platform="tpu")

    closed = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((n_in * T, WB), np.uint8),
        jax.ShapeDtypeStruct((P,), np.int32),
        jax.ShapeDtypeStruct((P,), np.int32))
    seen, big = set(), []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            seen.add(name)
            if name == "pallas_call":
                continue
            subs = [v for v in eqn.params.values()
                    if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
            if subs:
                for sub in subs:
                    walk(getattr(sub, "jaxpr", sub))
                continue
            if name == "reshape":
                continue
            for v in list(eqn.invars) + list(eqn.outvars):
                if int(np.prod(v.aval.shape)) >= n_sel * T:
                    big.append((name, v.aval))

    walk(closed.jaxpr)
    assert "pallas_call" in seen
    assert not big, big
