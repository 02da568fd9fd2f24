"""Tier-1 guard of the out-of-the-box growth policy (``growth="leafwise"``,
``max_depth=-1``): ``tests/test_leafwise_fast.py`` is ``slow`` as a whole, so
these two cases, small enough for tier-1, hold the batched grower to the
sequential one tree for tree on both of its per-level data movements at a
depth cap that ``unbounded_depth="auto"`` produces.  ``wired``: 70 leaves map
to cap 11, whose 2048 run slots lie past the 1024 the gate
``leafwise_layout.max_segments`` stopped at before PR 29 and inside its 4096,
the path the benchmark's ``higgs10m_leaf255.job_bestfirst`` cell runs (cap 12).
``plan``: the same job under ``deep_layout="legacy"``, the opt-out and what
caps 13 and 14 still take (sort, record gather, segmented histograms).

In interpret mode the wired case costs by its tiles, not its rows: the run
bookkeeping mandates ``2 * 2048 + 2`` tiles a level whatever the table holds,
and each is two 512 x 512 x 128 products in the move kernel: 100 s a level,
1072 s the case (measured, PR 29), so ``wired`` is ``slow``.  Tier-1 runs
``wired-xla-move``: the same grower, gate, bounds, run bookkeeping at 2048
slots and ``hist_from_layout``, with ``leafperm.move_level`` (unchanged, and
held to its numpy oracle by ``tests/test_leafperm.py``) replaced by the few
lines below: that oracle's sides, then one XLA scatter."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_leafwise_fast import _assert_same_tree, _fixture  # noqa: E402

from dryad_tpu.config import make_params  # noqa: E402
from dryad_tpu.engine import leafperm  # noqa: E402
from dryad_tpu.engine.grower import grow_any, grow_tree  # noqa: E402
from dryad_tpu.engine.leafwise_fast import (  # noqa: E402
    _MAX_FAST_DEPTH,
    _MAX_WIRED_SEGMENTS,
    effective_depth_params,
    leafwise_layout_supported,
    supports,
)


def _move_level_xla(lay_rec, lay_tile_run, run_rec, run_catmask=None, *, bin_dtype,
                    learn_missing=False, n_out_tiles=None, platform=None,
                    axis_name=None):
    """``leafperm.move_level`` as one scatter: the numpy oracle's sides
    (``layout_sides_np``), ``level_moves`` for the destinations, a row's rank
    among its tile's rows of the same side for its place."""
    T = leafperm._TILE_ROWS
    n_rows = lay_rec.shape[0]
    side = jax.pure_callback(
        lambda *a: leafperm.layout_sides_np(           # the callback is handed jax arrays
            *(None if x is None else np.asarray(x) for x in a),
            bin_dtype=bin_dtype, learn_missing=learn_missing),
        jax.ShapeDtypeStruct((n_rows,), jnp.int32),
        lay_rec, lay_tile_run, run_rec, run_catmask).reshape(n_rows // T, T)
    left, right = side == 0, side == 1
    counts = jnp.stack([left.sum(1), right.sum(1)], axis=1).astype(jnp.int32)
    dstl, dstr, base_l, base_r, _ = leafperm.level_moves(
        lay_tile_run, counts, run_rec.shape[0])
    li, ri = left.astype(jnp.int32), right.astype(jnp.int32)
    pos = jnp.where(left, dstl[:, None] + jnp.cumsum(li, 1) - li,
                    jnp.where(right, dstr[:, None] + jnp.cumsum(ri, 1) - ri, n_rows))
    out = jnp.zeros_like(lay_rec).at[pos.reshape(-1)].set(lay_rec, mode="drop")
    return out, base_l, base_r


@pytest.mark.parametrize("deep_layout,wired,xla_move", [
    pytest.param("auto", True, False, id="wired", marks=pytest.mark.slow),
    pytest.param("auto", True, True, id="wired-xla-move"),
    pytest.param("legacy", False, False, id="plan"),
])
def test_unbounded_leafwise_batched_equals_sequential(deep_layout, wired, xla_move,
                                                      monkeypatch):
    if xla_move:
        monkeypatch.setattr(leafperm, "move_level", _move_level_xla)
    Xb, g, h, bag, fmask, iscat = _fixture(n=6000)
    asked = make_params(dict(objective="l2", growth="leafwise", num_leaves=70,
                             max_depth=-1, min_data_in_leaf=20, hist_backend="pallas",
                             deep_layout=deep_layout))
    p = effective_depth_params(asked, Xb.shape[1], 32, Xb.shape[0])
    assert p.max_depth == 7 + 4 and 1024 < (1 << p.max_depth) <= _MAX_WIRED_SEGMENTS
    assert supports(p, Xb.shape[1], 32, Xb.shape[0])
    assert leafwise_layout_supported(
        p, Xb.shape[1], 32, Xb.dtype.itemsize, "tpu") is wired
    bat = grow_any(p, 32, Xb, g, h, bag, fmask, iscat)       # the trainer's own route
    seq = grow_tree(p, 32, Xb, g, h, bag, fmask, iscat)
    _assert_same_tree(seq, bat)
    leaves = int((jnp.asarray(bat["feature"]) >= 0).sum()) + 1
    assert leaves == 70 and int(bat["selected_splits"]) == 69
    # the expansion grew every valid split to the cap, the selection kept 69
    assert int(bat["expanded_splits"]) > 69
    assert 7 <= int(bat["max_depth"]) <= p.max_depth


def test_the_gate_admits_the_cell_s_cap_and_no_deeper():
    """The benchmark cell's 255 leaves with no ``max_depth`` is cap 12, 4096 run
    slots: the last the gate admits.  Caps 13 and 14 keep the plan path."""
    cell = make_params(dict(objective="binary", growth="leafwise", num_leaves=255,
                            max_depth=-1, hist_backend="pallas"))
    assert effective_depth_params(cell, 28, 256, 10_000_000).max_depth == 12
    for cap, wired in ((12, True), (13, False), (_MAX_FAST_DEPTH, False)):
        assert leafwise_layout_supported(
            cell.replace(max_depth=cap), 28, 256, 1, "tpu") is wired
