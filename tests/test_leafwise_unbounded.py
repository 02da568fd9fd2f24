"""Tier-1 guard of the out-of-the-box growth policy (``growth="leafwise"``,
``max_depth=-1``): ``tests/test_leafwise_fast.py`` is ``slow`` as a whole, so
this one case, small enough for tier-1, holds the batched grower to the
sequential one tree for tree on the path the benchmark's
``higgs10m_leaf255.job_bestfirst`` cell runs: ``unbounded_depth="auto"`` maps
"unbounded" to a depth cap whose ``2^cap`` segments are past the policy gate
``leafwise_layout.max_segments``, so the wired layout is off and the
expansion takes the plan path (sort, record gather, segmented histograms)."""

import os
import sys

import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_leafwise_fast import _assert_same_tree, _fixture  # noqa: E402

from dryad_tpu.config import make_params  # noqa: E402
from dryad_tpu.engine.grower import grow_any, grow_tree  # noqa: E402
from dryad_tpu.engine.leafwise_fast import (  # noqa: E402
    _MAX_WIRED_SEGMENTS,
    effective_depth_params,
    leafwise_layout_supported,
    supports,
)


def test_unbounded_leafwise_batched_equals_sequential_past_the_layout_gate():
    Xb, g, h, bag, fmask, iscat = _fixture(n=6000)
    asked = make_params(dict(objective="l2", growth="leafwise", num_leaves=70,
                             max_depth=-1, min_data_in_leaf=20, hist_backend="pallas"))
    p = effective_depth_params(asked, Xb.shape[1], 32, Xb.shape[0])
    assert p.max_depth == 7 + 4 and (1 << p.max_depth) > _MAX_WIRED_SEGMENTS
    assert supports(p, Xb.shape[1], 32, Xb.shape[0])
    assert not leafwise_layout_supported(p, Xb.shape[1], 32, Xb.dtype.itemsize, "tpu")
    bat = grow_any(p, 32, Xb, g, h, bag, fmask, iscat)       # the trainer's own route
    seq = grow_tree(p, 32, Xb, g, h, bag, fmask, iscat)
    _assert_same_tree(seq, bat)
    leaves = int((jnp.asarray(bat["feature"]) >= 0).sum()) + 1
    assert leaves == 70 and int(bat["selected_splits"]) == 69
    # the expansion grew every valid split to the cap, the selection kept 69
    assert int(bat["expanded_splits"]) > 69
    assert 7 <= int(bat["max_depth"]) <= p.max_depth
