"""A data-parallel job on a four-device CPU mesh through the WIRED batched
leaf-wise grower, as ``dryad.train(mesh=...)`` runs it: valid set, eval
callback, checkpoints.  Its trees, callback values and checkpoints are the
one-device job's.

Tier-1 at the smallest size that runs every wired level (1001 rows, which do
not divide by four, so a pad row rides along; 7 leaves, so the policy's cap is
3 + 4 = 7 and the expansion 64 columns): ``hist_backend="pallas"`` puts the
interpreted kernels under ``shard_map`` (what
``tests/test_leafperm_sharded.py::test_sharded_wired_*``, all ``slow``, run at
length).  The exchange is float32: four partial sums added by psum round
otherwise than one sum, so leaf values and the AUC agree to float32 rounding,
not to the bit; structures, covers and counts are exact.
"""

import numpy as np
import pytest

import jax

import dryad_tpu as dryad
from dryad_tpu.checkpoint import Checkpointer
from dryad_tpu.datasets import higgs_like
from dryad_tpu.obs.registry import default_registry

PARAMS = dict(objective="binary", metric="auc", growth="leafwise", num_leaves=7, max_depth=-1,
              max_bins=32, num_trees=3, min_data_in_leaf=5, hist_backend="pallas",
              hist_precision="exact", hist_reduce="auto")
ROWS, EVERY = 1001, 2


@pytest.fixture(scope="module")
def data():
    X, y = higgs_like(ROWS + 301, seed=47)
    ds = dryad.Dataset(X[:ROWS], y[:ROWS], max_bins=32)
    return ds, ds.bind(X[ROWS:], y[ROWS:])


def job(data, mesh, ckdir):
    ds, vds = data
    seen = []
    booster = dryad.train(PARAMS, ds, valid_sets=[vds], backend="tpu", mesh=mesh,
                          callbacks=[lambda it, ev: seen.append((it, ev["valid_auc"]))],
                          checkpoint_dir=str(ckdir), checkpoint_every=EVERY)
    return booster, seen


@pytest.fixture(scope="module")
def one(data, tmp_path_factory):
    return job(data, None, tmp_path_factory.mktemp("one"))


@pytest.fixture(scope="module")
def four(data, tmp_path_factory):
    from dryad_tpu.engine.distributed import make_mesh

    assert len(jax.devices()) >= 4, "conftest must provide the virtual devices"
    ckdir = tmp_path_factory.mktemp("four")
    booster, seen = job(data, make_mesh(jax.devices()[:4]), ckdir)
    gauges = default_registry().snapshot()["gauges"]
    return booster, seen, ckdir, gauges


@pytest.mark.distributed
def test_four_shards_grow_the_trees_one_device_grows(one, four):
    b1, b4 = one[0], four[0]
    assert b1.num_total_trees == b4.num_total_trees == PARAMS["num_trees"]
    for key in ("feature", "threshold", "left", "right", "is_cat", "default_left"):
        np.testing.assert_array_equal(b1.tree_arrays()[key], b4.tree_arrays()[key], err_msg=key)
    np.testing.assert_array_equal(b1.cover, b4.cover)          # counts are exact
    np.testing.assert_allclose(b1.value, b4.value, rtol=0, atol=1e-6)
    assert (b1.feature >= 0).sum() == 6 * PARAMS["num_trees"]  # every tree has its 7 leaves


@pytest.mark.distributed
def test_the_callback_sees_the_same_metric_in_lockstep(one, four):
    seen1, seen4 = one[1], four[1]
    assert [it for it, _ in seen1] == [it for it, _ in seen4] == list(range(PARAMS["num_trees"]))
    np.testing.assert_allclose([v for _, v in seen1], [v for _, v in seen4], rtol=0, atol=1e-6)


@pytest.mark.distributed
def test_the_checkpoint_of_sharded_state_reads_back(four):
    b4, _, ckdir, _ = four
    booster, iteration = Checkpointer(str(ckdir), every=EVERY).latest()
    assert iteration == EVERY
    for key in ("feature", "threshold", "left", "right", "value", "cover"):
        np.testing.assert_array_equal(booster.tree_arrays()[key],
                                      b4.tree_arrays()[key][:iteration], err_msg=key)


@pytest.mark.distributed
def test_the_job_ran_wired_at_the_policys_cap_and_says_what_it_exchanged(four):
    gauges = four[3]
    assert max(gauges["dryad_leafwise_depth_cap"].values()) == 7.0
    arms = {str(label) for label in gauges["dryad_policy_choice"]}
    assert any('gate="leafwise_layout"' in a and 'arm="layout"' in a for a in arms), arms
    payload = {str(label): v for label, v in gauges["dryad_comm_psum_bytes_per_iter"].items()}
    # the registry is the process's: another module's four-shard job (a
    # depth-wise one, say) may have left its own label beside this job's
    (label,) = [lbl for lbl in payload if 'shards="4"' in lbl and 'growth="leafwise"' in lbl]
    assert 'arm="fused"' in label and payload[label] > 0
