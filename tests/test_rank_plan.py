"""The growth-policy envelope after PR 32 (one rule, by peak residency, with
constants that envelop what the TPU compiler reckons) and the λ-plan's gauges."""

import numpy as np
import pytest

from dryad_tpu.config import (
    LEAFWISE_CELL_BYTES,
    LEAFWISE_PEAK_FACTOR,
    LEAFWISE_ROW_BYTES,
    LEAFWISE_TOTAL_BYTES_BUDGET,
    effective_depth_params,
    leafwise_fast_supported,
    leafwise_rows_bytes,
    make_params,
)

LEAF255 = dict(growth="leafwise", num_leaves=255, max_depth=-1)
HBM_BYTES = int(15.75 * (1 << 30))        # what the v5e's compiler allows one program


def reckoned(rows, features, depth, bins=256):
    """The rule's figure for one device: the pinned expansion written out
    here, the rows' term the rule's own (the compiled shapes below hold it
    to the compiler's figures)."""
    pinned = (1 << (depth - 1)) * 3 * features * bins * 4
    per_row = LEAFWISE_ROW_BYTES + 16 + features * (LEAFWISE_CELL_BYTES + 1)
    return LEAFWISE_PEAK_FACTOR * pinned + leafwise_rows_bytes(depth, features, 1, rows, per_row)


@pytest.mark.parametrize("features,rows,cap", [
    (28, 10_000_000, 12),       # higgs10m_leaf255: as before
    (42, 2_270_296, 12),        # the widest table the retired 256 MiB cap admitted
    (43, 2_270_296, 12),        # ... and the narrowest it refused
    (136, 2_270_296, 12),       # mslr2m_leaf255: 12.77 of 12.88 GB reckoned, 9.72 compiled
    (137, 2_270_296, 12),       # the source table's count of features
    (136, 2_400_000, 11),       # a few more rows: one level down, not the sequential grower
    (300, 1_000_000, 10),       # pinned 1.9 GB at cap 12: two levels down
    (2000, 400_000, -1),        # Epsilon's table: pinned 3.1 GB at cap 10 still
    (136, 10_000_000, -1),      # the rows alone: 14.8 GB reckoned
    (28, 20_000_000, -1),       # 670 B a row compiled at this width: 13.4 GB
    (8, 11_000_000, 12),        # expo11m_leaf255: 8.82 GB reckoned, 8.17 compiled
    # refused by the budget alone, as 20M x 28 is: 14.58 GB reckoned, 14.10
    # compiled (under the compiler's 15.75 with its arguments; not run on a chip)
    (8, 20_000_000, -1),
])
def test_unbounded_leafwise_growth_is_admitted_by_peak_residency(features, rows, cap):
    p = make_params(dict(objective="lambdarank", **LEAF255))
    got = effective_depth_params(p, features, 256, rows)
    assert got.max_depth == cap
    assert (got is p) == (cap == -1)
    for depth in (12, 11, 10):           # the documented cap, then two steps under it
        fits = reckoned(rows, features, depth) <= LEAFWISE_TOTAL_BYTES_BUDGET
        assert fits == (cap >= depth)


# One boosting iteration of the batched grower (engine.train.audit_iteration_fn,
# binary objective, 256 bins) compiled ahead of time for a v5e by the TPU's own
# compiler (PR 32; PERF.md section 6): rows, features, max_depth, num_leaves,
# temp_size_in_bytes (None: the compiler ran out of the chip's memory),
# argument_size_in_bytes.
COMPILED = [
    (2_270_296, 28, 12, 255, 2_547_784_704, 93_124_096),
    (5_000_000, 28, 12, 255, 4_377_757_184, 205_039_104),
    (10_000_000, 28, 12, 255, 7_729_731_584, 410_037_760),
    (15_000_000, 28, 12, 255, 11_080_991_744, 615_041_024),
    (20_000_000, 28, 12, 255, 14_452_551_168, 820_039_680),
    (2_270_296, 64, 12, 255, 5_141_300_736, 165_774_848),
    (5_000_000, 64, 12, 255, 4_780_249_088, 365_041_152),
    (8_600_000, 64, 12, 255, 7_194_014_720, 627_843_072),
    (10_000_000, 64, 12, 255, 8_132_288_000, 730_037_760),
    (2_270_296, 136, 10, 255, 2_932_192_256, 329_239_040),
    (2_270_296, 136, 11, 255, 5_053_129_216, 329_239_040),
    (10_000_000, 136, 11, 255, 11_395_534_336, 1_450_037_760),
    (1_000_000, 136, 12, 255, 9_508_078_592, 145_047_040),
    (2_270_296, 136, 12, 255, 9_717_486_080, 329_239_040),
    (5_000_000, 136, 12, 255, 9_024_292_352, 725_045_760),
    (10_000_000, 136, 12, 255, 11_823_775_232, 1_450_037_760),
    (16_000_000, 136, 12, 255, None, None),
    (100_000, 180, 12, 255, 9_705_969_152, 19_355_648),
    (1_000_000, 300, 10, 255, 4_516_578_816, 313_057_792),
    (2_900_000, 300, 10, 255, 7_505_414_144, 907_770_368),
    (1_000_000, 300, 11, 255, 8_501_444_096, 313_057_792),
    (4_000_000, 300, 11, 255, 10_644_744_704, 1_252_039_680),
    (1_000_000, 300, 12, 255, 16_471_025_664, 313_057_792),
    (400_000, 2000, 6, 63, 6_607_345_152, 803_613_184),
    (730_000, 2000, 6, 63, 11_888_215_552, 1_466_806_272),
    (1_000_000, 2000, 6, 63, None, None),
    (2_000_000, 2000, 6, 63, None, None),
    # the narrow tables (8 features: expo11m_leaf255's width; the
    # categorical program compiles 71 MB over the numeric one at 11M rows,
    # 8_170_013_696); 20M x 8 is refused at cap 12, though it compiles
    (1_000_000, 8, 12, 255, 2_101_902_336, 17_038_848),
    (2_000_000, 8, 12, 255, 2_065_506_816, 34_040_320),
    (5_000_000, 8, 12, 255, 4_077_315_584, 85_037_568),
    (11_000_000, 8, 12, 255, 8_098_866_176, 187_040_768),
    (11_000_000, 8, 12, 255, 8_170_013_696, 187_041_280),
    (20_000_000, 8, 12, 255, 14_101_842_432, 340_039_680),
    (11_000_000, 4, 12, 255, 8_022_949_376, 143_040_512),
    (11_000_000, 16, 12, 255, 8_199_690_752, 275_041_280),
    (11_000_000, 28, 12, 255, 8_400_049_664, 451_042_304),
]


# The same at 64 bins (the source's max_bin 63 and the missing bin), with the
# split scan's two planes of a table with missing values (envelope_aot.py's
# ``rows,features,max_depth,num_leaves,1,0,64,1``): bosch1m_leaf255's shape
# at the cap the envelope gives it.
COMPILED_64_MISSING = [
    (1_000_000, 968, 10, 255, 8_163_383_808, 977_100_800),
]


@pytest.mark.parametrize("rows,features,depth,leaves,temp,args", COMPILED_64_MISSING)
def test_the_envelope_envelops_the_64_bin_iteration_with_missing_values(rows, features, depth,
                                                                         leaves, temp, args):
    p = make_params(dict(growth="leafwise", num_leaves=leaves, max_depth=depth, max_bins=64))
    assert leafwise_fast_supported(p, features, 64, rows)
    assert reckoned(rows, features, depth, bins=64) >= temp
    assert temp + args <= HBM_BYTES


def test_the_bosch_shape_steps_two_levels_down_to_cap_10():
    """1,000,000 x 968 at 64 bins and 255 leaves (bosch1m_leaf255): the
    pinned expansion alone is 16.75 GB at cap 12, cap 11 reckons 8.37 + 7.30
    GB of rows, cap 10 4.19 + 7.30 = 11.49, admitted.  At 256 bins the shape
    is refused at all three and keeps the sequential grower."""
    p = make_params(dict(objective="binary", max_bins=64, **LEAF255))
    assert effective_depth_params(p, 968, 64, 1_000_000).max_depth == 10
    for depth, admitted in ((12, False), (11, False), (10, True)):
        assert leafwise_fast_supported(p.replace(max_depth=depth), 968, 64,
                                       1_000_000) is admitted
        assert (reckoned(1_000_000, 968, depth, bins=64)
                <= LEAFWISE_TOTAL_BYTES_BUDGET) is admitted
    assert round(reckoned(1_000_000, 968, 10, bins=64) / 1e9, 2) == 11.49
    assert effective_depth_params(p, 968, 256, 1_000_000).max_depth == -1


@pytest.mark.parametrize("rows,features,depth,leaves,temp,args", COMPILED)
def test_the_envelope_never_admits_less_than_the_compiler_reckons(rows, features, depth, leaves,
                                                                  temp, args):
    """Wherever the rule admits a compiled shape it reckons at least the
    compiler's temporaries, and the program fits the chip with its arguments;
    a shape the compiler refused is refused."""
    p = make_params(dict(growth="leafwise", num_leaves=leaves, max_depth=depth))
    admitted = leafwise_fast_supported(p, features, 256, rows)
    if temp is None:
        assert not admitted
    elif admitted:
        assert reckoned(rows, features, depth) >= temp
        assert temp + args <= HBM_BYTES


def test_the_benchmarks_shapes_keep_their_verdicts():
    """Both Higgs cells and Epsilon route as before PR 32: depth-wise jobs are
    not the policy's, and the leaf-wise Higgs job gets cap 12 with or without
    the row count."""
    higgs = make_params(dict(objective="binary", growth="depthwise", max_depth=8, num_leaves=255))
    assert effective_depth_params(higgs, 28, 256, 10_000_000) is higgs
    epsilon = make_params(dict(objective="regression", growth="depthwise", max_depth=6,
                               num_leaves=63))
    assert effective_depth_params(epsilon, 2000, 256, 400_000) is epsilon
    leaf = make_params(dict(objective="binary", **LEAF255))
    assert effective_depth_params(leaf, 28, 256).max_depth == 12
    assert effective_depth_params(leaf, 28, 256, 10_000_000).max_depth == 12
    # the envelope's older assertions: refused at depth 12, admitted at 6, refused at 5M rows
    d12 = make_params(dict(num_leaves=4095, max_depth=12))
    d6 = make_params(dict(num_leaves=63, max_depth=6))
    assert not leafwise_fast_supported(d12, 2000, 256, 400_000)
    assert leafwise_fast_supported(d6, 2000, 256, 400_000)
    assert not leafwise_fast_supported(d6, 2000, 256, 5_000_000)
    assert not leafwise_fast_supported(d12.replace(hist_subtraction=False), 28, 256)


def test_pair_cells_of_a_small_plan_by_hand():
    from dryad_tpu.engine.lambdarank import PaddingPlan, pair_cells

    sizes = np.array([1, 2, 31, 40])
    # padded to S = 40: 4 x 1600; own 1 + 4 + 961 + 1600; kept at T = 30:
    # 1 + 4 + 30 x 31 + 30 x 40
    assert pair_cells(sizes, 40, 30) == {"padded": 6400, "own": 2566, "kept": 2135}
    assert pair_cells(sizes, 40, None)["kept"] == 2566
    plan = PaddingPlan(np.concatenate([[0], np.cumsum(sizes)]), truncation=30)
    assert (plan.Q, plan.S) == (4, 40) and plan.pair_cells["kept"] == 2135


def test_the_host_entry_builds_its_own_plan_with_the_objectives_truncation():
    """``grad_hess_ranking`` without a plan (the path outside the trainer's
    chunk program) sizes ``kept`` by the objective's truncation too."""
    from dryad_tpu.engine.lambdarank import grad_hess_ranking
    from dryad_tpu.objectives import LambdaRank
    from dryad_tpu.obs.registry import Registry, default_registry, set_default_registry

    sizes = np.array([3, 12, 9])
    rng = np.random.default_rng(3)
    old = default_registry()
    set_default_registry(Registry(enabled=True))
    try:
        grad_hess_ranking(LambdaRank(truncation=4), rng.standard_normal(24).astype(np.float32),
                          rng.integers(0, 5, 24).astype(np.float32), None,
                          np.concatenate([[0], np.cumsum(sizes)]))
        cells = default_registry().snapshot()["gauges"]["dryad_rank_pair_cells"]
    finally:
        set_default_registry(old)
    kept = [v for k, v in cells.items() if 'kind="kept"' in str(k)]
    assert kept == [float((np.minimum(4, sizes) * sizes).sum())]


def test_a_ranking_job_leaves_the_plans_gauges_in_the_registry():
    import dryad_tpu as dryad
    from dryad_tpu import datasets
    from dryad_tpu.obs.registry import Registry, default_registry, set_default_registry

    X, y, group = datasets.mslr_like(num_queries=30, docs_per_query=(3, 21), num_features=6,
                                     seed=5)
    old = default_registry()
    set_default_registry(Registry(enabled=True))
    try:
        dryad.train(dict(objective="lambdarank", num_trees=1, num_leaves=4,
                         lambdarank_truncation=10), dryad.Dataset(X, y, group=group),
                    backend="tpu")
        gauges = default_registry().snapshot()["gauges"]
    finally:
        set_default_registry(old)
    S = -(-int(group.max()) // 8) * 8
    assert list(gauges["dryad_rank_queries"].values()) == [30.0]
    assert list(gauges["dryad_rank_plan_width"].values()) == [float(S)]
    cells = {str(k).split('kind="')[1].split('"')[0]: v
             for k, v in gauges["dryad_rank_pair_cells"].items()}
    assert cells == {"padded": 30.0 * S * S, "own": float((group * group).sum()),
                     "kept": float((np.minimum(10, group) * group).sum())}


# ---- the envelope counts what ONE DEVICE holds (PR 34) -----------------------

CRITEO = dict(objective="binary", min_data_in_leaf=20, **LEAF255)


@pytest.mark.parametrize("rows,features,shards,cap", [
    (12_000_000, 67, 1, -1),    # criteo12m_leaf255 on one chip: 4.64 GB pinned + 12.0 GB of rows
    (12_000_000, 67, 2, 12),    # 4.64 + 5.98
    (12_000_000, 67, 4, 12),    # the cell: 4.64 + 2.99 = 7.63 GB reckoned, 5.18 compiled
    (32_000_000, 67, 4, 12),    # 8M rows a chip: the most the rule gives four chips at cap 12
    (34_000_000, 67, 4, 11),    # one level down
    (10_000_000, 136, 1, -1),   # PR 32's refused shape ...
    (10_000_000, 136, 4, 11),   # ... a mesh holds one level down (9.4 GB pinned at cap 12)
    (2_270_296, 136, 4, 12),    # mslr2m_leaf255's shape: as on one chip
    (12_000_000, 67, 5, 12),    # rows that do not divide: a shard's share is rounded up
])
def test_the_envelope_reckons_a_devices_share_of_the_rows(rows, features, shards, cap):
    p = make_params(CRITEO)
    got = effective_depth_params(p, features, 256, rows, shards)
    assert got.max_depth == cap and (got is p) == (cap == -1)
    share = -(-rows // shards)
    for depth in (12, 11, 10):
        fits = reckoned(share, features, depth) <= LEAFWISE_TOTAL_BYTES_BUDGET
        assert fits == (cap >= depth)
        assert fits == leafwise_fast_supported(p.replace(max_depth=depth), features, 256, rows,
                                               shards)
        # one device holding a shard's rows is given what the mesh's device is
        assert fits == leafwise_fast_supported(p.replace(max_depth=depth), features, 256, share)


def test_one_chip_refuses_the_data_parallel_cell_at_every_cap_and_four_admit_it():
    p = make_params(CRITEO)
    for depth in (12, 11, 10):
        assert not leafwise_fast_supported(p.replace(max_depth=depth), 67, 256, 12_000_000)
        assert not leafwise_fast_supported(p.replace(max_depth=depth), 67, 256, 12_000_000, 1)
    assert leafwise_fast_supported(p.replace(max_depth=12), 67, 256, 12_000_000, 4)
    assert effective_depth_params(p, 67, 256, 12_000_000) is p            # the sequential grower
    assert effective_depth_params(p, 67, 256, 12_000_000, 4).max_depth == 12
    # more shards never refuse what fewer admit
    verdicts = [leafwise_fast_supported(p.replace(max_depth=12), 67, 256, 40_000_000, n)
                for n in (1, 2, 4, 8, 16)]
    assert verdicts == sorted(verdicts)
    # shape-only callers and every shape the parity tests run: the shard count changes nothing
    assert effective_depth_params(p, 67, 256, None, 4).max_depth == 12
    for n in (1, 4, 8):
        assert effective_depth_params(p, 28, 32, 4096, n).max_depth == 12


# The sharded iteration (``scripts/envelope_aot.py rows,features,depth,leaves,shards``),
# compiled ahead of time for four chips of a v5e:2x2 (PR 34): global rows, features,
# max_depth, num_leaves, shards, one device's temp_size_in_bytes and argument_size_in_bytes.
COMPILED_SHARDED = [
    (12_000_000, 67, 12, 255, 4, 5_182_831_616, 243_042_304),
    (2_000_000, 67, 12, 255, 4, 4_457_256_960, 40_546_816),
    (500_000, 67, 12, 255, 4, 4_396_321_280, 10_169_856),
]


@pytest.mark.parametrize("rows,features,depth,leaves,shards,temp,args", COMPILED_SHARDED)
def test_the_envelope_envelops_the_sharded_iteration_too(rows, features, depth, leaves, shards,
                                                         temp, args):
    p = make_params(dict(growth="leafwise", num_leaves=leaves, max_depth=depth))
    assert leafwise_fast_supported(p, features, 256, rows, shards)
    assert reckoned(-(-rows // shards), features, depth) >= temp
    assert temp + args <= HBM_BYTES
