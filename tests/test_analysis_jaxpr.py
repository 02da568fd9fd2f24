"""jaxpr auditor (dryad_tpu/analysis layer 2): the collective/sort census
over the real grower arms, the _comm_stats cross-check, kernel dtype
discipline, and the digest tripwire — including the mutation direction
(a program with an EXTRA collective or sort must be caught).

Everything here traces with abstract inputs on the 8 fake CPU devices;
nothing compiles or runs, so the module stays cheap relative to the
training fixtures around it.
"""

from __future__ import annotations

from collections import Counter

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dryad_tpu.analysis.digests import canonical_digest
from dryad_tpu.analysis.jaxpr_audit import (
    ARMS,
    Census,
    census_jaxpr,
    kernel_dtype_violations,
    run_audit,
    trace_arm,
)
from dryad_tpu.engine.distributed import AXIS, make_mesh

pytestmark = pytest.mark.distributed


@pytest.fixture(scope="module")
def audit_report():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return run_audit()


def _arm(report, name):
    return next(a for a in report.arms if a.name == name)


# ---------------------------------------------------------------------------
# the documented invariants, arm by arm

def test_all_arms_pass_invariants(audit_report):
    for arm in audit_report.arms:
        assert arm.ok, f"{arm.name}: {arm.failures}"


def test_psum_census_matches_comm_stats_every_arm(audit_report):
    """The accounting (_comm_stats) and the traced program must agree —
    this is the cross-check that retires hand-maintained drift."""
    for arm in audit_report.arms:
        assert arm.census.collectives.get("psum", 0) == arm.expected_psums, \
            arm.name


def test_wired_paths_sort_free(audit_report):
    """'Nothing on the wired path sorts rows' (r10) — now machine-checked
    (the r16 feature-reduction arms ride the wired layout too)."""
    for name in ("levelwise_wired", "leafwise_wired",
                 "levelwise_feature", "leafwise_feature"):
        c = _arm(audit_report, name).census
        assert c.global_row_sorts == 0 and c.local_row_sorts == 0, name


# what a per-row pass over the layout looks like in a jaxpr: the lookups,
# prefix sums and dtype passes the level move kept in XLA before PR 26
_ROW_PASS_PRIMS = ("gather", "cumsum", "cumlogsumexp", "cummax", "cummin",
                   "cumprod", "reduce_window", "reduce_window_sum",
                   "convert_element_type")


def _layout_row_passes(closed):
    """(layout rows, offenders): the row count of the record buffer the
    ``permute_records`` kernel is handed, and every equation inside
    ``dryad.layout`` that is a gather, a prefix sum or a convert with an
    operand or result of that leading size (flat, or tiled as
    ``(n_tiles, T, ...)``) — a row-sized XLA pass over the layout.  ``pallas_call`` equations are not entered: the
    record buffer passed to the kernels is the one row-sized array the
    scope may hold."""
    eqns = []

    def walk(jaxpr, stack):
        for eqn in jaxpr.eqns:
            here = stack + "/" + str(eqn.source_info.name_stack)
            eqns.append((here, eqn))
            if eqn.primitive.name == "pallas_call":
                continue
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner, here)

    walk(closed.jaxpr, "")
    rows = {e.invars[-2].aval.shape[0] * e.invars[-2].aval.shape[1]
            for _, e in eqns if e.primitive.name == "pallas_call"
            and e.params["name"] == "permute_records"}
    assert len(rows) == 1, rows           # one layout buffer size per arm
    n_rows = rows.pop()

    def row_sized(v):
        # (n_rows, ...) or its tiled view (n_tiles, T, ...)
        shape = getattr(v.aval, "shape", ())
        return bool(shape) and (shape[0] == n_rows or (
            len(shape) > 1 and shape[0] * shape[1] == n_rows))

    bad = [(e.primitive.name, [v.aval.shape for v in e.invars + e.outvars
                               if hasattr(v.aval, "shape")])
           for here, e in eqns
           if "dryad.layout" in here and e.primitive.name in _ROW_PASS_PRIMS
           and any(row_sized(v) for v in e.invars + e.outvars)]
    return n_rows, bad


@pytest.mark.parametrize("name", ["levelwise_wired", "leafwise_wired"])
def test_wired_layout_scope_holds_no_row_sized_pass(name):
    """The static form of "the level move's mechanism engaged" (PR 26):
    sides and in-tile ranks are derived inside the layout's kernels from
    per-TILE split records, so no gather, prefix sum or convert inside
    ``dryad.layout`` touches an array as large as the layout's rows.  A
    later edit that brings a per-row pass back fails here, on the CPU."""
    fn, args, _, _ = ARMS[name].build()
    n_rows, bad = _layout_row_passes(jax.make_jaxpr(fn)(*args))
    assert n_rows % 512 == 0 and not bad, (n_rows, bad)


def test_row_sized_layout_pass_is_caught():
    """Mutation direction: the per-row record gather the old level move
    paid (``rec_t[...][jnp.repeat(tile_run, T)]``) beside the kernels
    must be flagged."""
    from dryad_tpu.engine import leafperm

    T = leafperm._TILE_ROWS

    def old_style(rec, tile_run, run_rec):
        with jax.named_scope("dryad.layout"):
            rr = run_rec[jnp.repeat(tile_run, T)]     # the per-row gather
            out, _, _ = leafperm.move_level(rec, tile_run, run_rec,
                                            bin_dtype=jnp.uint8,
                                            platform="cpu")
        return out, rr

    sds = jax.ShapeDtypeStruct
    closed = jax.make_jaxpr(old_style)(
        sds((4 * T, leafperm._REC_WB), jnp.uint8), sds((4,), jnp.int32),
        sds((8, 2), jnp.uint32))
    n_rows, bad = _layout_row_passes(closed)
    assert n_rows == 4 * T
    assert [prim for prim, _ in bad] == ["gather"], bad


def test_legacy_arm_keeps_its_tile_plan_sorts(audit_report):
    """The comparison arm must keep sorting — if the legacy path silently
    stopped sorting it is no longer the program the bench compares."""
    c = _arm(audit_report, "levelwise_legacy").census
    assert c.local_row_sorts > 0
    assert c.global_row_sorts == 0


def test_goss_adds_exactly_one_global_sort(audit_report):
    assert _arm(audit_report, "goss_iteration").census.global_row_sorts == 1


def test_renewal_adds_exactly_one_global_sort(audit_report):
    assert _arm(audit_report,
                "renewal_iteration").census.global_row_sorts == 1


def test_sharded_predict_collective_free(audit_report):
    c = _arm(audit_report, "sharded_predict").census
    assert not c.collectives
    assert c.global_row_sorts == 0 and c.local_row_sorts == 0


def test_train_eval_walks_one_table_gather_a_level(audit_report):
    """The training eval of a fresh tree (PR 33): six levels of ONE
    node-word gather and the value lookup; the bin comes from the masked
    reduce, so nothing else gathers."""
    c = _arm(audit_report, "train_eval").census
    assert (c.table_gathers, c.row_gathers) == (6 * 1 + 1, 0)
    assert not c.collectives and not c.pallas_kernels


def test_soa_eval_walk_is_caught():
    """Mutation direction: the structure-of-arrays walk the trainer ran
    before (every tree key handed to ``tree_leaves``, the bitset's among
    them) reads eight gathers a level in the same census."""
    from dryad_tpu.engine.predict import tree_leaves
    from dryad_tpu.engine.train import _TREE_KEYS

    _, (out, t, vXb, _), meta, _ = ARMS["train_eval"].build()

    def soa(out, t, vXb):
        return tree_leaves({key: out[key][t] for key in _TREE_KEYS}, vXb, 6)

    c = census_jaxpr(jax.make_jaxpr(soa)(out, t, vXb),
                     meta["rows_threshold"])
    assert c.table_gathers + c.row_gathers == 6 * 8


@pytest.mark.parametrize("name,trees", [
    ("levelwise_wired", 1), ("levelwise_legacy", 1), ("leafwise_wired", 1),
    ("goss_iteration", 1), ("multiclass_shared_roots", 3),
    ("levelwise_feature", 1), ("leafwise_feature", 1)])
def test_score_update_gathers_once_a_tree_from_records(audit_report, name,
                                                       trees):
    """The static form of "the score update's mechanism engaged" (PR 35):
    scope ``dryad.score`` holds ONE row-sized gather a tree, from the
    composed ``(keys, 2)`` record table, and none from a 1-D table."""
    c = _arm(audit_report, name).census
    assert (c.score_row_gathers, c.score_flat_gathers) == (trees, 0)


def test_renewal_pays_a_second_record_gather(audit_report):
    """Leaf renewal needs each row's leaf before the leaf has its value:
    the leaf from one record gather, the renewed value from a second, both
    of the two-word form."""
    c = _arm(audit_report, "renewal_iteration").census
    assert (c.score_row_gathers, c.score_flat_gathers) == (2, 0)


def test_the_pair_of_flat_score_gathers_is_caught():
    """Mutation direction: the score update as it was, each row's leaf from
    a 1-D table and its value from another, reads two row-sized gathers in
    ``dryad.score``, both flat; ``_row_records`` in its place reads one and
    none."""
    from dryad_tpu.engine.train import _row_records

    def flat(key_leaf, value, row_key):
        with jax.named_scope("dryad.score"):
            return value[key_leaf[jnp.minimum(row_key, 254)]]

    def records(key_leaf, value, row_key):
        with jax.named_scope("dryad.score"):
            return _row_records(key_leaf, value, row_key)[0]

    sds = jax.ShapeDtypeStruct
    args = (sds((255,), jnp.int32), sds((511,), jnp.float32),
            sds((2048,), jnp.int32))
    c = census_jaxpr(jax.make_jaxpr(flat)(*args), 256)
    assert (c.score_row_gathers, c.score_flat_gathers) == (2, 2)
    c = census_jaxpr(jax.make_jaxpr(records)(*args), 256)
    assert (c.score_row_gathers, c.score_flat_gathers) == (1, 0)


def test_only_documented_collectives_anywhere(audit_report):
    """fused arms: psum only.  feature arms (r16): psum (root) +
    reduce_scatter + all_gather (+ the communication-free axis_index the
    slice/offset derivation uses) — nothing else, anywhere."""
    feature_arms = {"levelwise_feature", "leafwise_feature"}
    for arm in audit_report.arms:
        allowed = {"psum"}
        if arm.name in feature_arms:
            allowed |= {"reduce_scatter", "all_gather", "axis_index"}
        extra = {k: v for k, v in arm.census.collectives.items()
                 if k not in allowed}
        assert not extra, (arm.name, extra)


def test_feature_arm_collective_plan_matches_comm_stats(audit_report):
    """The r16 collective plan, census-verified: on the feature arms the
    root keeps ONE psum, every level shows exactly one reduce_scatter and
    one combine all_gather (cross-checked against _comm_stats inside
    trace_arm; re-asserted here so the plan is visible in the test)."""
    for name, levels in (("levelwise_feature", 7), ("leafwise_feature", 5)):
        c = _arm(audit_report, name).census
        assert c.collectives.get("psum", 0) == 1, name
        assert c.collectives.get("reduce_scatter", 0) == levels, name
        assert c.collectives.get("all_gather", 0) == levels, name
    # the fused twins are untouched: same configs, psum-only plans
    for name in ("levelwise_wired", "leafwise_wired"):
        c = _arm(audit_report, name).census
        assert set(c.collectives) == {"psum"}, name


def test_wired_kernels_present_and_u8(audit_report):
    """The wired arms must actually run the layout kernels (the gates
    admitted) and every kernel's dominant integer operand stays u8/u16."""
    for name in ("levelwise_wired", "leafwise_wired"):
        c = _arm(audit_report, name).census
        # the auditor names a kernel by its pallas_call's ``name=`` (PR 25)
        assert "_hist_tiles" in c.pallas_kernels, name
        assert "permute_records" in c.pallas_kernels, name
        assert not kernel_dtype_violations(c), name


def test_digests_match_committed_goldens(audit_report):
    assert audit_report.drift_ok, audit_report.drift


# ---------------------------------------------------------------------------
# census machinery: weighting, nesting, mutation direction

def _mesh8():
    return make_mesh(jax.devices()[:8])


def test_census_weights_scan_trip_counts():
    mesh = _mesh8()

    def inner(x):
        def body(i, c):
            return c + jax.lax.psum(x.sum() * i, AXIS)

        return jax.lax.fori_loop(0, 5, body, jnp.float32(0))

    fn = jax.shard_map(inner, mesh=mesh, in_specs=(P(AXIS),), out_specs=P())
    closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((64,), jnp.float32))
    c = census_jaxpr(closed, row_threshold=8)
    assert c.collectives["psum"] == 5


def test_census_seeded_extra_psum_is_counted():
    """Mutation check: a second collective sneaking into a builder-shaped
    program must move the census (and thus fail the _comm_stats check)."""
    mesh = _mesh8()

    def one(x):
        return jax.lax.psum(x.sum(), AXIS)

    def two(x):
        return jax.lax.psum(x.sum(), AXIS) + jax.lax.psum(x.max(), AXIS)

    def trace(f):
        fn = jax.shard_map(f, mesh=mesh, in_specs=(P(AXIS),), out_specs=P())
        return census_jaxpr(jax.make_jaxpr(fn)(
            jax.ShapeDtypeStruct((64,), jnp.float32)), 8)

    assert trace(one).collectives["psum"] == 1
    assert trace(two).collectives["psum"] == 2


def test_census_splits_global_vs_shard_local_sorts():
    mesh = _mesh8()
    N = 512

    def local_sorting(x):
        return jnp.sort(x)     # sorts the SHARD

    fn = jax.shard_map(local_sorting, mesh=mesh, in_specs=(P(AXIS),),
                   out_specs=P(AXIS))

    def global_sorting(x):
        return jnp.sort(fn(x))  # sorts the GLOBAL array

    closed = jax.make_jaxpr(global_sorting)(
        jax.ShapeDtypeStruct((N,), jnp.float32))
    c = census_jaxpr(closed, row_threshold=N // 8)
    assert c.local_row_sorts == 1
    assert c.global_row_sorts == 1


def test_census_ignores_slot_scale_sorts():
    def f(gains, rows):
        return jnp.argsort(gains), rows * 2   # (31,) slot sort only

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((31,), jnp.float32),
                               jax.ShapeDtypeStruct((4096,), jnp.float32))
    c = census_jaxpr(closed, row_threshold=512)
    assert c.global_row_sorts == 0 and c.local_row_sorts == 0


def test_kernel_dtype_rule_flags_i32_tiles():
    c = Census(collectives=Counter())
    c.pallas_kernels["_hist_kernel"] = {
        "(int32(4,),int32(4, 512, 128),bfloat16(4, 8, 512))"}
    bad = kernel_dtype_violations(c)
    assert bad and "int32" in bad[0]


def test_kernel_dtype_rule_accepts_u8_tiles():
    c = Census(collectives=Counter())
    c.pallas_kernels["_hist_kernel"] = {
        "(int32(4,),uint8(4, 512, 128),bfloat16(4, 8, 512))"}
    assert not kernel_dtype_violations(c)


# ---------------------------------------------------------------------------
# digests

def test_digest_stable_across_retrace():
    def f(x):
        return jnp.cumsum(x * 2)

    a = canonical_digest(jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((128,), jnp.float32)))
    b = canonical_digest(jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((128,), jnp.float32)))
    assert a == b


def test_digest_moves_when_program_changes():
    def f(x):
        return jnp.cumsum(x * 2)

    def g(x):
        return jnp.cumsum(x * 3)   # literal change

    def h(x):
        return jnp.cumsum(x + x)   # op change

    sds = jax.ShapeDtypeStruct((128,), jnp.float32)
    d = {canonical_digest(jax.make_jaxpr(fn)(sds)) for fn in (f, g, h)}
    assert len(d) == 3


def test_goldens_corruption_is_reported(tmp_path, audit_report):
    """The CI failure path: a stale/foreign golden must surface as drift
    (exit 4 in the CLI), never silently pass."""
    import json

    from dryad_tpu.analysis.digests import load_goldens, save_goldens
    from dryad_tpu.analysis.jaxpr_audit import run_audit as run

    gpath = str(tmp_path / "goldens.json")
    data = json.loads(json.dumps(load_goldens()))   # deep copy of committed
    data["arms"]["sharded_predict"]["digest"] = "not-the-digest"
    save_goldens(data, gpath)
    rep = run(arm_names=["sharded_predict"], goldens_path=gpath)
    assert rep.ok and not rep.drift_ok
    assert "digest" in rep.drift[0]


def test_update_goldens_roundtrip(tmp_path):
    gpath = str(tmp_path / "goldens.json")
    run_audit(arm_names=["sharded_predict"], goldens_path=gpath,
              update_goldens=True)
    rep = run_audit(arm_names=["sharded_predict"], goldens_path=gpath)
    assert rep.ok and rep.drift_ok


# ---------------------------------------------------------------------------
# the traced arm IS the trained program (spot anchor)

def test_wired_arm_gates_really_admit():
    """Guard against the silent-skip failure mode: if a fixture config
    stopped passing deep_layout_supported, the 'wired' arm would quietly
    trace the legacy program and the zero-sort check would pin nothing."""
    from dryad_tpu.config import make_params
    from dryad_tpu.engine.levelwise import deep_layout_supported
    from dryad_tpu.engine.leafwise_fast import leafwise_layout_supported

    p = make_params(dict(objective="binary", num_trees=1, num_leaves=127,
                         max_depth=7, growth="depthwise", max_bins=32,
                         hist_backend="pallas")).validate()
    assert deep_layout_supported(p, 8, 32, 1, "tpu")
    pl = make_params(dict(objective="binary", num_trees=1, num_leaves=31,
                          max_depth=5, growth="leafwise", max_bins=32,
                          hist_backend="pallas")).validate()
    assert leafwise_layout_supported(pl, 8, 32, 1, "tpu")


def test_single_arm_trace_smoke():
    rep = trace_arm("sharded_predict")
    assert rep.ok and rep.digest
    assert set(ARMS) >= {"levelwise_wired", "levelwise_legacy",
                         "leafwise_wired", "levelwise_feature",
                         "leafwise_feature", "goss_iteration",
                         "renewal_iteration", "multiclass_shared_roots",
                         "sharded_predict"}


def test_update_goldens_subset_merges_not_clobbers(tmp_path):
    """--arm X --update-goldens must refresh X's pin ONLY: wiping the
    other arms' goldens would force a full unreviewed re-baseline."""
    from dryad_tpu.analysis.digests import load_goldens

    gpath = str(tmp_path / "goldens.json")
    run_audit(arm_names=["sharded_predict"], goldens_path=gpath,
              update_goldens=True)
    run_audit(arm_names=["renewal_iteration"], goldens_path=gpath,
              update_goldens=True)
    arms = load_goldens(gpath)["arms"]
    assert set(arms) == {"sharded_predict", "renewal_iteration"}
    rep = run_audit(arm_names=["sharded_predict", "renewal_iteration"],
                    goldens_path=gpath)
    assert rep.ok and rep.drift_ok


def test_env_change_reported_as_rebaseline_not_code_drift(tmp_path):
    import json

    from dryad_tpu.analysis.digests import load_goldens, save_goldens

    gpath = str(tmp_path / "goldens.json")
    run_audit(arm_names=["sharded_predict"], goldens_path=gpath,
              update_goldens=True)
    data = json.loads(json.dumps(load_goldens(gpath)))
    data["jax_version"] = "0.0.1"
    save_goldens(data, gpath)
    rep = run_audit(arm_names=["sharded_predict"], goldens_path=gpath)
    assert not rep.drift_ok
    assert "re-baseline" in rep.drift[0]


def test_update_goldens_refuses_on_invariant_failure(tmp_path, monkeypatch):
    """Review r11: --update-goldens must never pin a program that fails
    its own invariants."""
    import os

    import dryad_tpu.analysis.jaxpr_audit as ja

    real = ja.trace_arm

    def broken(name):
        rep = real(name)
        rep.failures.append("seeded failure")
        return rep

    monkeypatch.setattr(ja, "trace_arm", broken)
    gpath = str(tmp_path / "goldens.json")
    rep = ja.run_audit(arm_names=["sharded_predict"], goldens_path=gpath,
                       update_goldens=True)
    assert not rep.ok
    assert not os.path.exists(gpath)
    assert any("refusing" in d for d in rep.drift)
