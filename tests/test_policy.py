"""r23 self-tuning dispatch: the policy calibration subsystem.

The hard invariant under test: a policy flip NEVER changes traced-program
semantics — only which pre-audited arm dispatches — and under the
COMMITTED default table every gate resolves bitwise-identically to the
pre-r23 hand-tuned constants.  The oracle arms below are spelled as
literals (not derived from GATE_DEFAULTS), so a drifted default fails
here even though the code would still be self-consistent.
"""

from __future__ import annotations

import json
import warnings

import numpy as np
import pytest

import dryad_tpu as dryad
from dryad_tpu.datasets import higgs_like
from dryad_tpu.policy import calibrate, device, gates
from dryad_tpu.policy import table as ptable

ROOT = __file__.rsplit("/tests/", 1)[0]


@pytest.fixture(autouse=True)
def _fresh_policy(monkeypatch):
    """Each test sees a fresh memoized table/device/decision state and
    cannot leak its own (reset is the documented test-isolation hook)."""
    monkeypatch.delenv(ptable.TABLE_ENV, raising=False)
    ptable.reset_cache()
    gates.reset_decisions()
    yield
    ptable.reset_cache()
    gates.reset_decisions()
    device.reset()


# ---------------------------------------------------------------------------
# the committed golden and the default-parity contract

def test_committed_golden_equals_code_defaults():
    tab = ptable.load_table(ptable.GOLDEN_PATH, explicit=False)
    assert tab.fallback_reason is None
    assert tab.devices[ptable.DEFAULT_DEVICE_KEY]["gates"] \
        == ptable.GATE_DEFAULTS
    # and the committed default caps still mirror their structural twins
    from dryad_tpu.engine import leafperm

    assert ptable.GATE_DEFAULTS["deep_layout"]["max_record_bytes"] \
        == leafperm._REC_WB


def test_selftest_green():
    # the ci.sh gate: default parity + exact perturbation flips +
    # round-trip + derive rules, all seeded CPU, no probes
    assert calibrate.run_selftest(quiet=True) == 0


def test_parity_cases_are_the_pre_policy_constants():
    """Every oracle case resolves to its hand-written arm under the
    committed table with NO device key (the parity anchor)."""
    golden = ptable.load_table(ptable.GOLDEN_PATH, explicit=False)
    for gate, cases in calibrate.PARITY_CASES.items():
        for feats, want in cases:
            got = gates.resolve(gate, feats, device_kind=None, table=golden)
            assert got == want, (gate, feats)


def test_call_sites_straddle_every_threshold():
    """The routed call sites (not just resolve()) honor the committed
    thresholds exactly at the boundary."""
    from dryad_tpu.config import Params, hist_reduce_resolved
    from dryad_tpu.engine.histogram import resolve_backend
    from dryad_tpu.engine.leafwise_fast import leafwise_layout_supported
    from dryad_tpu.engine.levelwise import partition_prefers_reduce
    from dryad_tpu.engine.predict import SHARDED_MIN_WORK
    from dryad_tpu.resilience.policy import RetryPolicy

    assert partition_prefers_reduce(4096, 1)
    assert not partition_prefers_reduce(4097, 1)
    assert partition_prefers_reduce(2048, 2)
    assert not partition_prefers_reduce(2049, 2)

    p = Params(num_trees=1)
    assert hist_reduce_resolved(p, 1024, 256, 2) == "feature"
    assert hist_reduce_resolved(p, 1023, 256, 2) == "fused"
    assert hist_reduce_resolved(p, 1024, 256, 1) == "fused"
    # explicit params skip the gate entirely
    pf = Params(num_trees=1, hist_reduce="fused")
    assert hist_reduce_resolved(pf, 4000, 256, 8) == "fused"

    assert resolve_backend("auto", platform="tpu") == "pallas"
    assert resolve_backend("auto", platform="cpu") == "xla"
    assert resolve_backend("xla", platform="tpu") == "xla"

    p12 = Params(num_trees=1, max_depth=12, hist_backend="pallas")
    p13 = Params(num_trees=1, max_depth=13, hist_backend="pallas")
    assert leafwise_layout_supported(p12, 28, 256, 1, platform="tpu")
    assert not leafwise_layout_supported(p13, 28, 256, 1, platform="tpu")

    assert SHARDED_MIN_WORK == 32768
    assert RetryPolicy().ch_max_ladder == (8, 4, 2)


def test_unknown_gate_raises():
    with pytest.raises(KeyError, match="unknown policy gate"):
        gates.resolve("no_such_gate", {})
    with pytest.raises(KeyError, match="no value"):
        gates.gate_value("partition", "no_such_key")


def test_gate_value_lists_come_back_as_tuples():
    assert gates.gate_value("chunk_cap", "ladder") == (8, 4, 2)


# ---------------------------------------------------------------------------
# device-keyed overlay: a device entry flips exactly its gate

def test_device_entry_flips_only_its_gate():
    golden = ptable.load_table(ptable.GOLDEN_PATH, explicit=False)
    tab = ptable.CalibrationTable(
        devices={**golden.devices,
                 "weird-accel": {"gates": {"leafwise_layout":
                                           {"max_segments": 512}}}},
        source="<test>")
    # depth 10 (1024 segments) flips to legacy on the calibrated device...
    assert gates.resolve("leafwise_layout", {"max_depth": 10},
                         device_kind="weird-accel", table=tab) == "legacy"
    assert gates.resolve("leafwise_layout", {"max_depth": 9},
                         device_kind="weird-accel", table=tab) == "layout"
    # ...while every other gate and every other device is untouched
    assert gates.resolve("leafwise_layout", {"max_depth": 10},
                         device_kind="other", table=tab) == "layout"
    assert gates.resolve("partition", {"num_features": 4096, "itemsize": 1},
                         device_kind="weird-accel", table=tab) == "reduce"


def test_default_table_resolution_never_probes_the_device(monkeypatch):
    """The committed table ships only ``_default`` — resolving against it
    must not wake a jax runtime (fleet control plane + audit-env
    ordering).  A table WITH device entries pays the probe."""
    calls = []

    def probe():
        calls.append(1)
        return "probed-kind"

    monkeypatch.setattr(gates, "current_device_kind", probe)
    golden = ptable.load_table(ptable.GOLDEN_PATH, explicit=False)
    assert gates.resolve("partition", {"num_features": 1, "itemsize": 1},
                         table=golden) == "reduce"
    assert calls == []
    keyed = ptable.CalibrationTable(
        devices={**golden.devices, "probed-kind": {"gates": {}}},
        source="<test>")
    gates.resolve("partition", {"num_features": 1, "itemsize": 1},
                  table=keyed)
    assert calls == [1]


# ---------------------------------------------------------------------------
# bitwise train/predict parity: explicit default table vs no table

def test_train_predict_bitwise_with_explicit_default_table(monkeypatch):
    X, y = higgs_like(1200)
    ds = dryad.Dataset(X, y, max_bins=32)
    params = dict(objective="binary", num_trees=3, num_leaves=15,
                  max_bins=32, learning_rate=0.2)

    ptable.reset_cache()
    base = dryad.train(params, ds, backend="tpu")
    base_pred = base.predict(X)

    monkeypatch.setenv(ptable.TABLE_ENV, ptable.GOLDEN_PATH)
    ptable.reset_cache()
    assert ptable.current_table().explicit
    tabbed = dryad.train(params, ds, backend="tpu")
    for k, v in base.tree_arrays().items():
        np.testing.assert_array_equal(v, tabbed.tree_arrays()[k],
                                      err_msg=f"tree array {k!r} diverged")
    np.testing.assert_array_equal(base_pred, tabbed.predict(X))


# ---------------------------------------------------------------------------
# loud-once fallback semantics

def test_corrupt_table_warns_once_and_resolves_on_defaults(
        tmp_path, monkeypatch):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    monkeypatch.setenv(ptable.TABLE_ENV, str(bad))
    ptable.reset_cache()
    with pytest.warns(RuntimeWarning, match="corrupt JSON"):
        tab = ptable.current_table()
    assert tab.fallback_reason and tab.explicit
    # resolution proceeds on the committed defaults
    assert gates.resolve("partition", {"num_features": 4096, "itemsize": 1},
                         device_kind=None) == "reduce"
    # loud ONCE: a second current_table() stays quiet
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ptable.current_table()


def test_missing_and_wrong_schema_tables_fall_back(tmp_path):
    missing = ptable.load_table(str(tmp_path / "nope.json"))
    assert "unreadable" in missing.fallback_reason
    wrong = tmp_path / "wrong.json"
    wrong.write_text(json.dumps({"calibration_schema": 99, "devices": {}}))
    assert "schema" in ptable.load_table(str(wrong)).fallback_reason
    nomap = tmp_path / "nomap.json"
    nomap.write_text(json.dumps({"calibration_schema": 1, "devices": 3}))
    assert "malformed" in ptable.load_table(str(nomap)).fallback_reason
    # broken tables still resolve every gate on the code defaults
    for tab in (missing,):
        assert tab.gate_values("partition", None) \
            == ptable.GATE_DEFAULTS["partition"]


def test_explicit_table_unknown_device_warns_once_per_kind(tmp_path):
    p = tmp_path / "t.json"
    ptable.save_table({"_default": {"gates": {}}}, str(p))
    tab = ptable.load_table(str(p))       # path given -> explicit
    with pytest.warns(RuntimeWarning, match="no entry for device_kind"):
        tab.gate_values("partition", "TPU v99")
    with warnings.catch_warnings():       # once per kind
        warnings.simplefilter("error")
        tab.gate_values("hist_reduce", "TPU v99")
    with pytest.warns(RuntimeWarning):    # a new kind warns again
        tab.gate_values("partition", "TPU v100")


def test_committed_table_unknown_device_is_silent():
    golden = ptable.load_table(ptable.GOLDEN_PATH, explicit=False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        vals = golden.gate_values("partition", "some-future-tpu")
    assert vals == ptable.GATE_DEFAULTS["partition"]


# ---------------------------------------------------------------------------
# calibration: round-trip, derive rules, check diff

def test_save_load_round_trip(tmp_path):
    devices = {"_default": {"gates": dict(ptable.GATE_DEFAULTS)},
               "TPU v5e": {"gates": {"partition":
                                     {"reduce_max_row_bytes": 8192}},
                           "git_rev": "abc1234"}}
    p = tmp_path / "cal.json"
    ptable.save_table(devices, str(p))
    loaded = ptable.load_table(str(p))
    assert loaded.fallback_reason is None
    assert loaded.devices == devices
    assert gates.resolve("partition", {"num_features": 8192, "itemsize": 1},
                         device_kind="TPU v5e", table=loaded) == "reduce"


def test_derive_overrides_rules_and_spread_veto():
    walls = {
        "partition": {512: {"reduce": {"ms": 1.0, "spread": 0.0},
                            "gather": {"ms": 9.0, "spread": 0.0}},
                      8192: {"reduce": {"ms": 9.0, "spread": 0.0},
                             "gather": {"ms": 1.0, "spread": 0.0}}},
        "predict_layout": {28: {"packed": {"ms": 2.0, "spread": 0.0},
                                "legacy": {"ms": 1.0, "spread": 0.0}}},
        "hist_backend": {28: {"masked": {"ms": 1.0, "spread": 0.0},
                              "segmented": {"ms": 2.0, "spread": 0.0}}},
    }
    ov, notes = calibrate.derive_overrides(walls)
    assert ov["partition"] == {"reduce_max_row_bytes": 512}
    assert ov["predict_layout"] == {"preferred": "legacy"}
    assert notes["hist_backend"] == "informational"
    walls["predict_layout"][28]["packed"]["spread"] = 0.2
    ov2, notes2 = calibrate.derive_overrides(walls)
    assert "predict_layout" not in ov2
    assert "suspect" in notes2["predict_layout"]


def test_check_calib_flags_resolution_drift(monkeypatch):
    """A sweep whose derived thresholds flip a committed resolution (with
    clean spreads) must fail the check; the same walls marked suspect
    must not."""
    walls = {
        "partition": {512: {"reduce": {"ms": 9.0, "spread": 0.0},
                            "gather": {"ms": 1.0, "spread": 0.0}},
                      4096: {"reduce": {"ms": 9.0, "spread": 0.0},
                             "gather": {"ms": 1.0, "spread": 0.0}},
                      8192: {"reduce": {"ms": 9.0, "spread": 0.0},
                             "gather": {"ms": 1.0, "spread": 0.0}}},
    }
    monkeypatch.setattr(calibrate, "run_sweep", lambda **kw: walls)
    report = calibrate.check_calib(device_kind="fake-kind")
    assert not report["ok"]
    assert report["gates"]["partition"]["verdict"] == "drift"
    assert report["gates"]["partition"]["diffs"]
    for width in walls["partition"]:
        walls["partition"][width]["gather"]["spread"] = 0.5
    report2 = calibrate.check_calib(device_kind="fake-kind")
    assert report2["ok"]
    assert report2["gates"]["partition"]["verdict"] in ("ok", "suspect")


# ---------------------------------------------------------------------------
# decisions / stats / the predict_layout fallback reason

def test_decisions_and_stats_block_record_the_fallback_reason():
    from dryad_tpu.engine.predict import packed_fallback_reason

    reason = packed_fallback_reason(
        np.array([0]), np.array([70000]), np.array([1]), np.array([2]))
    assert "threshold" in reason and "16-bit" in reason
    arm = gates.resolve("predict_layout", {"fits": reason is None},
                        device_kind=None, detail=reason)
    assert arm == "legacy"
    d = gates.decisions()["predict_layout"]
    assert d["arm"] == "legacy" and "threshold" in d["detail"]
    block = gates.stats_block()
    assert block["decisions"]["predict_layout"]["detail"] == reason
    assert block["fallback_reason"] is None
    assert "_default" in block["device_keys"]


def test_stage_trees_auto_records_policy_decision():
    X, y = higgs_like(400)
    ds = dryad.Dataset(X, y, max_bins=32)
    b = dryad.train(dict(objective="binary", num_trees=2, num_leaves=7,
                         max_bins=32), ds, backend="cpu")
    from dryad_tpu.engine.predict import stage_trees

    gates.reset_decisions()
    trees, _, _ = stage_trees(b)
    assert "node_word" in trees            # numeric model packs
    d = gates.decisions()["predict_layout"]
    assert d["arm"] == "packed" and d["detail"] is None


# ---------------------------------------------------------------------------
# the r23 lint rules (mutation checks, like test_analysis_lint.py)

def _lint(rule, overrides=None):
    from dryad_tpu.analysis.lint import run_lint

    rep = run_lint(ROOT, rule_names=[rule], overrides=overrides)
    return [v for v in rep.violations if v.rule == rule]


def test_gate_through_policy_clean_and_catches_folded_literal():
    assert _lint("gate-through-policy") == []
    src = open(f"{ROOT}/dryad_tpu/engine/levelwise.py").read()
    bad = src.replace(
        'return resolve("partition", {"num_features": num_features,\n'
        '                                 "itemsize": itemsize}) == "reduce"',
        "return num_features * itemsize <= (1 << 15)")
    assert bad != src
    hits = _lint("gate-through-policy",
                 {"dryad_tpu/engine/levelwise.py": bad})
    assert any("32768" in v.message and "partition_prefers_reduce"
               in v.message for v in hits)


def test_gate_through_policy_ignores_small_shape_arithmetic():
    src = open(f"{ROOT}/dryad_tpu/engine/levelwise.py").read()
    ok = src.replace(
        'return resolve("partition", {"num_features": num_features,\n'
        '                                 "itemsize": itemsize}) == "reduce"',
        "return num_features * itemsize <= 9 + 2 * 8")
    assert ok != src
    assert _lint("gate-through-policy",
                 {"dryad_tpu/engine/levelwise.py": ok}) == []


def test_policy_jax_free_clean_and_catches_direct_import():
    assert _lint("policy-jax-free") == []
    src = open(f"{ROOT}/dryad_tpu/policy/gates.py").read()
    bad = src + "\n\ndef _peek():\n    import jax\n    return jax\n"
    hits = _lint("policy-jax-free", {"dryad_tpu/policy/gates.py": bad})
    assert any("import jax" in v.message for v in hits)


def test_policy_jax_free_catches_transitive_chain():
    src = open(f"{ROOT}/dryad_tpu/policy/table.py").read()
    bad = "from dryad_tpu.engine.histogram import resolve_backend\n" + src
    hits = _lint("policy-jax-free", {"dryad_tpu/policy/table.py": bad})
    assert any("transitive jax import" in v.message for v in hits)
