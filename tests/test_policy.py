"""Dispatch gates (``dryad_tpu/policy``): what each gate decides, pinned.

The hard invariant under test: a gate NEVER changes traced-program
semantics — only which pre-audited arm dispatches.  The oracle arms
below are spelled as literals (not derived from ``gates.THRESHOLDS``),
so a drifted threshold fails here, by name, even though the code would
still be self-consistent.
"""

from __future__ import annotations

import numpy as np
import pytest

import dryad_tpu as dryad
from dryad_tpu.datasets import higgs_like
from dryad_tpu.policy import device, gates

ROOT = __file__.rsplit("/tests/", 1)[0]


@pytest.fixture(autouse=True)
def _fresh_policy():
    """Each test sees fresh decision/device state and cannot leak its
    own (reset is the documented test-isolation hook)."""
    gates.reset_decisions()
    yield
    gates.reset_decisions()
    device.reset()


# ---------------------------------------------------------------------------
# what the gates decide: (gate, features, arm) at and around each threshold

PARITY_CASES = [
    ("partition", {"num_features": 4096, "itemsize": 1}, "reduce"),
    ("partition", {"num_features": 4097, "itemsize": 1}, "gather"),
    ("partition", {"num_features": 2048, "itemsize": 2}, "reduce"),
    ("partition", {"num_features": 2049, "itemsize": 2}, "gather"),
    ("partition", {"num_features": 28, "itemsize": 1}, "reduce"),
    ("partition", {"num_features": 2000, "itemsize": 1}, "reduce"),
    ("partition", {"num_features": 2000, "itemsize": 2}, "reduce"),
    ("partition", {"num_features": 2000, "itemsize": 4}, "gather"),
    ("hist_reduce",
     {"num_features": 28, "total_bins": 256, "n_shards": 1}, "fused"),
    ("hist_reduce",
     {"num_features": 28, "total_bins": 256, "n_shards": 8}, "fused"),
    ("hist_reduce",
     {"num_features": 1023, "total_bins": 256, "n_shards": 2}, "fused"),
    ("hist_reduce",
     {"num_features": 1024, "total_bins": 256, "n_shards": 2}, "feature"),
    ("hist_reduce",
     {"num_features": 1024, "total_bins": 256, "n_shards": 1}, "fused"),
    ("hist_reduce",
     {"num_features": 2000, "total_bins": 256, "n_shards": 8}, "feature"),
    ("hist_reduce",
     {"num_features": 256, "total_bins": 512, "n_shards": 2}, "feature"),
    ("hist_reduce",
     {"num_features": 255, "total_bins": 512, "n_shards": 2}, "fused"),
    ("hist_backend", {"platform": "cpu"}, "xla"),
    ("hist_backend", {"platform": "tpu"}, "pallas"),
    ("hist_backend", {"platform": "gpu"}, "xla"),
    ("deep_layout", {"num_leaves": 512, "record_bytes": 128}, "layout"),
    ("deep_layout", {"num_leaves": 513, "record_bytes": 128}, "legacy"),
    ("deep_layout", {"num_leaves": 512, "record_bytes": 129}, "legacy"),
    ("deep_layout", {"num_leaves": 31, "record_bytes": 37}, "layout"),
    ("leafwise_layout", {"max_depth": 12}, "layout"),
    ("leafwise_layout", {"max_depth": 13}, "legacy"),
    ("leafwise_layout", {"max_depth": 1}, "layout"),
    ("leafwise_layout", {"max_depth": 0}, "legacy"),
    ("predict_layout", {"fits": True}, "packed"),
    ("predict_layout", {"fits": False}, "legacy"),
    ("predict_sharded", {"work": 32767}, "single"),
    ("predict_sharded", {"work": 32768}, "sharded"),
    ("predict_sharded", {"work": 1}, "single"),
    ("chunk_cap", {}, "8/4/2"),
]


def _case_id(case):
    gate, feats, arm = case
    return f"{gate}-{'-'.join(str(v) for v in feats.values())}-{arm}"


def _resolve_all() -> list:
    return [gates.resolve(g, feats) for g, feats, _want in PARITY_CASES]


@pytest.mark.parametrize("case", PARITY_CASES, ids=_case_id)
def test_parity_cases_are_the_pre_policy_constants(case):
    """Every oracle case resolves to its hand-written arm."""
    gate, feats, want = case
    assert gates.resolve(gate, feats) == want


#: (gate, its moved threshold, the PARITY_CASES features that must flip)
PERTURBATIONS = [
    ("partition", {"reduce_max_row_bytes": 0},
     {"num_features": 4096, "itemsize": 1}),
    ("hist_reduce", {"wide_bytes": 1},
     {"num_features": 28, "total_bins": 256, "n_shards": 8}),
    ("hist_backend", {"pallas_platforms": []}, {"platform": "tpu"}),
    ("deep_layout", {"max_leaves": 256},
     {"num_leaves": 512, "record_bytes": 128}),
    ("leafwise_layout", {"max_segments": 512}, {"max_depth": 12}),
    ("predict_layout", {"preferred": "legacy"}, {"fits": True}),
    ("predict_sharded", {"min_work": 1}, {"work": 32767}),
    ("chunk_cap", {"ladder": [2]}, {}),
]


@pytest.mark.parametrize("gate,moved,target", PERTURBATIONS,
                         ids=[p[0] for p in PERTURBATIONS])
def test_moved_threshold_flips_its_own_gate_only(monkeypatch, gate, moved,
                                                 target):
    """Moving one threshold flips that gate's boundary case and no case
    of any other gate."""
    base = _resolve_all()
    monkeypatch.setitem(gates.THRESHOLDS, gate,
                        {**gates.THRESHOLDS[gate], **moved})
    flipped = {(g, str(feats))
               for (g, feats, _w), was, now
               in zip(PARITY_CASES, base, _resolve_all()) if was != now}
    assert (gate, str(target)) in flipped
    assert {g for g, _ in flipped} == {gate}


def test_deep_layout_record_cap_is_leafperm_record_width():
    """The tuned cap mirrors its structural twin: a record the gate
    admits must fit the layout's fixed record width."""
    from dryad_tpu.engine import leafperm

    assert gates.gate_value("deep_layout", "max_record_bytes") \
        == leafperm._REC_WB


def test_call_sites_straddle_every_threshold():
    """The routed call sites (not just resolve()) honor the thresholds
    exactly at the boundary."""
    from dryad_tpu.config import Params, hist_reduce_resolved
    from dryad_tpu.engine.histogram import resolve_backend
    from dryad_tpu.engine.leafwise_fast import leafwise_layout_supported
    from dryad_tpu.engine.levelwise import partition_prefers_reduce
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

    assert gates.gate_value("predict_sharded", "min_work") == 32768
    assert RetryPolicy().ch_max_ladder == (8, 4, 2)


def test_unknown_gate_raises():
    with pytest.raises(KeyError, match="unknown policy gate"):
        gates.resolve("no_such_gate", {})
    with pytest.raises(KeyError, match="no value"):
        gates.gate_value("partition", "no_such_key")


def test_gate_value_lists_come_back_as_tuples():
    assert gates.gate_value("chunk_cap", "ladder") == (8, 4, 2)


# ---------------------------------------------------------------------------
# decisions / stats / the predict_layout fallback reason

def test_decisions_and_stats_block_record_the_fallback_reason():
    from dryad_tpu.engine.predict import packed_fallback_reason

    reason = packed_fallback_reason(
        np.array([0]), np.array([70000]), np.array([1]), np.array([2]))
    assert "threshold" in reason and "16-bit" in reason
    arm = gates.resolve("predict_layout", {"fits": reason is None},
                        detail=reason)
    assert arm == "legacy"
    d = gates.decisions()["predict_layout"]
    assert d["arm"] == "legacy" and "threshold" in d["detail"]
    block = gates.stats_block()
    assert block["decisions"]["predict_layout"]["detail"] == reason


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
# the two lint rules (mutation checks, like test_analysis_lint.py)

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
    src = open(f"{ROOT}/dryad_tpu/policy/gates.py").read()
    bad = "from dryad_tpu.engine.histogram import resolve_backend\n" + src
    hits = _lint("policy-jax-free", {"dryad_tpu/policy/gates.py": bad})
    assert any("transitive jax import" in v.message for v in hits)
