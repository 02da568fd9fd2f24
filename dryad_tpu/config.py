"""Typed training-parameter surface for dryad_tpu.

Mirrors the ``dryad.train(params, dataset)`` API contract (BASELINE.json:5;
SURVEY.md §5 "Config/flag system").  The reference checkout was absent in this
environment (SURVEY.md header), so param names follow the de-facto GBDT
vocabulary (LightGBM/XGBoost family) that the capability contract in
SURVEY.md §2 implies; aliases can be grafted on once the reference's exact
names are observable.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Mapping, Sequence

OBJECTIVES = ("binary", "multiclass", "regression", "lambdarank",
              "l1", "huber", "fair", "quantile", "poisson")
GROWTH_POLICIES = ("leafwise", "depthwise")

# Alias table so configs written against common GBDT engines keep working.
_PARAM_ALIASES = {
    "num_iterations": "num_trees",
    "n_estimators": "num_trees",
    "num_round": "num_trees",
    "num_boost_round": "num_trees",
    "eta": "learning_rate",
    "shrinkage_rate": "learning_rate",
    "max_bin": "max_bins",
    "reg_lambda": "lambda_l2",
    "lambda": "lambda_l2",
    "min_sum_hessian_in_leaf": "min_child_weight",
    "min_child_samples": "min_data_in_leaf",
    "min_data": "min_data_in_leaf",
    "min_gain_to_split": "min_split_gain",
    "bagging_fraction": "subsample",
    "feature_fraction": "colsample",
    "random_state": "seed",
    "bagging_seed": "seed",
    "application": "objective",
    "grow_policy": "growth",
    "num_classes": "num_class",
    "boosting_type": "boosting",
    "top_rate": "goss_top_rate",
    "other_rate": "goss_other_rate",
    "rate_drop": "drop_rate",
}

_OBJECTIVE_ALIASES = {
    "binary_logloss": "binary",
    "logistic": "binary",
    "binary:logistic": "binary",
    "softmax": "multiclass",
    "multi:softmax": "multiclass",
    "multiclassova": "multiclass",
    "l2": "regression",
    "mse": "regression",
    "reg:squarederror": "regression",
    "mae": "l1",
    "regression_l1": "l1",
    "reg:absoluteerror": "l1",
    "reg:quantileerror": "quantile",
    "count:poisson": "poisson",
    "lambdamart": "lambdarank",
    "rank:ndcg": "lambdarank",
}

_GROWTH_ALIASES = {
    "leaf": "leafwise",
    "lossguide": "leafwise",
    "leaf_wise": "leafwise",
    "depth": "depthwise",
    "depth_wise": "depthwise",
}


@dataclasses.dataclass(frozen=True)
class Params:
    """Frozen, validated hyper-parameters for one training run."""

    objective: str = "binary"
    num_class: int = 1
    num_trees: int = 100
    num_leaves: int = 31
    max_depth: int = -1          # -1: bounded only by num_leaves
    learning_rate: float = 0.1
    # includes the reserved missing bin (id 0).  Values above 1024 fall off
    # the Pallas histogram kernel onto the XLA builder (correct, measurably
    # slower per level) — keep <= 1024 on TPU unless accuracy demands more.
    max_bins: int = 256
    lambda_l2: float = 1.0
    min_child_weight: float = 1e-3
    min_data_in_leaf: int = 20
    min_split_gain: float = 0.0
    growth: str = "leafwise"
    # Policy for leaf-wise max_depth=-1 ("unlimited").  "auto" (default)
    # maps it to a documented effective cap min(ceil(log2(num_leaves))+4, 14)
    # whenever the batched leaf-wise grower can take the config — identical
    # policy on the CPU backend, so parity holds
    # (engine/leafwise_fast.effective_depth_params).  "exact" keeps true
    # unbounded best-first growth on the sequential grower.
    unbounded_depth: str = "auto"
    # gbdt: plain boosting (+ optional bagging). goss: gradient-based
    # one-side sampling — keep the goss_top_rate fraction with the largest
    # |grad|, Bernoulli-sample goss_other_rate of the rest and amplify their
    # grad/hess by (1-top)/other to stay unbiased.  dart: dropout boosting
    # (DART paper semantics): each iteration drops every previous
    # iteration's trees independently with prob drop_rate (whole
    # iterations for multiclass; skipped entirely with prob skip_drop),
    # fits the new tree against the pruned ensemble, then scales the new
    # tree by 1/(k+1) and the k dropped iterations by k/(k+1).
    # rf: random-forest mode (LightGBM boosting_type="rf" semantics):
    # every tree fits the gradients at the CONSTANT init score (no
    # residual chaining), trains on a fresh bagged subset (subsample < 1
    # required, per-iteration Philox draw), shrinkage is forced to 1.0
    # (see effective_learning_rate), and the prediction is
    # init + (sum of tree outputs) / n_iterations — an average of
    # full-strength trees rather than a boosted sum.
    boosting: str = "gbdt"
    goss_top_rate: float = 0.2
    goss_other_rate: float = 0.1
    drop_rate: float = 0.1
    skip_drop: float = 0.5
    max_drop: int = 50
    subsample: float = 1.0
    colsample: float = 1.0
    seed: int = 0
    categorical_features: tuple[int, ...] = ()
    # per-feature -1/0/+1; () = unconstrained. Split-level enforcement: a +1
    # feature may only split where right-child value >= left-child value.
    monotone_constraints: tuple[int, ...] = ()
    # evaluation / early stopping
    metric: str = ""              # "" = objective default
    # 0 = disabled.  Counts EVALUATIONS without improvement, not iterations:
    # with eval_period > 1 the effective patience in iterations is
    # early_stopping_rounds * eval_period (LightGBM counts iterations, but
    # it also evaluates every iteration — at eval_period=1 the two agree).
    early_stopping_rounds: int = 0
    # evaluate every k-th iteration (each eval read mid-run is a
    # device->host fetch the boosting loop waits on); early stopping checks
    # at that cadence
    eval_period: int = 1
    # binary: multiply the positive class's grad/hess (imbalanced data)
    scale_pos_weight: float = 1.0
    # Robust / count regression family (LightGBM conventions): ``alpha``
    # is the Huber delta AND the quantile level; ``fair_c`` the Fair-loss
    # scale; ``poisson_max_delta_step`` the Poisson hessian stabilizer
    alpha: float = 0.9
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    # LambdaMART
    sigmoid: float = 1.0
    ndcg_at: int = 10
    lambdarank_truncation: int = 30
    # Engine knobs (TPU path)
    hist_backend: str = "auto"   # auto | xla | pallas
    # Per-level data movement for BOTH level-synchronous growers
    # (levelwise + the batched leaf-wise expansion): "auto" carries the
    # leaf-ordered record layout through every level from the root (no
    # per-level sort / record gather, no shallow->deep handoff) whenever
    # the config admits it (engine/levelwise.deep_layout_supported; the
    # leaf-wise expansion adds a run-capacity depth cap on top —
    # engine/leafwise_fast.leafwise_layout_supported); "legacy" forces
    # the plan-based sort+gather path — the comparison arm for the
    # on-device parity gates and benches.  Switching arms changes
    # program/fusion shapes, so fp32 near-tie argmaxes may flip between
    # them (the documented chunked-vs-dispatch tolerance class in
    # engine/train.py); model quality is unaffected.
    deep_layout: str = "auto"    # auto | legacy
    # Device predict traversal table layout (engine/predict.stage_trees,
    # r21): "auto" stages the packed node-word tables — per node one
    # (2,)-uint32 limb pair holding children/threshold/feature/
    # default_left/is_cat/internal (width-asserted: children+threshold
    # 16 bits, feature 12), so the per-level traversal body pays ONE
    # small-table gather instead of the legacy structure-of-arrays ~7 —
    # falling back to "legacy" when a field exceeds its width.  "packed"
    # forces the packed arm (ValueError when it cannot fit); "legacy"
    # keeps the per-field tables — the comparison arm for parity gates
    # and benches.  Leaf-value accumulation is untouched by the layout,
    # so packed ≡ legacy predict is BITWISE on every arm (single-device,
    # sharded, serve cache) — tests/test_predict_packed.py pins it.
    predict_layout: str = "auto"    # auto | packed | legacy
    # Cross-shard histogram reduction for the level-synchronous growers
    # (levelwise + the batched leaf-wise expansion) under shard_map:
    # "fused" keeps the classic one fused grad/hess/count psum of the full
    # (P, 3, F, B) stack per builder call (the XGBoost-style allreduce —
    # the comparison arm); "feature" reduce-scatters a static contiguous
    # feature partition instead (each shard owns F/n fully-reduced
    # columns), runs the split scan on the owned slice only, and combines
    # tiny per-shard best-split records with one all-gather per level
    # (LightGBM's reduce-scatter data-parallel mode) — at Epsilon shape
    # (F=2000, B=256) the per-device reduced payload shrinks ~n-fold.
    # "auto" picks "feature" iff F * B * bin_bytes clears the hist_reduce
    # gate's wide_bytes AND more than one shard participates — a pure
    # function of (params, feature/bin shape, shard count), never of rows
    # (CLAUDE.md same-program rule).  An explicit "feature" at 1 shard
    # runs the degenerate full-slice program, so near-tie argmaxes can
    # never flip between shard counts within the arm; switching ARMS
    # (fused <-> feature) is same-program per shard count by construction
    # (reduce-scatter slices measured bitwise-equal to the psum's), and
    # pinned bitwise on the tie-free parity fixtures.  The sequential
    # (unbounded-depth leaf-wise) grower ignores this knob — its per-split
    # masked pass always rides the fused psum.
    hist_reduce: str = "auto"    # auto | fused | feature
    # Cap on boosting iterations fused into one device program (the chunked
    # dispatch path in engine/train.py).  0 = no cap beyond the calibrated
    # watchdog budget.  Precedence (single documented order): the
    # DRYAD_CH_MAX env var, when set > 0, OVERRIDES this param (the
    # operational escape hatch stays the highest authority); otherwise this
    # param applies; the resilience supervisor's adaptive chunk policy
    # (resilience/policy.py) may additionally cap individual chunks at
    # runtime, below whichever of the two is in force.  ch_max=2 is the
    # floor of that policy's degradation ladder (8 -> 4 -> 2).
    ch_max: int = 0
    hist_subtraction: bool = True
    rows_per_chunk: int = 65536  # row-tile for the chunked histogram scan
    deterministic: bool = True
    # exact: fp32 MXU passes, keeps gain-argmax parity with the CPU ref.
    # fast: single-pass bf16 MXU (~6x histogram speedup); counts stay exact
    # (f32 accumulation of 0/1 products), grad/hess sums carry ~0.4%/elem
    # rounding — tree structures may differ slightly, model quality doesn't.
    hist_precision: str = "exact"

    # ---- derived -----------------------------------------------------------
    @property
    def effective_num_leaves(self) -> int:
        if self.growth == "depthwise" and self.max_depth > 0:
            return min(self.num_leaves, 2 ** self.max_depth) if self.num_leaves > 0 else 2 ** self.max_depth
        return self.num_leaves

    @property
    def max_nodes(self) -> int:
        return 2 * self.effective_num_leaves - 1

    @property
    def num_outputs(self) -> int:
        """Trees trained per boosting iteration (K for multiclass, else 1)."""
        return self.num_class if self.objective == "multiclass" else 1

    @property
    def effective_learning_rate(self) -> float:
        """1.0 under boosting='rf' — rf averages full-strength trees
        (LightGBM likewise forces shrinkage 1.0 in rf mode); shrinking
        them would just scale the average.  Both leaf-value finalizers
        (engine/grower.py, cpu/histogram.leaf_output) use THIS, never the
        raw learning_rate, so the two backends cannot diverge."""
        return 1.0 if self.boosting == "rf" else self.learning_rate

    def validate(self) -> "Params":
        if self.objective not in OBJECTIVES:
            raise ValueError(f"objective must be one of {OBJECTIVES}, got {self.objective!r}")
        if self.objective == "multiclass" and self.num_class < 2:
            raise ValueError("multiclass requires num_class >= 2")
        if self.growth not in GROWTH_POLICIES:
            raise ValueError(f"growth must be one of {GROWTH_POLICIES}, got {self.growth!r}")
        if not (2 <= self.max_bins <= 65536):
            raise ValueError("max_bins must be in [2, 65536]")
        if self.categorical_features and self.max_bins > 256:
            raise ValueError("categorical splits support max_bins <= 256 (bitset width)")
        if self.min_data_in_leaf < 1:
            raise ValueError("min_data_in_leaf must be >= 1")
        if any(m not in (-1, 0, 1) for m in self.monotone_constraints):
            raise ValueError("monotone_constraints entries must be -1, 0 or +1")
        if self.boosting not in ("gbdt", "goss", "dart", "rf"):
            raise ValueError("boosting must be 'gbdt', 'goss', 'dart' or 'rf'")
        if self.boosting == "rf" and self.subsample >= 1.0:
            # without row bagging every rf tree would fit the SAME
            # gradients on the SAME rows and the average would equal one
            # tree (LightGBM likewise requires bagging for rf)
            raise ValueError(
                "boosting='rf' requires subsample < 1.0: trees only "
                "de-correlate through per-iteration row bagging")
        if self.boosting == "dart":
            if not (0.0 <= self.drop_rate <= 1.0):
                raise ValueError("drop_rate must be in [0, 1]")
            if not (0.0 <= self.skip_drop <= 1.0):
                raise ValueError("skip_drop must be in [0, 1]")
            if self.max_drop < 1:
                raise ValueError("max_drop must be >= 1")
            if self.early_stopping_rounds:
                # best_iteration truncation is unsound under DART: drops
                # AFTER the best iteration rescale earlier trees in place,
                # so the truncated model no longer matches the metric that
                # selected it (LightGBM disables early stopping here too)
                raise ValueError(
                    "early_stopping_rounds is incompatible with "
                    "boosting='dart' (later drop iterations rescale the "
                    "trees the best iteration was scored with)")
        if self.boosting == "goss":
            if not (0 < self.goss_top_rate < 1) or not (0 < self.goss_other_rate < 1):
                raise ValueError("goss rates must be in (0, 1)")
            if self.goss_top_rate + self.goss_other_rate > 1:
                raise ValueError("goss_top_rate + goss_other_rate must be <= 1")
            if self.subsample < 1.0:
                raise ValueError("goss replaces bagging; set subsample=1.0")
        if self.num_leaves < 2:
            raise ValueError("num_leaves must be >= 2")
        if self.num_trees < 0:
            # 0 is the warm-start no-op append (train(init_model=m,
            # num_trees=0) returns a predict-identical copy); dryad.train
            # rejects it for a FRESH run, where an empty model is a typo
            raise ValueError("num_trees must be >= 0")
        if not (0.0 < self.learning_rate):
            raise ValueError("learning_rate must be > 0")
        if not (0.0 < self.subsample <= 1.0) or not (0.0 < self.colsample <= 1.0):
            raise ValueError("subsample/colsample must be in (0, 1]")
        if not (self.scale_pos_weight > 0.0):
            raise ValueError("scale_pos_weight must be > 0")
        if self.objective == "quantile" and not (0.0 < self.alpha < 1.0):
            raise ValueError("quantile objective needs alpha in (0, 1)")
        if self.objective == "huber" and not (self.alpha > 0.0):
            raise ValueError("huber objective needs alpha (delta) > 0")
        if self.objective == "fair" and not (self.fair_c > 0.0):
            raise ValueError("fair objective needs fair_c > 0")
        if (self.objective == "poisson"
                and not (self.poisson_max_delta_step >= 0.0)):
            raise ValueError("poisson_max_delta_step must be >= 0")
        if self.eval_period < 1:
            raise ValueError("eval_period must be >= 1")
        if self.unbounded_depth not in ("auto", "exact"):
            raise ValueError("unbounded_depth must be auto|exact")
        if self.hist_backend not in ("auto", "xla", "pallas"):
            raise ValueError("hist_backend must be auto|xla|pallas")
        if self.deep_layout not in ("auto", "legacy"):
            raise ValueError("deep_layout must be auto|legacy")
        if self.predict_layout not in ("auto", "packed", "legacy"):
            raise ValueError("predict_layout must be auto|packed|legacy")
        if self.hist_reduce not in ("auto", "fused", "feature"):
            raise ValueError("hist_reduce must be auto|fused|feature")
        if self.ch_max < 0:
            raise ValueError("ch_max must be >= 0 (0 = uncapped)")
        if self.hist_precision not in ("exact", "fast"):
            raise ValueError("hist_precision must be exact|fast")
        return self

    def replace(self, **kw: Any) -> "Params":
        return dataclasses.replace(self, **kw).validate()

    # ---- construction ------------------------------------------------------
    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "Params":
        norm: dict[str, Any] = {}
        known = {f.name for f in dataclasses.fields(cls)}
        for key, value in d.items():
            key = _PARAM_ALIASES.get(key, key)
            if key == "objective" and isinstance(value, str):
                value = _OBJECTIVE_ALIASES.get(value, value)
            if key == "growth" and isinstance(value, str):
                value = _GROWTH_ALIASES.get(value, value)
            if key in ("categorical_features", "monotone_constraints") and isinstance(value, Sequence):
                value = tuple(int(v) for v in value)
            if key not in known:
                raise ValueError(f"unknown parameter {key!r}")
            norm[key] = value
        return cls(**norm).validate()

    @classmethod
    def from_json(cls, path: str) -> "Params":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


# ---- growth-policy helpers (jax-free: the CPU backend imports these) --------
# Shared by engine/leafwise_fast.py (which re-exports ``supports``) and both
# trainer entries, so the max_depth=-1 mapping can never diverge by backend.
MAX_FAST_DEPTH = 14
# Peak-residency envelope of the batched grower, its ONE admission rule:
#
#   LEAFWISE_PEAK_FACTOR x pinned + rows x per_row <= LEAFWISE_TOTAL_BYTES_BUDGET
#
# ``pinned`` is the (2^(cap-1), 3, F, B) float32 expansion buffer, which the
# widest level fans out (small/large/l/r, the 2P children concat for the
# split finder, the kernels' chunk-padded output); ``per_row`` is what a row
# stages beside it (the layout's records or the plan path's sort and
# gathers, widened copies of the binned matrix, the g/h/score columns).
# 12 GiB of temporaries leave a v5e's 15.75 GiB the room for what the job
# holds live.  The rule reckons what ONE DEVICE holds: every shard of a
# mesh holds the whole expansion (``pinned`` is as it is on one chip) and
# its own share of the rows, ceil(global rows / shards).  A pure function of
# params + GLOBAL shape + shard count (as ``hist_reduce_resolved``), NEVER
# of backend nor of the local row count a traced function sees, so the CPU
# mirror (one shard) routes as one chip does and parity holds there.
#
# The constants are an upper envelope of one grow iteration's
# temp_size_in_bytes as the TPU compiler reckons it (v5e, compiled ahead of
# time at the shapes tests/test_rank_plan.py lists; three of them run on
# the chip, each within 8 % of its line; PERF.md section 6, PR 32):
#   x pinned     5.8 at 28 features, 8.4 at 300, 9.9-10.9 at 136      -> 11
#   bytes a row  670 at 28 features (wired layout), 250 at 136, 540 at
#                300, 11,100-13,900 at 2000 (u32 copies of the whole
#                matrix)                           -> 512 + 7 a feature
# 2,270,296 x 136 at cap 12 (cell mslr2m_leaf255.job_rank): reckoned
# 12.77 GB, compiled 9.72, the whole chunk program on the chip 10.50
# (11.18 GB held, 66 %; 4.4 s an iteration where the sequential grower
# takes 23.97).  10M x 28 at cap 12: reckoned 9.18, compiled 7.73, on the
# chip 7.78.  1,000,000 x 968 at 64 bins (cell bosch1m_leaf255.job_missing,
# missing values, caps 12 and 11 refused): at cap 10 reckoned 11.49,
# compiled 8.16 for one iteration, the step program on the chip 8.16
# (9.42 GB held, 56 %).  The rule errs high between the widths it was fitted at (10M x
# 136 at cap 12 compiles to 11.8 GB and is refused).  It must not err low:
# a refused shape runs a slower grower, an admitted one that does not fit
# dies.  Shape-only callers (num_rows None) are held to the expansion alone.
#
# Narrow tables: where a row's record fits the leaf-ordered layout
# (engine/leafperm.py: 9 bytes and one a feature, at most 128), the wired
# expansion's buffers also hold the mandated tiles of every level, and a row
# costs the compiler about 670 B whatever the width; at 8 features
# 512 + 7 F fell short: 11M x 8 at cap 12 compiled to 8.11 GB (8.10 with no
# categorical column) where the rule above reckoned 6.98.  So there the rows
# are reckoned at least LEAFWISE_WIRED_ROW_FLOOR a row plus two buffers'
# mandated tiles (ceil(8/7 (2 x 2^D + 2)) + 2 tiles of 64 KB each: 1.23 GB
# at cap 12), which envelops the compiler's figures at 4 to 28 features and
# 1M to 20M rows.  Of the compiled shapes it changes one verdict: 20M x 8
# at cap 12, reckoned 12.23 GB before where the compiler gives 14.10, is now
# reckoned 14.58 and refused by the budget, as 20M x 28 (14.45 compiled) is,
# though it would fit the compiler's 15.75 GB; that shape never ran on a chip.
LEAFWISE_PEAK_FACTOR = 11
LEAFWISE_ROW_BYTES = 512          # + LEAFWISE_CELL_BYTES a feature
LEAFWISE_CELL_BYTES = 6           # + the bin's own byte(s)
LEAFWISE_TOTAL_BYTES_BUDGET = 12 << 30
LEAFWISE_WIRED_RECORD_BYTES = 128     # the widest record the layout takes
LEAFWISE_WIRED_ROW_FLOOR = 640
LEAFWISE_WIRED_TILE_BYTES = 512 * 128
# max_depth=-1: caps tried under the documented one before the sequential
# grower (each level down halves ``pinned``)
LEAFWISE_CAP_STEPS = 2


def hist_reduce_resolved(p: Params, num_features: int, total_bins: int,
                         n_shards: int) -> str:
    """The ONE hist_reduce gate — shared by both level-synchronous growers
    AND train._comm_stats so the observability accounting can never drift
    from the program choice (the nat-gate/phase-plan precedent, ADVICE
    r4).  A pure function of (params, feature/bin shape, shard count) —
    NEVER of the row count (CLAUDE.md same-program rule).  "auto": the
    feature-parallel reduction pays one combine all-gather per level, so
    it only wins where the per-slot histogram column is big — F * B *
    bin_bytes at or past the gate's wide_bytes (policy/gates.py: 256 KB;
    Epsilon's 2000 x 256 u8 = 500 KB clears it, Higgs' 28 x 256 = 7 KB
    stays fused)."""
    if p.hist_reduce != "auto":
        return p.hist_reduce
    from dryad_tpu.policy.gates import resolve

    return resolve("hist_reduce", {"num_features": num_features,
                                   "total_bins": total_bins,
                                   "n_shards": n_shards})


def leafwise_fast_supported(p: Params, num_features: int,
                            total_bins: int,
                            num_rows: int | None = None,
                            n_shards: int = 1) -> bool:
    """Whether the batched leaf-wise grower can take this config: a finite
    depth, histogram subtraction, and a peak residency ON ONE DEVICE inside
    ``LEAFWISE_TOTAL_BYTES_BUDGET`` (the comment at the constant has the
    rationale, the compiler's numbers and the chip's).  ``num_rows`` is the
    GLOBAL, unpadded row count and ``n_shards`` the mesh's size (1 without a
    mesh, and in ``cpu/trainer.py``): the rows' working set is a shard's,
    ``ceil(num_rows / n_shards)``, beside the whole expansion, which every
    shard holds.  None counts the expansion alone (shape-only callers).
    Never the local row count of a traced shard: every caller of one job
    (``train_device``, ``grow_any`` under ``shard_map``, ``_comm_stats``)
    hands over the same two numbers and gets the same verdict.

    The invariant: N shards and one grow bit-identical trees wherever both
    are admitted at the same cap, which is every shape the parity tests run.
    More shards only ever admit more (the rows' term falls, nothing rises):
    12M x 67 at 255 leaves is refused on one chip at caps 12, 11 and 10
    (4.64 GB of expansion + 12.0 GB of rows) and admitted at 12 on four
    (4.64 + 2.99), since a mesh exists to hold what one chip cannot.  There
    the one-chip job would grow another tree (the sequential grower's
    uncapped one); gauge ``dryad_leafwise_depth_cap`` says which cap a run
    had."""
    D = p.max_depth
    if not 0 < D <= MAX_FAST_DEPTH:
        return False
    if not p.hist_subtraction:
        return False
    Pf = 1 << max(D - 1, 0)
    pinned = Pf * 3 * num_features * total_bins * 4
    bin_bytes = 1 if total_bins <= 256 else 2
    per_row = (LEAFWISE_ROW_BYTES + 16 * p.num_outputs
               + num_features * (LEAFWISE_CELL_BYTES + bin_bytes))
    shard_rows = -(-(num_rows or 0) // max(int(n_shards), 1))
    return (LEAFWISE_PEAK_FACTOR * pinned + leafwise_rows_bytes(D, num_features, bin_bytes,
                                                                shard_rows, per_row)
            <= LEAFWISE_TOTAL_BYTES_BUDGET)


def leafwise_rows_bytes(D: int, num_features: int, bin_bytes: int, rows: int,
                        per_row: int) -> int:
    """What the rows of one device add to the expansion's peak at cap ``D``:
    ``rows x per_row``, and where a row's record fits the leaf-ordered
    layout at least ``LEAFWISE_WIRED_ROW_FLOOR`` a row plus two layout
    buffers' mandated tiles (the comment at the constants)."""
    held = rows * per_row
    if rows and 9 + num_features * bin_bytes <= LEAFWISE_WIRED_RECORD_BYTES:
        mandated = -(-8 * (2 * (1 << D) + 2) // 7) + 2
        held = max(held, rows * LEAFWISE_WIRED_ROW_FLOOR
                   + 2 * mandated * LEAFWISE_WIRED_TILE_BYTES)
    return held


def effective_depth_params(p: Params, num_features: int,
                           total_bins: int,
                           num_rows: int | None = None,
                           n_shards: int = 1) -> Params:
    """The documented ``max_depth=-1`` policy for leaf-wise growth at scale.

    Unbounded-depth leaf-wise growth cannot be pre-expanded, so it takes the
    sequential O(N·L) grower — the out-of-the-box configuration's worst
    asymptotics (VERDICT r3 #3).  Under ``unbounded_depth="auto"`` (the
    default), "unlimited" maps to a documented effective cap

        min(ceil(log2(num_leaves)) + 4, MAX_FAST_DEPTH)

    — four levels of headroom past a balanced tree, enough that a best-first
    tree constrained by the cap is almost always the unconstrained one —
    whenever the resulting config rides the batched grower; where the
    envelope refuses that cap, the cap one level and then two levels under
    it (``LEAFWISE_CAP_STEPS``; the run's cap is in the gauge
    ``dryad_leafwise_depth_cap``).  The SAME mapping runs in
    ``cpu/trainer.py`` and ``engine/train.py``, so CPU↔TPU tree parity is
    untouched (it is a pure function of params + data shape + the mesh's
    size, never of backend; ``n_shards`` as in ``leafwise_fast_supported``:
    the envelope counts a device's share of the rows, so a mesh may be
    given the documented cap where one chip is refused it).  Configs the batched grower cannot take at any of the three
    (budget, subtraction disabled) keep true-unbounded sequential
    semantics, as does ``unbounded_depth="exact"``.

    What the cap did to the source's trees at 10M rows x 28, 255 leaves,
    cap 12 (benchmark configuration ``higgs10m_leaf255``; the plain
    reference's ``cap_stopped_steps``, chip runs of PR 27): no step of 254
    was passed over for the cap alone on the first three trees of 13 seeds
    nor on the first six of one; that seed's seventh tree lost 48 steps to
    it and its eighth 11 (the uncapped trees reach depth 13; valid AUC after
    eight trees 0.741955 against 0.741937 capped), and from there on 10 to
    54 of a tree's 255 leaves sit at depth 12.
    """
    if p.max_depth > 0 or p.growth != "leafwise" or p.unbounded_depth == "exact":
        return p
    L = p.effective_num_leaves
    eff = min(max((L - 1).bit_length(), 1) + 4, MAX_FAST_DEPTH)
    # the documented cap, then one and two levels under it (each halves the
    # pinned buffer) before the sequential grower
    for cap in range(eff, eff - LEAFWISE_CAP_STEPS - 1, -1):
        if L > (1 << cap):
            break                     # cap cannot express the leaf budget
        cand = p.replace(max_depth=cap)
        if leafwise_fast_supported(cand, num_features, total_bins, num_rows,
                                   n_shards):
            return cand
    return p


def make_params(params: "Params | Mapping[str, Any] | None" = None, **kw: Any) -> Params:
    """Accept a Params, a plain dict, or kwargs — the ``dryad.train`` front door."""
    if params is None:
        return Params.from_dict(kw)
    if isinstance(params, Params):
        return (params.replace(**kw) if kw else params.validate())
    merged = dict(params)
    merged.update(kw)
    return Params.from_dict(merged)
