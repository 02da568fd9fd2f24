"""``resolve(gate, shape_features) -> arm`` — the ONE entry, and the ONE
home of every dispatch threshold.

Each routed call site keeps its existing signature and calls in with its
shape features; the threshold CONSTANTS live in ``THRESHOLDS`` below and
nowhere else under ``dryad_tpu/``, the COMPARISON SEMANTICS in the
resolvers beside it.  Everything stays a pure function of (params,
feature/bin shape, shard count) — NEVER of the row count, which under
shard_map is the local shard and would let 1-shard and N-shard runs
choose different histogram programs (the CLAUDE.md same-program rule).
Every resolution is recorded: ``decisions()`` is the /stats block,
``dryad_policy_choice{gate,arm}`` the obs gauge (no-ops with obs
disabled — the registry owns that contract).
"""

from __future__ import annotations

from typing import Callable, Optional

from dryad_tpu.policy.device import current_device_kind

#: Every dispatch threshold, each assigned here and only here.  Each
#: entry names its evidence: "chip, PR n" where a chip run decided the
#: value, else "hand-tuned": set before the chip, and no benchmark cell
#: straddles it (ROADMAP "Gates without a far side").
THRESHOLDS: dict = {
    # levelwise.partition_prefers_reduce: masked reduce over the (N, F)
    # matrix while F*itemsize <= 4 KB/row, else gather.  Hand-tuned
    "partition": {"reduce_max_row_bytes": 4096},
    # config.hist_reduce_resolved: feature-parallel reduction once
    # F * B * bin_bytes >= 256 KB AND >1 shard participates.  Hand-tuned
    "hist_reduce": {"wide_bytes": 262144},
    # histogram.resolve_backend "auto": the Pallas kernel where Mosaic
    # runs, XLA everywhere else.  Hand-tuned (structural, in effect)
    "hist_backend": {"pallas_platforms": ["tpu"]},
    # levelwise.deep_layout_supported: past 512 leaves the empty
    # segments' mandated tiles stop being noise; past 128 B a record
    # (leafperm._REC_WB is the structural twin) the moved bytes outgrow
    # the sort+gather they replace.  Hand-tuned
    "deep_layout": {"max_leaves": 512, "max_record_bytes": 128},
    # leafwise_fast's run-slot cap: 4096 admits depth cap 12, what
    # unbounded depth gives 255 leaves.  Chip, PR 29 (1024 before): the
    # verdict and its numbers are at leafwise_fast._MAX_WIRED_SEGMENTS
    "leafwise_layout": {"max_segments": 4096},
    # predict.stage_trees "auto": the packed node-word table when every
    # traversal field fits its limb width.  Hand-tuned
    "predict_layout": {"preferred": "packed"},
    # serve's default sharded_threshold: below ~32k row-outputs the
    # per-shard blocks lose to one device's dispatch cost.  Hand-tuned
    "predict_sharded": {"min_work": 32768},
    # resilience.RetryPolicy.ch_max_ladder: chunk-cap degradation steps,
    # widest first, ending on the 2-iteration floor.  Hand-tuned
    "chunk_cap": {"ladder": [8, 4, 2]},
}

# gate -> (values, features) -> arm.  Comparison semantics only; every
# constant comes from the gate's THRESHOLDS entry.
_RESOLVERS: dict = {}


def _resolver(name: str):
    def deco(fn: Callable) -> Callable:
        _RESOLVERS[name] = fn
        return fn
    return deco


@_resolver("partition")
def _partition(v: dict, f: dict) -> str:
    # levelwise.partition_prefers_reduce (r5): masked reduce while the
    # per-row sequential traffic stays under the row budget
    row_bytes = f["num_features"] * f["itemsize"]
    return "reduce" if row_bytes <= v["reduce_max_row_bytes"] else "gather"


@_resolver("hist_reduce")
def _hist_reduce(v: dict, f: dict) -> str:
    # config.hist_reduce_resolved (r16).  bin_bytes is the binned-matrix
    # itemsize (u8 below 257 bins, else u16) — structural, no threshold
    bin_bytes = 1 if f["total_bins"] <= 256 else 2
    wide = (f["num_features"] * f["total_bins"] * bin_bytes
            >= v["wide_bytes"])
    return "feature" if (wide and f["n_shards"] > 1) else "fused"


@_resolver("hist_backend")
def _hist_backend(v: dict, f: dict) -> str:
    # histogram.resolve_backend "auto": Pallas on TPU-class platforms
    return "pallas" if f["platform"] in v["pallas_platforms"] else "xla"


@_resolver("deep_layout")
def _deep_layout(v: dict, f: dict) -> str:
    # levelwise.deep_layout_supported's TUNED caps (the structural
    # exclusions — backend, packed-word widths — stay at the call site)
    if f["num_leaves"] > v["max_leaves"]:
        return "legacy"
    if f["record_bytes"] > v["max_record_bytes"]:
        return "legacy"
    return "layout"


@_resolver("leafwise_layout")
def _leafwise_layout(v: dict, f: dict) -> str:
    # leafwise_fast's expansion-width cap: 2^D run slots vs the measured
    # mandatory-tile cost (_MAX_WIRED_SEGMENTS: depth caps to 12, PR 29)
    d = f["max_depth"]
    if not 0 < d or (1 << d) > v["max_segments"]:
        return "legacy"
    return "layout"


@_resolver("predict_layout")
def _predict_layout(v: dict, f: dict) -> str:
    # predict.stage_trees "auto" (r21): the preferred arm when every
    # traversal field fits its packed width, legacy otherwise
    return v["preferred"] if f["fits"] else "legacy"


@_resolver("predict_sharded")
def _predict_sharded(v: dict, f: dict) -> str:
    # serve's sharded_threshold default: rows x num_outputs must carry
    # real work
    return "sharded" if f["work"] >= v["min_work"] else "single"


@_resolver("chunk_cap")
def _chunk_cap(v: dict, f: dict) -> str:
    # resilience.RetryPolicy.ch_max_ladder — the decision record is the
    # ladder spelling; consumers take the tuple via gate_value()
    return "/".join(str(int(s)) for s in v["ladder"])


#: the gate catalog (stable order: the README table's)
GATE_NAMES = tuple(_RESOLVERS)

#: newest decision per gate: {gate: {"arm", "detail", "count"}}
_DECISIONS: dict = {}
_LAST_ARM: dict = {}


def resolve(gate: str, shape_features: dict,
            detail: Optional[str] = None) -> str:
    """Resolve one gate for one shape against ``THRESHOLDS``."""
    if gate not in _RESOLVERS:
        raise KeyError(f"unknown policy gate {gate!r} "
                       f"(catalog: {', '.join(GATE_NAMES)})")
    arm = _RESOLVERS[gate](THRESHOLDS[gate], shape_features)
    _note(gate, arm, detail)
    return arm


def gate_value(gate: str, key: str):
    """The raw threshold behind a gate (serve's threshold default, the
    resilience ladder)."""
    values = THRESHOLDS[gate]
    if key not in values:
        raise KeyError(f"gate {gate!r} has no value {key!r}")
    v = values[key]
    return tuple(v) if isinstance(v, list) else v


def _note(gate: str, arm: str, detail: Optional[str]) -> None:
    prev = _DECISIONS.get(gate)
    count = (prev["count"] + 1) if prev else 1
    _DECISIONS[gate] = {"arm": arm, "detail": detail, "count": count}
    try:
        from dryad_tpu.obs.registry import default_registry
    except Exception:  # noqa: BLE001 — decisions must survive a broken obs
        return
    reg = default_registry()
    if not reg.enabled:
        return
    fam = reg.gauge("dryad_policy_choice",
                    "Chosen dispatch arm per policy gate (1 = active)")
    last = _LAST_ARM.get(gate)
    if last is not None and last != arm:
        fam.labels(gate=gate, arm=last).set(0.0)
    _LAST_ARM[gate] = arm
    fam.labels(gate=gate, arm=arm).set(1.0)


def decisions() -> dict:
    """Snapshot of the newest decision per gate (the /stats block)."""
    return {g: dict(d) for g, d in _DECISIONS.items()}


def reset_decisions() -> None:
    """Forget recorded decisions (test isolation)."""
    _DECISIONS.clear()
    _LAST_ARM.clear()


def stats_block() -> dict:
    """The serve ``/stats`` "policy" block: the device the process runs
    on and the newest decision per gate (incl. predict_layout's fallback
    reason: /stats says WHY a model serves legacy)."""
    return {
        "device_kind": current_device_kind(),
        "decisions": decisions(),
    }
