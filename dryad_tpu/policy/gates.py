"""``resolve(gate, shape_features, device_kind) -> arm`` — the ONE entry.

Each routed call site keeps its existing signature and calls in with its
shape features; the threshold CONSTANTS live in the table, the
COMPARISON SEMANTICS live here, verbatim from the pre-policy gate
bodies (cited per resolver).  Everything stays a pure function of
(params, feature/bin shape, shard count) — NEVER of the row count,
which under shard_map is the local shard and would let 1-shard and
N-shard runs choose different histogram programs (the CLAUDE.md
same-program rule).  Every resolution is recorded: ``decisions()`` is
the /stats block, ``dryad_policy_choice{gate,arm}`` the obs gauge
(no-ops with obs disabled — the registry owns that contract).
"""

from __future__ import annotations

from typing import Callable, Optional

from dryad_tpu.policy import table as _table
from dryad_tpu.policy.device import current_device_kind

_UNSET = object()

# gate -> (values, features) -> arm.  Comparison semantics only; every
# constant comes from the overlaid table values.
_RESOLVERS: dict = {}


def _resolver(name: str):
    def deco(fn: Callable) -> Callable:
        _RESOLVERS[name] = fn
        return fn
    return deco


@_resolver("partition")
def _partition(v: dict, f: dict) -> str:
    # levelwise.partition_prefers_reduce (r5): masked reduce while the
    # per-row sequential traffic stays under the calibrated row budget
    row_bytes = f["num_features"] * f["itemsize"]
    return "reduce" if row_bytes <= v["reduce_max_row_bytes"] else "gather"


@_resolver("hist_reduce")
def _hist_reduce(v: dict, f: dict) -> str:
    # config.hist_reduce_resolved (r16).  bin_bytes is the binned-matrix
    # itemsize (u8 below 257 bins, else u16) — structural, not calibrated
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
    # levelwise.deep_layout_supported's CALIBRATED caps (the structural
    # exclusions — backend, packed-word widths, _REC_WB — stay at the
    # call site; a table can only narrow them, never widen past them)
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
    # predict.SHARDED_MIN_WORK: rows x num_outputs must carry real work
    return "sharded" if f["work"] >= v["min_work"] else "single"


@_resolver("chunk_cap")
def _chunk_cap(v: dict, f: dict) -> str:
    # resilience.RetryPolicy.ch_max_ladder — the decision record is the
    # ladder spelling; consumers take the tuple via gate_value()
    return "/".join(str(int(s)) for s in v["ladder"])


#: the gate catalog (stable order: README table, selftest sweep)
GATE_NAMES = tuple(_RESOLVERS)

#: newest decision per gate: {gate: {"arm", "detail", "count"}}
_DECISIONS: dict = {}
_LAST_ARM: dict = {}


def resolve(gate: str, shape_features: dict,
            device_kind=_UNSET, table=None,
            detail: Optional[str] = None) -> str:
    """Resolve one gate for one shape.  ``device_kind`` defaults to the
    process's device (``None`` explicitly = committed defaults);
    ``table`` defaults to the process table (``current_table``)."""
    if gate not in _RESOLVERS:
        raise KeyError(f"unknown policy gate {gate!r} "
                       f"(catalog: {', '.join(GATE_NAMES)})")
    tab = table if table is not None else _table.current_table()
    values = tab.gate_values(gate, _device_kind_for(tab, device_kind))
    arm = _RESOLVERS[gate](values, shape_features)
    _note(gate, arm, detail)
    return arm


def _device_kind_for(tab, device_kind):
    """Resolve the effective device key WITHOUT waking a device runtime
    when no table entry could change the answer: the committed table
    ships only ``_default``, so the common path (fleet control plane,
    RetryPolicy construction, CLI startup before the CPU-audit env is
    pinned) must never trigger the lazy jax probe.  Only a table that
    actually carries device-keyed entries pays the (memoized,
    best-effort) ``current_device_kind()`` call."""
    if device_kind is not _UNSET:
        return device_kind
    if not any(k != _table.DEFAULT_DEVICE_KEY for k in tab.devices):
        return None
    return current_device_kind()


def gate_value(gate: str, key: str, device_kind=_UNSET, table=None):
    """The raw calibrated value behind a gate (serve's threshold default,
    the resilience ladder) — same overlay as ``resolve``."""
    tab = table if table is not None else _table.current_table()
    device_kind = _device_kind_for(tab, device_kind)
    values = tab.gate_values(gate, device_kind)
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
    """The serve ``/stats`` "policy" block: where the table came from,
    whether it fell back, which device key resolutions use, and the
    newest decision per gate (incl. predict_layout's fallback reason —
    the r23 small-fix satellite: /stats now says WHY a model serves
    legacy)."""
    tab = _table.current_table()
    return {
        "device_kind": current_device_kind(),
        "table_source": tab.source,
        "table_explicit": tab.explicit,
        "fallback_reason": tab.fallback_reason,
        "device_keys": sorted(tab.devices),
        "decisions": decisions(),
    }
