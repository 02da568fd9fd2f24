"""Calibration: the measured loop from stage probes to the policy table.

``run_sweep`` drives the liveness-proven stage probes
(``engine/probes``) in A/B arm pairs per gate — partition reduce vs
gather, hist_reduce fused vs feature at three widths, packed vs legacy
predict traversal, plus the two histogram passes as informational walls
— and ``derive_overrides`` turns the walls into per-gate table entries
(spread-vetoed: a >5% arm spread keeps the committed value, the
CLAUDE.md "suspect capture, never a verdict" rule).  ``calibrate``
stamps the result with device_kind/git_rev and merges it under that
device's key; ``check_calib`` diffs a live sweep's resolutions against
the committed table the way ``bench_trend --check`` does.

``run_selftest`` is the ci.sh gate (CPU, seeded, NO probes): the
committed golden must equal the code defaults, every gate must resolve
identically to the pre-PR hand-tuned constants across shapes straddling
each threshold, a perturbed table entry must flip EXACTLY the intended
gate and nothing else, and save/load must round-trip resolutions
bitwise.

Probe imports stay lazy inside the sweep functions: importing this
module (and running the selftest) is jax-free by lint — the sweep is
the one explicitly device-facing operation in the package.
"""

from __future__ import annotations

from typing import Optional

from dryad_tpu.policy import gates as _gates
from dryad_tpu.policy import table as _table

#: per-arm spread above this vetoes a derived override (CLAUDE.md)
SPREAD_SUSPECT = 0.05

#: the sweep plan: gate -> A/B probe arms at the widths that straddle
#: the committed threshold (num_features; bins fixed at 256 so u8 row
#: bytes == F).  ``derive`` names the rule below; None = informational.
SWEEP = (
    {"gate": "partition",
     "arms": {"reduce": "partition_reduce", "gather": "partition_gather"},
     "widths": (512, 4096, 8192),
     "derive": "max_winning_row_bytes"},
    {"gate": "hist_reduce",
     "arms": {"fused": "split_scan", "feature": "hist_reduce"},
     "widths": (128, 1024, 2000),
     "derive": "crossover_wide_bytes"},
    {"gate": "predict_layout",
     "arms": {"packed": "predict_traversal_packed",
              "legacy": "predict_traversal"},
     "widths": (28,),
     "derive": "preferred_arm"},
    {"gate": "hist_backend",
     "arms": {"masked": "hist_masked", "segmented": "hist_segmented"},
     "widths": (28,),
     "derive": None},
)

#: probe bins for every sweep shape (u8 binned matrix: row bytes == F)
_SWEEP_BINS = 256


def run_sweep(rows: Optional[int] = None, K: int = 3, reps: int = 2,
              num_slots: int = 8, quiet: bool = True) -> dict:
    """Measured walls: {gate: {width: {arm: {"ms", "spread"}}}}."""
    from dryad_tpu.engine import probes

    out: dict = {}
    for job in SWEEP:
        gate = job["gate"]
        out[gate] = {}
        for width in job["widths"]:
            out[gate][width] = {}
            for arm, probe in job["arms"].items():
                r = probes.run_probe(
                    probe, rows=rows, K=K, reps=reps,
                    num_features=width, total_bins=_SWEEP_BINS,
                    num_slots=num_slots)
                out[gate][width][arm] = {"ms": r["ms"],
                                         "spread": r["spread"]}
                if not quiet:
                    print(f"calib {gate:15s} F={width:<5d} {arm:10s} "
                          f"{r['ms']:10.2f} ms  spread {r['spread']:.3f}")
    return out


def _suspect(walls: dict) -> bool:
    return any(a["spread"] > SPREAD_SUSPECT for a in walls.values())


def derive_overrides(measured: dict) -> tuple[dict, dict]:
    """Walls -> per-gate table overrides + per-gate verdict notes.

    Rules (each keeps the committed value on a spread veto or when the
    measurements never cross — overrides only record what the device
    actually demonstrated):

    * ``max_winning_row_bytes`` (partition): the largest tested u8 width
      where the reduce arm still beats the gather becomes
      ``reduce_max_row_bytes`` (0 when the gather wins everywhere).
    * ``crossover_wide_bytes`` (hist_reduce): the smallest tested width
      where the feature arm beats the fused scan sets ``wide_bytes`` to
      that shape's F*B*bin_bytes.
    * ``preferred_arm`` (predict_layout): the faster traversal arm.
    """
    overrides: dict = {}
    notes: dict = {}
    rules = {job["gate"]: job["derive"] for job in SWEEP}
    for gate, by_width in measured.items():
        rule = rules.get(gate)
        if rule is None:
            notes[gate] = "informational"
            continue
        if any(_suspect(w) for w in by_width.values()):
            notes[gate] = "suspect capture (arm spread > "\
                f"{SPREAD_SUSPECT:.0%}) — committed value kept"
            continue
        if rule == "max_winning_row_bytes":
            wins = [w for w, arms in sorted(by_width.items())
                    if arms["reduce"]["ms"] <= arms["gather"]["ms"]]
            overrides[gate] = {"reduce_max_row_bytes":
                               (max(wins) if wins else 0)}
            notes[gate] = f"reduce wins at widths {wins}"
        elif rule == "crossover_wide_bytes":
            bin_bytes = 1 if _SWEEP_BINS <= 256 else 2
            crossed = [w for w, arms in sorted(by_width.items())
                       if arms["feature"]["ms"] < arms["fused"]["ms"]]
            if crossed:
                overrides[gate] = {"wide_bytes":
                                   crossed[0] * _SWEEP_BINS * bin_bytes}
                notes[gate] = f"feature wins from width {crossed[0]}"
            else:
                notes[gate] = "feature arm never won — committed kept"
        elif rule == "preferred_arm":
            (width, arms), = list(by_width.items())
            pick = min(arms, key=lambda a: arms[a]["ms"])
            overrides[gate] = {"preferred": pick}
            notes[gate] = f"{pick} faster at width {width}"
    return overrides, notes


def calibrate(device_kind: Optional[str] = None, rows: Optional[int] = None,
              quiet: bool = True) -> tuple[dict, dict]:
    """Run the sweep and build the refreshed ``devices`` map (committed
    devices + this device's derived entry, stamped) plus the flat
    ``CALIB_*`` artifact dict for the trend ledger."""
    from dryad_tpu.obs.trends import artifact_stamp
    from dryad_tpu.policy.device import current_device_kind

    if device_kind is None:
        device_kind = current_device_kind()
    measured = run_sweep(rows=rows, quiet=quiet)
    overrides, notes = derive_overrides(measured)
    stamp = artifact_stamp(device_kind=device_kind)
    devices = dict(_table.current_table().devices)
    if device_kind:
        devices[device_kind] = {
            "gates": overrides,
            "git_rev": stamp.get("git_rev"),
            "notes": notes,
        }
    artifact = dict(stamp)
    artifact["calib_schema"] = _table.SCHEMA_VERSION
    for gate, by_width in measured.items():
        for width, arms in by_width.items():
            for arm, w in arms.items():
                artifact[f"calib_ms_{gate}_{arm}_f{width}"] = w["ms"]
                artifact[f"calib_spread_{gate}_{arm}_f{width}"] = w["spread"]
    artifact["calibration"] = {"overrides": overrides, "notes": notes}
    return devices, artifact


def check_calib(device_kind: Optional[str] = None,
                rows: Optional[int] = None, quiet: bool = True) -> dict:
    """Diff a live sweep against the committed table: for every gate the
    sweep can derive, the committed table's resolution at each tested
    shape must match the live-derived table's (suspect captures are
    reported but never fail — bench_trend's verdict discipline)."""
    from dryad_tpu.policy.device import current_device_kind

    if device_kind is None:
        device_kind = current_device_kind()
    measured = run_sweep(rows=rows, quiet=quiet)
    overrides, notes = derive_overrides(measured)
    committed = _table.current_table()
    live = _table.CalibrationTable(
        devices={**committed.devices,
                 device_kind or "_live": {"gates": overrides}},
        source="<live sweep>")
    report: dict = {"ok": True, "device_kind": device_kind,
                    "notes": notes, "gates": {}}
    for job in SWEEP:
        gate = job["gate"]
        if job["derive"] is None or gate not in measured:
            continue
        suspect = any(_suspect(w) for w in measured[gate].values())
        diffs = []
        for width in job["widths"]:
            feats = _features_at(gate, width)
            want = _gates.resolve(gate, feats, device_kind=device_kind,
                                  table=committed)
            got = _gates.resolve(gate, feats,
                                 device_kind=device_kind or "_live",
                                 table=live)
            if want != got:
                diffs.append({"width": width, "committed": want,
                              "live": got})
        verdict = ("suspect" if (diffs and suspect)
                   else "drift" if diffs else "ok")
        report["gates"][gate] = {"verdict": verdict, "diffs": diffs}
        if verdict == "drift":
            report["ok"] = False
    return report


def _features_at(gate: str, width: int) -> dict:
    """The resolve() features a sweep shape exercises (u8, 256 bins)."""
    if gate == "partition":
        return {"num_features": width, "itemsize": 1}
    if gate == "hist_reduce":
        return {"num_features": width, "total_bins": _SWEEP_BINS,
                "n_shards": 8}
    if gate == "predict_layout":
        return {"fits": True}
    raise KeyError(gate)


# ---------------------------------------------------------------------------
# selftest (the ci.sh gate: CPU, seeded, no probes)

#: every gate's oracle sweep: (features, pre-PR-constant arm).  The
#: expected arms are the HAND-TUNED semantics spelled out, independent
#: of GATE_DEFAULTS — this is the parity anchor, not a tautology.
PARITY_CASES: dict = {
    "partition": [
        ({"num_features": 4096, "itemsize": 1}, "reduce"),
        ({"num_features": 4097, "itemsize": 1}, "gather"),
        ({"num_features": 2048, "itemsize": 2}, "reduce"),
        ({"num_features": 2049, "itemsize": 2}, "gather"),
        ({"num_features": 28, "itemsize": 1}, "reduce"),
        ({"num_features": 2000, "itemsize": 1}, "reduce"),
        ({"num_features": 2000, "itemsize": 2}, "reduce"),
        ({"num_features": 2000, "itemsize": 4}, "gather"),
    ],
    "hist_reduce": [
        ({"num_features": 28, "total_bins": 256, "n_shards": 1}, "fused"),
        ({"num_features": 28, "total_bins": 256, "n_shards": 8}, "fused"),
        ({"num_features": 1023, "total_bins": 256, "n_shards": 2}, "fused"),
        ({"num_features": 1024, "total_bins": 256, "n_shards": 2},
         "feature"),
        ({"num_features": 1024, "total_bins": 256, "n_shards": 1}, "fused"),
        ({"num_features": 2000, "total_bins": 256, "n_shards": 8},
         "feature"),
        ({"num_features": 256, "total_bins": 512, "n_shards": 2},
         "feature"),
        ({"num_features": 255, "total_bins": 512, "n_shards": 2}, "fused"),
    ],
    "hist_backend": [
        ({"platform": "cpu"}, "xla"),
        ({"platform": "tpu"}, "pallas"),
        ({"platform": "gpu"}, "xla"),
    ],
    "deep_layout": [
        ({"num_leaves": 512, "record_bytes": 128}, "layout"),
        ({"num_leaves": 513, "record_bytes": 128}, "legacy"),
        ({"num_leaves": 512, "record_bytes": 129}, "legacy"),
        ({"num_leaves": 31, "record_bytes": 37}, "layout"),
    ],
    "leafwise_layout": [
        ({"max_depth": 12}, "layout"),
        ({"max_depth": 13}, "legacy"),
        ({"max_depth": 1}, "layout"),
        ({"max_depth": 0}, "legacy"),
    ],
    "predict_layout": [
        ({"fits": True}, "packed"),
        ({"fits": False}, "legacy"),
    ],
    "predict_sharded": [
        ({"work": 32767}, "single"),
        ({"work": 32768}, "sharded"),
        ({"work": 1}, "single"),
    ],
    "chunk_cap": [
        ({}, "8/4/2"),
    ],
}

#: per-gate perturbation for the flip test: (override entry, the case
#: index in PARITY_CASES whose arm must flip under it)
_PERTURBATIONS: dict = {
    "partition": ({"reduce_max_row_bytes": 0}, 0),
    "hist_reduce": ({"wide_bytes": 1}, 1),
    "hist_backend": ({"pallas_platforms": []}, 1),
    "deep_layout": ({"max_leaves": 256}, 0),
    "leafwise_layout": ({"max_segments": 512}, 0),
    "predict_layout": ({"preferred": "legacy"}, 0),
    "predict_sharded": ({"min_work": 1}, 0),
    "chunk_cap": ({"ladder": [2]}, 0),
}

_SELFTEST_KIND = "calib-selftest-device"


def _resolve_all(table: _table.CalibrationTable, device_kind) -> dict:
    """Every parity case's arm under one table: {(gate, idx): arm}."""
    return {(g, i): _gates.resolve(g, feats, device_kind=device_kind,
                                   table=table)
            for g, cases in PARITY_CASES.items()
            for i, (feats, _want) in enumerate(cases)}


def run_selftest(quiet: bool = False) -> int:
    """The ci.sh gate; returns a process exit code."""
    import tempfile

    failures: list[str] = []

    # 1. the committed golden must load clean and equal the code defaults
    golden = _table.load_table(_table.GOLDEN_PATH, explicit=False)
    if golden.fallback_reason:
        failures.append(f"committed golden unusable: "
                        f"{golden.fallback_reason}")
    elif golden.devices.get(_table.DEFAULT_DEVICE_KEY, {}).get("gates") \
            != _table.GATE_DEFAULTS:
        failures.append("committed golden _default drifted from "
                        "table.GATE_DEFAULTS — recommit calibration.json")

    # 2. default-table parity: every gate == the pre-PR constants
    for gate, cases in PARITY_CASES.items():
        for feats, want in cases:
            got = _gates.resolve(gate, feats, device_kind=None,
                                 table=golden)
            if got != want:
                failures.append(
                    f"default parity: {gate} {feats} -> {got}, "
                    f"pre-PR constant says {want}")

    # 3. a perturbed entry flips EXACTLY the intended gate
    base = _resolve_all(golden, _SELFTEST_KIND)
    for gate, (override, flip_idx) in _PERTURBATIONS.items():
        perturbed = _table.CalibrationTable(
            devices={**golden.devices,
                     _SELFTEST_KIND: {"gates": {gate: override}}},
            source="<selftest>")
        got = _resolve_all(perturbed, _SELFTEST_KIND)
        flipped = {k for k in base if base[k] != got[k]}
        if (gate, flip_idx) not in flipped:
            failures.append(f"perturbing {gate} {override} did not flip "
                            f"its target case {flip_idx}")
        stray = {k for k in flipped if k[0] != gate}
        if stray:
            failures.append(f"perturbing {gate} leaked into {sorted(stray)}")

    # 4. save/load round-trip preserves every resolution bitwise
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        path = f.name
    try:
        devices = {**golden.devices,
                   _SELFTEST_KIND: {"gates": {"partition":
                                              {"reduce_max_row_bytes": 64}},
                                    "git_rev": "deadbeef"}}
        _table.save_table(devices, path)
        loaded = _table.load_table(path)
        if loaded.fallback_reason:
            failures.append(f"round-trip reload failed: "
                            f"{loaded.fallback_reason}")
        elif loaded.devices != devices:
            failures.append("round-trip devices dict drifted")
        else:
            before = _resolve_all(
                _table.CalibrationTable(devices=devices), _SELFTEST_KIND)
            after = _resolve_all(loaded, _SELFTEST_KIND)
            if before != after:
                failures.append("round-trip resolutions drifted")
    finally:
        import os as _os

        _os.unlink(path)

    # 5. the derive rules on seeded walls (no probes)
    seeded = {
        "partition": {512: {"reduce": {"ms": 1.0, "spread": 0.0},
                            "gather": {"ms": 2.0, "spread": 0.0}},
                      4096: {"reduce": {"ms": 1.0, "spread": 0.0},
                             "gather": {"ms": 1.5, "spread": 0.0}},
                      8192: {"reduce": {"ms": 3.0, "spread": 0.0},
                             "gather": {"ms": 1.0, "spread": 0.0}}},
        "hist_reduce": {128: {"fused": {"ms": 1.0, "spread": 0.0},
                              "feature": {"ms": 2.0, "spread": 0.0}},
                        1024: {"fused": {"ms": 3.0, "spread": 0.0},
                               "feature": {"ms": 2.0, "spread": 0.0}},
                        2000: {"fused": {"ms": 5.0, "spread": 0.0},
                               "feature": {"ms": 2.0, "spread": 0.0}}},
        "predict_layout": {28: {"packed": {"ms": 1.0, "spread": 0.0},
                                "legacy": {"ms": 2.0, "spread": 0.0}}},
        "hist_backend": {28: {"masked": {"ms": 1.0, "spread": 0.0},
                              "segmented": {"ms": 1.0, "spread": 0.0}}},
        "suspect_gate_check": {},
    }
    seeded.pop("suspect_gate_check")
    ov, _notes = derive_overrides(seeded)
    if ov.get("partition") != {"reduce_max_row_bytes": 4096}:
        failures.append(f"derive partition: {ov.get('partition')}")
    if ov.get("hist_reduce") != {"wide_bytes": 1024 * 256}:
        failures.append(f"derive hist_reduce: {ov.get('hist_reduce')}")
    if ov.get("predict_layout") != {"preferred": "packed"}:
        failures.append(f"derive predict_layout: {ov.get('predict_layout')}")
    # the spread veto must keep the committed value
    seeded["partition"][512]["reduce"]["spread"] = 0.5
    ov2, notes2 = derive_overrides(seeded)
    if "partition" in ov2 or "suspect" not in notes2.get("partition", ""):
        failures.append("spread veto failed to hold the partition gate")

    for msg in failures:
        print(f"CALIB SELFTEST FAIL: {msg}")
    if not failures and not quiet:
        n = sum(len(c) for c in PARITY_CASES.values())
        print(f"CALIB SELFTEST OK: {n} parity cases pre-PR-identical, "
              f"{len(_PERTURBATIONS)} single-gate flips exact, "
              "round-trip + derive rules + spread veto green")
    return 1 if failures else 0
