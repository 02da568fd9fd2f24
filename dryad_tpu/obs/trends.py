"""Bench trend ledger: the committed ``BENCH_r*.json`` history as data.

The standing instruction — "bench.py trends, not points: acceptance
walls are cold single runs" — has had no machinery behind it: the per-round artifacts exist, but nothing compares
them.  This module ingests the committed history, compares the newest
point against the history median with a spread-aware tolerance, and emits
a machine-readable regression report (``scripts/bench_trend.py`` runs it
in ci.sh; the ``/stats`` endpoint can mount it as an extra provider).

Verdict rules (the CLAUDE.md measuring discipline, applied across
rounds instead of within a run):

* a metric regresses only against the MEDIAN of the prior rounds that
  recorded it (a single noisy round can neither fake nor mask a trend);
* the newest point's own per-arm spread fields are consulted first: a
  spread > 5% (``SPREAD_SUSPECT``) marks the verdict ``suspect`` —
  "suspect capture, never a regression verdict";
* the tolerance is deliberately loose (default 15%): cold single runs
  wobble, and the ledger is a tripwire for real cliffs, not a 1% gate.

Artifact stamps (r12 satellite): ``bench.py``/``scripts/bench_serve.py``
write ``schema_version``, ``git_rev`` and ``device_kind`` into their JSON
so history keys off data, not filenames; the reader stays
backfill-tolerant for the unstamped r1–r7 files (driver wrapper shape
``{"n", "cmd", "rc", "tail", "parsed": {...}}`` or bench.py's flat line).

Pure stdlib (json/glob/statistics) — the obs package is jax-free by lint.
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
from typing import Optional, Sequence

from dryad_tpu.obs.registry import Registry, default_registry

#: per-arm spread above this flags the capture (CLAUDE.md / serve bench)
SPREAD_SUSPECT = 0.05
#: relative regression tolerance vs the history median (trends, not points)
DEFAULT_TOLERANCE = 0.15
#: current bench artifact schema (the r12 stamping satellite)
SCHEMA_VERSION = 1

#: the r13 stage-profiler artifacts (obs/profiler.py writes them); every
#: ``stage_ms_<name>`` field is lower-better with its spread riding in
#: the sibling ``stage_spread_<name>`` — prefix rules, so new stage
#: probes are trend-tracked with no table edit here
PROFILE_PATTERN = "PROFILE_r*.json"
STAGE_MS_PREFIX = "stage_ms_"
STAGE_SPREAD_PREFIX = "stage_spread_"

#: r17 fleet-bench per-priority latency percentiles
#: (``fleet_<priority>_p{50,95,99}_ms_n<replicas>``) — pattern rule like
#: the stage profiler's, so new priorities/fleet sizes are tracked with
#: no table edit; lower is better, vouched by that fleet size's spread
_FLEET_PCT_RE = re.compile(r"^fleet_[a-z]+_p\d+_ms_(n\d+)$")

#: metric direction tables — anything in neither set is context, not a
#: tracked metric (row counts, spreads, tree counts, the stamps)
HIGHER_BETTER = frozenset({
    "value", "vs_baseline", "final_train_auc", "iters_per_sec_10m",
    "rows_per_s", "requests_per_s", "pipeline_speedup",
    # r14 fleet arm (scripts/bench_serve.py --fleet): closed-loop rows/s
    # through the router at N replicas, and the N-vs-1 scaling ratios
    "fleet_rows_per_s_n1", "fleet_rows_per_s_n2", "fleet_rows_per_s_n4",
    "fleet_scaling_n2", "fleet_scaling_n4",
    # r20 out-of-core training (scripts/stream_rss_probe.py): streamed
    # CPU train throughput
    "stream_train_rows_per_s",
    # r21 packed-vs-legacy serve layout A/B (scripts/bench_serve.py
    # --layout): closed-loop rows/s per traversal layout + their ratio
    "layout_rows_per_s_packed", "layout_rows_per_s_legacy",
    "predict_layout_speedup",
})
LOWER_BETTER = frozenset({
    "marginal_s_per_iter_10m", "wall_2tree_10m", "wall_8tree_10m",
    "deep_level_ms_wired", "deep_level_ms_legacy",
    "leafwise_level_ms_wired", "leafwise_level_ms_legacy",
    # r16 wide-shape histogram-reduction arms (bench.py hist_reduce_probe)
    "hist_reduce_ms_fused", "hist_reduce_ms_feature",
    "supervisor_overhead_ms", "obs_overhead_ms", "obs_overhead_pct",
    # r18 drift-monitor overhead (scripts/bench_serve.py --drift:
    # instrumented-vs-disabled serve arms, gate <= 2% like obs_overhead)
    "drift_overhead_ms", "drift_overhead_pct",
    # r20 streamed-vs-resident train overhead and the RSS proof peak
    "stream_overhead_pct", "stream_rss_peak_mb",
    # r21 per-layout predict traversal walls (bench.py
    # predict_layout_probe: one node-word table gather/level vs ~7)
    "predict_us_per_row_packed", "predict_us_per_row_legacy",
    # r22 elastic capacity (scripts/smoke_fleet.py ramp drill summary):
    # capacity actions and peak replica count a FIXED stepped ramp needs
    # to stay unshed — a stabler controller (or faster replicas) holds
    # the same load with fewer actions and a smaller pool
    "fleet_scale_up_total", "fleet_scale_down_total", "fleet_replicas",
    "p50_ms", "p99_ms",
})

#: metric -> the newest point's spread fields that vouch for it; the 10M
#: marginal is a (8-tree − 2-tree) difference, so BOTH arm spreads apply
_SPREAD_FIELDS = {
    "iters_per_sec_10m": ("spread_2tree_10m", "spread_8tree_10m"),
    "marginal_s_per_iter_10m": ("spread_2tree_10m", "spread_8tree_10m"),
    "wall_2tree_10m": ("spread_2tree_10m",),
    "wall_8tree_10m": ("spread_8tree_10m",),
    "deep_level_ms_wired": ("deep_level_spread_wired",),
    "deep_level_ms_legacy": ("deep_level_spread_legacy",),
    "leafwise_level_ms_wired": ("leafwise_level_spread_wired",),
    "leafwise_level_ms_legacy": ("leafwise_level_spread_legacy",),
    "hist_reduce_ms_fused": ("hist_reduce_spread_fused",),
    "hist_reduce_ms_feature": ("hist_reduce_spread_feature",),
    "supervisor_overhead_ms": ("supervisor_overhead_spread",),
    "obs_overhead_ms": ("obs_overhead_spread",),
    "obs_overhead_pct": ("obs_overhead_spread",),
    "drift_overhead_ms": ("drift_overhead_spread",),
    "drift_overhead_pct": ("drift_overhead_spread",),
    "stream_train_rows_per_s": ("stream_overhead_spread",),
    "stream_overhead_pct": ("stream_overhead_spread",),
    "predict_us_per_row_packed": ("predict_spread_packed",),
    "predict_us_per_row_legacy": ("predict_spread_legacy",),
    "layout_rows_per_s_packed": ("layout_spread_packed",),
    "layout_rows_per_s_legacy": ("layout_spread_legacy",),
    "predict_layout_speedup": ("layout_spread_packed",
                               "layout_spread_legacy"),
    "rows_per_s": ("spread_rows_per_s",),
    "fleet_rows_per_s_n1": ("fleet_spread_n1",),
    "fleet_rows_per_s_n2": ("fleet_spread_n2",),
    "fleet_rows_per_s_n4": ("fleet_spread_n4",),
    # the ratios inherit both arms' capture quality
    "fleet_scaling_n2": ("fleet_spread_n1", "fleet_spread_n2"),
    "fleet_scaling_n4": ("fleet_spread_n1", "fleet_spread_n4"),
}

_ROUND_RE = re.compile(r"_r0*(\d+)\.json$")


def _direction(name: str) -> Optional[str]:
    """Tracked-metric direction, or None for context fields.  Exact
    tables first, then the stage-profiler and fleet-percentile pattern
    rules."""
    if name in HIGHER_BETTER:
        return "higher_better"
    if (name in LOWER_BETTER or name.startswith(STAGE_MS_PREFIX)
            or _FLEET_PCT_RE.match(name)):
        return "lower_better"
    return None


def _spread_fields_of(name: str) -> tuple:
    """The newest point's spread fields vouching for ``name``."""
    if name.startswith(STAGE_MS_PREFIX):
        return (STAGE_SPREAD_PREFIX + name[len(STAGE_MS_PREFIX):],)
    m = _FLEET_PCT_RE.match(name)
    if m:
        # percentile capture quality rides that fleet size's arm spread
        return (f"fleet_spread_{m.group(1)}",)
    return _SPREAD_FIELDS.get(name, ())


def _extract_metrics(doc: dict) -> Optional[dict]:
    """The flat numeric-metrics dict out of one artifact, whatever its
    vintage: the driver wrapper carries ``parsed``; a bare bench.py line
    saved directly IS the dict (it has ``metric``/``bench``); a profile
    artifact carries ``profile_schema`` even when its stamp failed."""
    if isinstance(doc.get("parsed"), dict):
        return doc["parsed"]
    if ("metric" in doc or "bench" in doc or "schema_version" in doc
            or "profile_schema" in doc):
        return doc
    return None


def load_history(root: str = ".",
                 pattern: str = "BENCH_r*.json",
                 paths: Optional[Sequence[str]] = None) -> list[dict]:
    """Ordered bench points: ``{"round", "path", "metrics", "git_rev",
    "device_kind", "schema_version"}``.  Unstamped r1–r7 artifacts load
    with ``None`` stamps (backfill tolerance); unreadable or metric-less
    files are skipped, never fatal."""
    if paths is None:
        paths = sorted(glob.glob(os.path.join(root, pattern)))
    out = []
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        metrics = _extract_metrics(doc)
        if not metrics:
            continue
        m = _ROUND_RE.search(os.path.basename(path))
        rnd = int(m.group(1)) if m else doc.get("n")
        out.append({
            "round": rnd if isinstance(rnd, int) else None,
            "path": os.path.basename(path),
            "metrics": {k: v for k, v in metrics.items()
                        if isinstance(v, (int, float))
                        and not isinstance(v, bool)},
            "schema_version": metrics.get("schema_version"),
            "git_rev": metrics.get("git_rev") or doc.get("git_rev"),
            "device_kind": metrics.get("device_kind") or doc.get("device_kind"),
        })
    out.sort(key=lambda p: (p["round"] is None, p["round"]))
    return out


def compare(history: Sequence[dict],
            tolerance: float = DEFAULT_TOLERANCE) -> dict:
    """Newest point vs the median of its history, per tracked metric.

    Returns ``{"ok", "newest", "n_points", "metrics": {name: {value,
    median, n_history, rel_delta, direction, spread, verdict}}}`` where
    verdict is ``ok`` / ``improved`` / ``regression`` / ``suspect`` (the
    spread veto) / ``new`` (no history records the metric).  ``ok`` is
    False only on a ``regression``.
    """
    if len(history) < 1:
        return {"ok": True, "n_points": 0, "newest": None, "metrics": {}}
    newest = history[-1]
    prior = list(history[:-1])
    report: dict = {"ok": True, "n_points": len(history),
                    "newest": newest["path"], "metrics": {}}
    for name, value in sorted(newest["metrics"].items()):
        direction = _direction(name)
        if direction is None:
            continue
        hist_vals = [p["metrics"][name] for p in prior
                     if name in p["metrics"]]
        entry = {"value": value, "n_history": len(hist_vals),
                 "direction": direction}
        if not hist_vals:
            entry.update(median=None, rel_delta=None, verdict="new")
            report["metrics"][name] = entry
            continue
        med = statistics.median(hist_vals)
        entry["median"] = med
        rel = (value - med) / abs(med) if med else 0.0
        entry["rel_delta"] = round(rel, 4)
        worse = -rel if direction == "higher_better" else rel
        spread = max((newest["metrics"].get(f, 0.0)
                      for f in _spread_fields_of(name)), default=0.0)
        entry["spread"] = spread
        if worse > tolerance:
            if spread > SPREAD_SUSPECT:
                # suspect capture, never a regression verdict (CLAUDE.md)
                entry["verdict"] = "suspect"
            else:
                entry["verdict"] = "regression"
                report["ok"] = False
        elif worse < -tolerance:
            entry["verdict"] = "improved"
        else:
            entry["verdict"] = "ok"
        report["metrics"][name] = entry
    return report


def ingest(history: Sequence[dict],
           registry: Optional[Registry] = None) -> int:
    """Fold the history into registry series — one
    ``dryad_bench_value{metric=..., round=...}`` gauge point per tracked
    metric per round, plus ``dryad_bench_rounds`` — so scrapers see the
    whole trajectory on ``/metrics``.  Returns the number of series set.
    """
    reg = registry if registry is not None else default_registry()
    if not reg.enabled:
        return 0
    fam = reg.gauge("dryad_bench_value",
                    "Committed bench-history metric values by round")
    n = 0
    for point in history:
        rnd = point["round"] if point["round"] is not None else -1
        for name, value in point["metrics"].items():
            if _direction(name) is not None:
                fam.labels(metric=name, round=rnd).set(float(value))
                n += 1
    reg.gauge("dryad_bench_rounds",
              "Bench-history points loaded").set(len(history))
    return n


def artifact_stamp(device_kind: Optional[str] = "auto",
                   root: Optional[str] = None) -> dict:
    """The r12 bench-artifact stamp: ``schema_version`` + ``git_rev`` +
    ``device_kind``.  r23: the default ``"auto"`` resolves through the
    ONE derivation (``policy.device.current_device_kind`` — itself a
    lazy, best-effort jax probe, so this module stays jax-free by lint);
    pass an explicit kind, or explicit ``None`` for a deliberately
    unstamped artifact.  Keys the history off data instead of filenames;
    failures stamp ``None``, never raise (a bench must not die because
    git is absent)."""
    if device_kind == "auto":
        try:
            from dryad_tpu.policy.device import current_device_kind
            device_kind = current_device_kind()
        except Exception:  # noqa: BLE001 — the stamp is best-effort
            device_kind = None
    rev = None
    try:
        import subprocess

        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5,
            cwd=root or os.getcwd())
        rev = out.stdout.strip() or None
    except Exception:   # noqa: BLE001 — the stamp is best-effort
        rev = None
    return {"schema_version": SCHEMA_VERSION, "git_rev": rev,
            "device_kind": device_kind}


def stats_provider(root: str = ".", tolerance: float = DEFAULT_TOLERANCE):
    """An ``extra_stats`` provider for the /stats endpoint: loads the
    committed histories once (static for the life of a run) and serves
    the regression reports under ``bench_trends`` (always) and
    ``profile_trends`` (when any ``PROFILE_r*.json`` exists)."""
    cache: dict = {}

    def provide() -> dict:
        if "report" not in cache:
            history = load_history(root)
            cache["report"] = compare(history, tolerance) if history else {
                "ok": True, "n_points": 0, "newest": None, "metrics": {}}
            prof = load_history(root, pattern=PROFILE_PATTERN)
            cache["profile"] = compare(prof, tolerance) if prof else None
        out = {"bench_trends": cache["report"]}
        if cache["profile"] is not None:
            out["profile_trends"] = cache["profile"]
        return out

    return provide
