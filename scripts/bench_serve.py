"""Closed-loop serving benchmark CLI (engine: dryad_tpu/serve/bench.py).

    PYTHONPATH=. python scripts/bench_serve.py \
        [--model m.dryad] [--backend auto|tpu|cpu] [--clients 8] \
        [--duration 5] [--arms 2] [--max-batch-rows 256] [--max-wait-ms 1.0] \
        [--sizes 1,3,9,17,40] [--pipeline-depth 2] [--compare] [--sharded] \
        [--smoke] [--json report.json]

Without --model it trains a small throwaway booster first.  The last
stdout line is ONE flat JSON summary (bench.py's format) with rows/s,
p50/p99, batch fill, recompile count, and the per-arm spread —
``suspect_capture`` flags spread > 5% per CLAUDE.md.

Arms:
  --compare   pipeline-vs-serial A/B (records ``pipeline_speedup``;
              ISSUE r7 acceptance wants ≥ 1.3× on CPU)
  --sharded   adds a forced-sharded arm (backend tpu, every bucket on the
              mesh — on CPU CI this is the 8 fake devices)
  --smoke     short CI mode: tiny model, short loops, exit 1 unless BOTH
              the bucketed and sharded arms report zero recompiles after
              warmup (scripts/ci.sh runs this)
  --drift     drift-monitor overhead A/B (r18): the same closed loop with
              the model-drift monitor on vs off —
              ``drift_overhead_ms/_pct/_spread`` (obs/trends.py tracks
              them); exit 1 when the cost exceeds 2% and the spread does
              not veto the capture
  --layout    packed-vs-legacy predict traversal layout A/B (r21): the
              same closed loop with ``predict_layout`` forced to packed
              (one node-word table gather per level) vs legacy (~7) on
              the jax backend — ``layout_rows_per_s_packed/_legacy`` +
              ``predict_layout_speedup`` (obs/trends.py tracks them);
              recompiles in either arm fail the run
  --fleet     closed-loop fleet arm (r14, dryad_tpu/fleet/bench.py): REAL
              subprocess replicas behind the router at N=1/2/4
              (``fleet_rows_per_s_nN`` + spreads + ``fleet_scaling_nN``)
              plus a rolling-swap drill under load (``fleet_swap_*``;
              zero failed requests is the acceptance bar).  r17: every
              request carries an ``X-Dryad-Trace`` id (non-echoing
              responses fail the arm) and the report records per-priority
              latency percentiles from the router's mergeable histograms
              (``fleet_<priority>_p{50,95,99}_ms_nN`` — the ROADMAP's
              "p99 budgets per priority class, not just rows/s";
              obs/trends.py tracks them like bench walls).  Standalone
              mode: the in-process arms are skipped.

Acceptance gate: a forced-CPU run must report
``recompiles_after_warmup: 0`` — the shape-bucketed cache makes warm
traffic structurally recompile-free (bench warms every reachable bucket
before measuring, and shard-arm routing is deterministic per bucket).
"""

from __future__ import annotations

import argparse
import json
import sys


def _train_throwaway(n_rows: int = 4000, num_trees: int = 50):
    import dryad_tpu as dryad
    from dryad_tpu.datasets import higgs_like

    X, y = higgs_like(n_rows, seed=11)
    ds = dryad.Dataset(X, y, max_bins=64)
    return dryad.train(dict(objective="binary", num_trees=num_trees,
                            num_leaves=31, max_bins=64), ds, backend="cpu")


def run_fleet_arm(args) -> int:
    """The r14 fleet arm: spawn real serve replicas (they pay the jax
    import; this process only drives HTTP), measure scaling + the
    rolling-swap drill, stamp, and print the bench.py-format summary."""
    import os
    import tempfile

    from dryad_tpu.fleet.bench import run_fleet_bench
    from dryad_tpu.obs.trends import artifact_stamp

    tmpdir = None
    if args.model:
        model_path = args.model
        from dryad_tpu.booster import Booster

        booster = Booster.load_any(model_path)
    else:
        booster = _train_throwaway(n_rows=1500 if args.smoke else 4000,
                                   num_trees=20 if args.smoke else 50)
        tmpdir = tempfile.TemporaryDirectory(prefix="dryad-fleet-bench-")
        model_path = os.path.join(tmpdir.name, "model.dryad")
        booster.save(model_path)
    mapper = booster.mapper
    num_features = getattr(mapper, "base", mapper).num_features

    sizes = [int(s) for s in (args.sizes or "1,3,9,17").split(",")]
    duration = args.duration if args.duration is not None else 2.0
    replicas = tuple(int(n) for n in args.fleet_replicas.split(","))
    if args.smoke:
        duration, replicas = min(duration, 1.0), (1, 2)
    try:
        report = run_fleet_bench(
            model_path, num_features, backend=args.backend,
            replica_counts=replicas, clients=args.clients,
            duration_s=duration, sizes=sizes, arms=args.arms,
            seed=args.seed,
            max_batch_rows=args.max_batch_rows or 256,
            max_wait_ms=args.max_wait_ms or 1.0,
            swap_replicas=min(2, max(replicas)), verbose=not args.smoke)
    finally:
        if tmpdir is not None:
            tmpdir.cleanup()
    report.update(artifact_stamp(device_kind=None))

    print(json.dumps(report, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
    if report.get("suspect_capture"):
        print("WARNING: per-arm spread > 5% — suspect capture (CLAUDE.md)",
              file=sys.stderr)
    # the one-line summary is the LAST stdout line (bench.py's format)
    print(json.dumps(report))
    failed = report.get("fleet_swap_failed", 0) + sum(
        v for k, v in report.items() if k.startswith("fleet_failures_n"))
    if failed:
        print(f"ERROR: {failed} failed fleet request(s) — the zero-drop "
              "contract is broken", file=sys.stderr)
        return 1
    mismatches = sum(v for k, v in report.items()
                     if k.startswith("fleet_trace_mismatches_n"))
    if mismatches:
        print(f"ERROR: {mismatches} response(s) did not echo their "
              "X-Dryad-Trace id — trace propagation is broken",
              file=sys.stderr)
        return 1
    if report.get("fleet_swap_versions_seen", 2) < 2:
        print("ERROR: the swap drill never observed both versions — the "
              "push did not happen under load", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench_serve")
    ap.add_argument("--model", help="model path; trains a throwaway if absent")
    ap.add_argument("--backend", default="cpu",
                    choices=["auto", "tpu", "cpu"])
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--duration", type=float, default=None)
    ap.add_argument("--arms", type=int, default=2,
                    help="measured-loop repetitions (per-arm spread)")
    ap.add_argument("--max-batch-rows", type=int, default=None)
    ap.add_argument("--max-wait-ms", type=float, default=None)
    ap.add_argument("--sizes", default=None,
                    help="comma-separated request row sizes")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="overlapped dispatch run-ahead (1 = serial loop)")
    ap.add_argument("--compare", action="store_true",
                    help="pipeline-vs-serial A/B (pipeline_speedup)")
    ap.add_argument("--sharded", action="store_true",
                    help="add a forced-sharded arm (backend tpu over the "
                         "mesh; CI runs it on the 8 fake CPU devices)")
    ap.add_argument("--smoke", action="store_true",
                    help="short CI mode: bucketed + sharded arms, exit 1 "
                         "on any recompile after warmup")
    ap.add_argument("--drift", action="store_true",
                    help="drift-monitor overhead A/B (instrumented vs "
                         "disabled; drift_overhead_ms/_pct/_spread, exit 1 "
                         "over the 2% budget unless the spread vetoes)")
    ap.add_argument("--layout", action="store_true",
                    help="packed-vs-legacy predict layout A/B on the jax "
                         "backend (layout_rows_per_s_packed/_legacy + "
                         "predict_layout_speedup; exit 1 on any recompile "
                         "after warmup in either arm)")
    ap.add_argument("--fleet", action="store_true",
                    help="closed-loop fleet arm: real subprocess replicas "
                         "at N=1/2/4 + a rolling-swap drill (standalone; "
                         "exit 1 on any failed swap-drill request)")
    ap.add_argument("--fleet-replicas", default="1,2,4",
                    help="comma-separated fleet sizes for the scaling arm; "
                         "with a device --backend replica i is given chip "
                         "i, so a size past the host's chip count fails "
                         "that replica's start-up (it never serves from "
                         "the CPU instead)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", help="also write the report here")
    args = ap.parse_args(argv)

    if args.fleet:
        return run_fleet_arm(args)

    from dryad_tpu.serve.bench import run_bench, run_bench_compare, summary_line

    # --compare measures the BULK-scoring regime (the north star's "giant
    # batches"): pow2-aligned requests big enough that both pipeline
    # stages are dominated by GIL-releasing native/XLA work — that is
    # where host/device overlap is physical rather than GIL-interleaved.
    # Interactive-sized defaults otherwise.
    if args.sizes is None:
        args.sizes = "2048,4096" if args.compare else "1,3,9,17,40"
    if args.max_batch_rows is None:
        args.max_batch_rows = 4096 if args.compare else 256
    if args.max_wait_ms is None:
        args.max_wait_ms = 0.5 if args.compare else 1.0
    if args.duration is None:
        args.duration = 2.0 if args.compare else 5.0
    if args.smoke:
        args.duration = min(args.duration, 0.5)
        args.arms = 1
        args.clients = min(args.clients, 4)
    model = args.model if args.model else _train_throwaway(
        n_rows=1500 if args.smoke else 4000,
        num_trees=20 if args.smoke else 50)
    kw = dict(clients=args.clients, duration_s=args.duration,
              sizes=[int(s) for s in args.sizes.split(",")],
              max_batch_rows=args.max_batch_rows,
              max_wait_ms=args.max_wait_ms, seed=args.seed, arms=args.arms,
              verbose=not args.smoke)

    report: dict
    if args.compare:
        report = run_bench_compare(model, backend=args.backend,
                                   pipeline_depth=args.pipeline_depth, **kw)
        summary = summary_line(report["pipeline"], "serve_pipeline")
        summary["serial_rows_per_s"] = round(report["serial"]["rows_per_s"], 1)
        summary["pipeline_speedup"] = report["pipeline_speedup"]
        summary["suspect_capture"] = report["suspect_capture"]
        # the exit gate must cover BOTH arms — a serial-only recompile
        # regression would otherwise pass --compare runs silently
        summary["recompiles_after_warmup"] = report["recompiles_after_warmup"]
    else:
        report = run_bench(model, backend=args.backend,
                           pipeline_depth=args.pipeline_depth, **kw)
        summary = summary_line(report, "serve")

    if args.drift:
        # r18 drift-monitor overhead A/B (instrumented vs disabled, the
        # obs_overhead_ms shape); obs/trends.py tracks the fields with
        # the spread veto, and the <= 2% gate fails the run below
        from dryad_tpu.serve.bench import run_bench_drift

        drift = run_bench_drift(model, backend=args.backend,
                                pipeline_depth=args.pipeline_depth, **kw)
        drift.pop("drift_windows", None)
        report["drift_overhead"] = drift
        summary.update({k: v for k, v in drift.items()
                        if k.startswith("drift_overhead")})

    if args.layout:
        # r21 packed-vs-legacy traversal layout A/B: always on the jax
        # backend ('tpu'; the 8 fake CPU devices in CI) — the cpu predict
        # path never stages device tables, so it has no layout to compare
        from dryad_tpu.serve.bench import run_bench_layout

        layout = run_bench_layout(model,
                                  pipeline_depth=args.pipeline_depth, **kw)
        report["layout"] = layout
        summary.update({k: v for k, v in layout.items()
                        if k.startswith(("layout_", "predict_layout"))})
        summary["suspect_capture"] = (summary.get("suspect_capture", False)
                                      or layout["suspect_capture"])

    if args.sharded:
        # forced-sharded arm: every bucket takes the shard_map family
        sharded_report = run_bench(model, backend="tpu", sharded=True,
                                   pipeline_depth=args.pipeline_depth, **kw)
        if args.smoke and sharded_report["mesh_shards"] <= 1:
            # a 1-device mesh silently degrades this arm to a duplicate
            # single-device check — the CI gate must not pass on that
            print("ERROR: sharded smoke got a 1-device mesh (set "
                  "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
                  file=sys.stderr)
            return 1
        report = {"bucketed": report, "sharded": sharded_report}
        summary["sharded_rows_per_s"] = round(
            sharded_report["rows_per_s"], 1)
        summary["sharded_recompiles_after_warmup"] = (
            sharded_report["recompiles_after_warmup"])
        summary["mesh_shards"] = sharded_report["mesh_shards"]

    # artifact stamp (r12): schema_version + git rev + device kind ride
    # both the full report and the one-line summary so the trend ledger
    # (dryad_tpu/obs/trends.py) keys serve history off data, not filenames
    from dryad_tpu.obs.trends import artifact_stamp

    # r23: device_kind rides the stamp's "auto" default — the ONE
    # derivation (policy/device.py), best-effort like the old inline probe
    stamp = artifact_stamp()
    report.update(stamp)
    summary.update(stamp)

    print(json.dumps(report, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
    if summary.get("suspect_capture"):
        print("WARNING: per-arm spread > 5% — suspect capture (CLAUDE.md)",
              file=sys.stderr)
    # the one-line summary is the LAST stdout line (bench.py's format)
    print(json.dumps(summary))

    recompiles = summary.get("recompiles_after_warmup", 0)
    recompiles += summary.get("sharded_recompiles_after_warmup", 0)
    recompiles += summary.get("layout_recompiles_after_warmup", 0)
    if recompiles != 0:
        print("WARNING: cache recompiled after warmup", file=sys.stderr)
        return 1
    # drift-overhead gate (<= 2%), with the standard spread veto: a
    # noisy capture is "suspect", never a verdict (CLAUDE.md)
    pct = summary.get("drift_overhead_pct")
    if pct is not None and pct > 0.02:
        if summary.get("drift_overhead_spread", 0.0) > 0.05:
            print("WARNING: drift overhead gate skipped — per-arm spread "
                  "> 5% (suspect capture)", file=sys.stderr)
        else:
            print(f"ERROR: drift monitoring costs {pct:.1%} rows/s — over "
                  "the 2% budget", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
