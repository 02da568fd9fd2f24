"""Command-line front end (SURVEY.md §5 config/flag system).

    python -m dryad_tpu train   --config params.json --data X.npy --label y.npy \
        [--valid Xv.npy --valid-label yv.npy] [--model out.dryad] \
        [--checkpoint-dir DIR --checkpoint-every N --resume] \
        [--supervise --journal run.jsonl --retry-budget N] \
        [--metrics-port N [--metrics-host H] [--auth-token T]] \
        [--log-jsonl metrics.jsonl] [--backend auto|tpu|cpu] [--quiet]
    python -m dryad_tpu predict --model m.dryad --data X.npy --out preds.npy [--raw]
    python -m dryad_tpu dump    --model m.dryad [--out model.json]
    python -m dryad_tpu profile [--selftest] [--stage NAME ...] [--rows N] \
        [--k K --reps R --slots P] [--out PROFILE.json] [--list]
    python -m dryad_tpu serve   --model m.dryad [--model fraud=m2.dryad ...] \
        [--host H --port P] [--backend auto|tpu|cpu] \
        [--max-batch-rows N --max-wait-ms F] [--pipeline-depth 2] \
        [--sharded auto|on|off] [--device-budget-mb M] [--log-requests] \
        [--auth-token T] [--port-file F]   # F gets 'host port' when ready \
        [--request X.npy --out p.npy]   # one-shot through the full stack
    python -m dryad_tpu fleet   --model m.dryad --replicas N [--port P] \
        [--journal fleet.jsonl --retry-budget N] [--warmup] \
        [--max-inflight N --bulk-max-inflight N] [--model-cap NAME=N] \
        [--auth-token T]   # supervised replica pool + health-routed router \
        [--continual-data fresh.npz [--retrain-trees K --probation-polls N]]
                           # r19: drift_breach -> warm-start retrain ->
                           # probationed rolling publish (+ auto-rollback)
    python -m dryad_tpu retrain --model m.dryad --data fresh.npz --out g1.dryad \
        [--trees K --refit-decay D --supervise] [--job-index J]
                           # the scheduler's warm-start append worker

Data formats: ``.npy`` (dense float matrix), ``.npz`` with keys
``indptr/indices/values/num_features`` (CSR sparse), or ``.csv``
(comma-separated, no header).  Params JSON accepts the same names/aliases as
``dryad.train`` (config.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def _load_matrix(path: str):
    """-> dense ndarray, or ('csr', (indptr, indices, values, num_features))."""
    if path.endswith(".npy"):
        return np.load(path)
    if path.endswith(".npz"):
        z = np.load(path)
        if "indptr" in z.files:
            return ("csr", (z["indptr"], z["indices"], z["values"],
                            int(z["num_features"])))
        return z[z.files[0]]
    if path.endswith(".csv"):
        return np.loadtxt(path, delimiter=",", dtype=np.float32, ndmin=2)
    raise SystemExit(f"unsupported data format: {path} (use .npy/.npz/.csv)")


def _load_vector(path: str) -> np.ndarray:
    return np.asarray(_load_matrix(path)).reshape(-1)


def _make_dataset(data_path, label_path, group_path, params, mapper=None):
    import dryad_tpu as dryad

    y = _load_vector(label_path) if label_path else None
    group = _load_vector(group_path).astype(np.int64) if group_path else None
    X = _load_matrix(data_path)
    kw = dict(
        weight=None, group=group,
        categorical_features=params.categorical_features if params else (),
        max_bins=params.max_bins if params else 256,
        mapper=mapper,
    )
    if isinstance(X, tuple) and X[0] == "csr":
        return dryad.Dataset(None, y, csr=X[1], **kw)
    return dryad.Dataset(X, y, **kw)


def cmd_train(args) -> int:
    import dryad_tpu as dryad
    from dryad_tpu.callbacks import JsonlLogger, log_evaluation
    from dryad_tpu.config import Params

    # pure-argument guards FIRST: a mis-flagged invocation must not pay
    # the full dataset load/bin (minutes at 10M rows) before the usage error
    if args.supervise and not args.checkpoint_dir:
        raise SystemExit("--supervise requires --checkpoint-dir "
                         "(resume is the recovery mechanism)")
    if args.supervise and not args.resume:
        # mid-run faults always auto-resume, but continuing a PRIOR
        # invocation's checkpoints must be explicit (--resume), exactly
        # like the unsupervised path — a stale dir under changed
        # params/data would silently yield a mixed model otherwise.
        from dryad_tpu.checkpoint import Checkpointer

        if Checkpointer.has_checkpoints(args.checkpoint_dir):
            raise SystemExit(
                f"--supervise found existing checkpoints in "
                f"{args.checkpoint_dir}; pass --resume to continue "
                "that run, or clear the directory to start fresh")
    if not args.supervise:
        if args.journal:
            raise SystemExit("--journal is the supervised-run journal; "
                             "it requires --supervise")
        if args.retry_budget is not None:
            raise SystemExit("--retry-budget configures the supervised "
                             "fault budget; it requires --supervise")

    params = Params.from_json(args.config) if args.config else dryad.Params()

    # live observability: mount the metrics endpoint BEFORE the (possibly
    # minutes-long) dataset load so /healthz answers for the whole run;
    # with --supervise --journal the journal is tailed into the registry
    # live, so fault/backoff/resume series appear on /stats as they happen
    exporter = tail = None
    # parse the hold up front: a malformed value must fail HERE, not inside
    # the finally block where it would mask a training error (and skip the
    # model save after a completed run)
    try:
        hold = float(os.environ.get("DRYAD_METRICS_HOLD_S", "0") or 0)
    except ValueError:
        raise SystemExit("DRYAD_METRICS_HOLD_S must be a number, got "
                         f"{os.environ['DRYAD_METRICS_HOLD_S']!r}")
    if args.metrics_port is not None:
        from dryad_tpu.obs import JournalTail, start_exporter
        from dryad_tpu.obs.trends import stats_provider

        # the bench trend ledger rides /stats (r12): when the cwd holds a
        # committed BENCH_r*.json history the report appears under
        # "bench_trends"; with no files it serves an empty ok report
        exporter = start_exporter(host=args.metrics_host,
                                  port=args.metrics_port,
                                  auth_token=args.auth_token,
                                  extra_stats=[stats_provider()])
        if not args.quiet:
            print(f"metrics on http://{exporter.host}:{exporter.port}  "
                  "(GET /stats, /metrics, /healthz)")
        if args.journal:
            tail = JournalTail(args.journal).start()

    trace_buf = None
    if args.trace_out:
        # capture the span tree live; the trace is written in the finally
        # below so a faulted run still leaves its timeline behind
        from dryad_tpu.obs import trace_export

        trace_buf = trace_export.enable_tracing()
        # the ring is process-wide: an in-process caller's SECOND train
        # run would otherwise write the first run's spans into its trace
        trace_buf.clear()

    logger = None
    # everything past exporter/tail startup runs under the finally that
    # stops them: an in-process caller (tests, smoke_obs) hitting a bad
    # --data path or a SystemExit validation below must not leak a bound
    # HTTP server and tail thread
    try:
        ds = _make_dataset(args.data, args.label, args.group, params)
        valid_sets = None
        if args.valid:
            if not args.valid_label:
                raise SystemExit("--valid requires --valid-label")
            vds = _make_dataset(args.valid, args.valid_label,
                                args.valid_group, params, mapper=ds.mapper)
            valid_sets = [vds]

        callbacks = []
        if not args.quiet:
            callbacks.append(log_evaluation(period=args.log_period))
        if args.log_jsonl:
            logger = JsonlLogger(args.log_jsonl)
            callbacks.append(logger)

        if args.supervise:
            # resilient long runs: classify device faults, degrade
            # chunking, auto-resume from checkpoints (dryad_tpu/resilience);
            # the stale-checkpoint --resume guard already ran up top
            from dryad_tpu.resilience import RetryPolicy, supervise_train

            policy = (RetryPolicy() if args.retry_budget is None
                      else RetryPolicy(retry_budget=args.retry_budget))
            booster = supervise_train(
                params, ds, valid_sets,
                backend=args.backend,
                policy=policy,
                checkpoint_dir=args.checkpoint_dir,
                checkpoint_every=args.checkpoint_every,
                journal=args.journal,
                callbacks=callbacks,
                profile_dir=args.profile_dir,
            )
        else:
            booster = dryad.train(
                params, ds, valid_sets,
                backend=args.backend,
                callbacks=callbacks,
                checkpoint_dir=args.checkpoint_dir,
                checkpoint_every=args.checkpoint_every,
                resume=args.resume,
                profile_dir=args.profile_dir,
            )
    finally:
        if trace_buf is not None:
            from dryad_tpu.obs import trace_export

            try:
                journal_events = ()
                if args.journal and os.path.exists(args.journal):
                    from dryad_tpu.resilience.journal import RunJournal

                    journal_events = RunJournal.read_last_run(args.journal)
                trace_export.write_trace(args.trace_out,
                                         span_events=trace_buf.events(),
                                         journal_events=journal_events)
                if not args.quiet:
                    print(f"wrote Chrome trace -> {args.trace_out}")
            except Exception as e:  # noqa: BLE001 — the trace is best-
                print(f"trace export failed: {e!r}",  # effort; never mask
                      file=sys.stderr)                # the training error
            finally:
                trace_export.disable_tracing()
        if logger is not None:
            logger.close()
        # DRYAD_METRICS_HOLD_S keeps the endpoint up briefly after the run
        # (smokes/tests scrape the final state through it; 0 = no hold)
        if exporter is not None and hold > 0:
            time.sleep(hold)
        if tail is not None:
            tail.stop()
        if exporter is not None:
            exporter.stop()
    if args.model:
        booster.save(args.model)
        if not args.quiet:
            print(f"saved {booster.num_iterations} iterations -> {args.model}")
    return 0


def cmd_profile(args) -> int:
    """Stage-level device profiler (engine/probes.py): liveness-proven
    timed-fori walls for the named hot-path stages, exported as
    ``dryad_stage_ms`` gauges and a stamped PROFILE artifact the trend
    ledger ingests.  ``--selftest`` is the ci.sh gate: the seeded
    dead-perturbation probe MUST be rejected and every shipped probe must
    pass liveness (CPU, seconds)."""
    from dryad_tpu.engine import probes

    if args.list:
        for name, probe in probes.PROBES.items():
            print(f"{name:20s} {probe.doc}")
        return 0
    if args.selftest:
        return probes.run_selftest(quiet=args.quiet)

    names = args.stage or list(probes.PROBES)
    unknown = [n for n in names if n not in probes.PROBES]
    if unknown:
        raise SystemExit(f"unknown stage(s): {unknown} "
                         f"(see --list)")
    results = []
    for name in names:
        r = probes.run_probe(name, rows=args.rows, K=args.k,
                             reps=args.reps, num_slots=args.slots)
        if not args.quiet:
            flag = "  SUSPECT CAPTURE" if (
                r["spread"] > probes.SPREAD_SUSPECT) else ""
            print(f"stage {name:20s} {r['ms']:10.2f} ms  "
                  f"spread {r['spread']:.3f}{flag}")
        results.append(r)

    from dryad_tpu.obs.profiler import export_stages, profile_artifact
    from dryad_tpu.obs.trends import PROFILE_PATTERN, compare, load_history

    export_stages(results)
    from dryad_tpu.policy.device import current_device_kind

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    art = profile_artifact(
        results, device_kind=current_device_kind(), root=root)
    print(json.dumps(art))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(art, f, indent=1)
            f.write("\n")
    if args.check_trend and not args.trend_root:
        raise SystemExit("--check-trend requires --trend-root (the "
                         "directory holding the PROFILE_r*.json history)")
    if args.trend_root:
        history = load_history(args.trend_root, pattern=PROFILE_PATTERN)
        if not history:
            # an empty/typo'd history must not turn a CI gate green
            msg = (f"no loadable PROFILE_r*.json under {args.trend_root!r}"
                   " — nothing to compare")
            if args.check_trend:
                raise SystemExit(msg)
            print(msg, file=sys.stderr)
        else:
            report = compare(history)
            print(json.dumps({"profile_trends": report}))
            if args.check_trend and not report["ok"]:
                return 1
    return 0


def cmd_predict(args) -> int:
    import dryad_tpu as dryad

    booster = dryad.Booster.load(args.model)
    X = _load_matrix(args.data)
    if isinstance(X, tuple) and X[0] == "csr":
        from dryad_tpu.data.binning import bin_csr

        indptr, indices, values, nf = X[1]
        Xb = bin_csr(indptr, indices, values, nf, booster.mapper)
        preds = booster.predict_binned(Xb, raw_score=args.raw,
                                       backend=args.backend)
    else:
        preds = booster.predict(np.asarray(X, np.float32), raw_score=args.raw,
                                backend=args.backend)
    np.save(args.out, preds)
    print(f"wrote predictions {preds.shape} -> {args.out}")
    return 0


def cmd_dump(args) -> int:
    import dryad_tpu as dryad

    booster = dryad.Booster.load(args.model)
    # --text emits the versioned round-trippable format (Booster.save_text
    # / load_text — bit-identical predict); the default dump_model() JSON
    # is a lighter inspection view without the mapper
    text = (booster.dump_text() if getattr(args, "text", False)
            else json.dumps(booster.dump_model(), indent=2))
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    else:
        print(text)
    return 0


def cmd_serve(args) -> int:
    from dryad_tpu.serve import PredictServer

    if args.request and not args.out:
        raise SystemExit("--request requires --out")
    server = PredictServer(
        backend=args.backend,
        max_batch_rows=args.max_batch_rows,
        max_wait_ms=args.max_wait_ms,
        queue_size=args.queue_size,
        pipeline_depth=args.pipeline_depth,
        sharded={"auto": "auto", "on": True, "off": False}[args.sharded],
        device_budget_bytes=(args.device_budget_mb * (1 << 20)
                             if args.device_budget_mb else None),
        drift="off" if args.drift_window == 0 else "auto",
        drift_window=args.drift_window,
    )
    import os.path

    for spec in args.model:
        # NAME=path registers a routing alias for multi-model co-serving;
        # a spec that exists on disk, or whose left-of-'=' part looks like
        # a path, is always a plain path (model paths may contain '=')
        name, path = None, spec
        if "=" in spec and not os.path.exists(spec):
            cand, _, rest = spec.partition("=")
            if cand and "/" not in cand and "\\" not in cand:
                name, path = cand, rest
        version = server.load_model(path, name=name)
        if not args.quiet:
            alias = f" (name {name!r})" if name else ""
            print(f"loaded {path} -> version {version}{alias}")

    if args.warmup:
        # compile every (version, bucket) program up front AND arm the
        # recompile tripwire: from here on an unexpected compile degrades
        # /healthz instead of silently stalling traffic (obs/tripwire.py)
        touched = server.warmup()
        if not args.quiet:
            print(f"warmed {touched} (version, bucket) programs; "
                  "recompile tripwire armed")

    if args.request:
        # one-shot mode: run a single request through the FULL serving
        # stack (bucketed compiled predict + micro-batcher) and exit —
        # a smoke/deployment check with no long-lived process
        X = _load_matrix(args.request)
        with server:
            if isinstance(X, tuple) and X[0] == "csr":
                from dryad_tpu.data.binning import bin_csr

                indptr, indices, values, nf = X[1]
                entry = server.registry.get()
                Xb = bin_csr(indptr, indices, values, nf, entry.booster.mapper)
                preds = server.predict(Xb, raw_score=args.raw, binned=True)
            else:
                preds = server.predict(np.asarray(X, np.float32),
                                       raw_score=args.raw)
        np.save(args.out, preds)
        if not args.quiet:
            print(f"wrote predictions {preds.shape} -> {args.out}")
            print(json.dumps(server.stats(), indent=1))
        return 0

    from dryad_tpu.resilience.faults import injector_from_env
    from dryad_tpu.serve.http import make_http_server

    # request tracing (r17): install the span ring so /trace serves and
    # per-request stage spans are captured — DRYAD_TRACE=0 opts out (the
    # obs registry disabled also keeps the request path allocation-free)
    if os.environ.get("DRYAD_TRACE", "1") != "0":
        from dryad_tpu.obs.trace_export import enable_tracing

        enable_tracing()

    # replica fault drills (fleet supervisor -> env -> this process):
    # absent/empty env costs nothing; a malformed spec fails startup loudly
    fault_hook = injector_from_env()
    httpd = make_http_server(server, args.host, args.port,
                             verbose=not args.quiet,
                             log_requests=args.log_requests,
                             auth_token=args.auth_token,
                             fault_hook=fault_hook)
    host, port = httpd.server_address[:2]
    if args.port_file:
        # the fleet handshake: replicas bind port 0, so readiness and the
        # chosen port must be announced race-free — write-then-rename so a
        # watcher never reads a half-written file
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(f"{host} {port}\n")
        os.replace(tmp, args.port_file)
    print(f"dryad serving on http://{host}:{port}  "
          f"(backend={server.backend}; POST /predict, GET /stats)")
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.server_close()
        server.stop()
        print(json.dumps(server.stats(), indent=1))
    return 0


def cmd_retrain(args) -> int:
    """Continual-boosting retrain worker (r19): the ONLY jax-importing
    piece of the drift→retrain→publish loop — the scheduler launches one
    of these as a subprocess per job, so a wedged device dies here, not
    in the fleet control plane.

    Loads the served artifact, warm-start APPENDS ``--trees`` new trees
    on the fresh rows (``--data``: an npz with ``X``/``y``, binned in
    the model's frozen bin space), optionally after a ``Booster.refit``
    re-weighting pass, and saves the new generation with a fresh
    reference profile.  ``DRYAD_CONTINUAL_FAULTS`` (e.g.
    ``retrain:1:bad_generation``) is the deterministic drill knob: a
    fired ``bad_generation`` point trains against a covariate-scaled
    copy of the rows — a structurally valid model whose embedded profile
    breaches against live traffic, exactly what a poisoned retrain data
    pipeline would ship (the probation window must catch it)."""
    # every generation ships a drift baseline unless explicitly disabled
    os.environ.setdefault("DRYAD_PROFILE", "1")

    import dryad_tpu as dryad
    from dryad_tpu.resilience.faults import (BAD_GENERATION,
                                             CONTINUAL_FAULTS_ENV,
                                             injector_from_env)

    model = dryad.Booster.load_any(args.model)

    injector = injector_from_env(env_var=CONTINUAL_FAULTS_ENV)
    fault_fired = None
    scale = None
    if injector is not None:
        pt = injector.take("retrain", args.job_index)
        if pt is not None and pt.kind == BAD_GENERATION:
            # the poisoned-pipeline twin: scale the covariates so the
            # generation's fresh profile is built on rows live traffic
            # never resembles
            scale = np.float32(0.25)
            fault_fired = pt.kind

    if os.path.isdir(args.data):
        # chunked corpus: a directory of npz shards (each with X/y, bound
        # by sorted filename) streamed through the model's frozen mapper
        # into an on-disk spill — drift-triggered retrains work on
        # corpora that never fit in RAM as a single npz (Issue 17)
        from dryad_tpu.data.streaming import dataset_from_chunks

        if args.refit_decay:
            raise SystemExit(
                "--refit-decay needs a resident npz corpus (refit rebinning "
                "touches every raw row at once); drop it or pass one npz")
        shards = sorted(
            os.path.join(args.data, f) for f in os.listdir(args.data)
            if f.endswith(".npz"))
        if not shards:
            raise SystemExit(f"--data {args.data!r} holds no .npz shards")
        ys = []
        for s in shards:
            with np.load(s) as z:
                if "X" not in z.files or "y" not in z.files:
                    raise SystemExit(f"shard {s!r} must hold X and y")
                ys.append(np.asarray(z["y"]))
        y = np.concatenate(ys)

        def corpus_chunks():
            for s in shards:
                with np.load(s) as z:
                    Xc = np.asarray(z["X"], np.float32)
                yield Xc if scale is None else Xc * scale

        spill_path = args.out + ".bins"
        ds = dataset_from_chunks(
            corpus_chunks, y, int(y.shape[0]), model.mapper.num_features,
            mapper=model.mapper, spill=spill_path)
    else:
        z = np.load(args.data)
        if "X" not in z.files or "y" not in z.files:
            raise SystemExit(f"--data {args.data!r} must be an npz with X and y")
        X = np.asarray(z["X"], np.float32)
        y = np.asarray(z["y"])
        if scale is not None:
            X = X * scale

        if args.refit_decay:
            # re-weight the OLD trees' leaves toward the fresh rows first,
            # then append — structure is kept, so the frozen bin space and
            # tree geometry still match for the warm start
            model = model.refit(X, y, decay_rate=args.refit_decay)

        ds = dryad.Dataset(X, y, mapper=model.mapper)
    p = model.params.replace(num_trees=args.trees)

    if args.supervise:
        from dryad_tpu.resilience import RetryPolicy, supervise_train

        ckdir = args.checkpoint_dir or (args.out + ".ckpt")
        booster = supervise_train(p, ds, backend=args.backend,
                                  policy=RetryPolicy(),
                                  checkpoint_dir=ckdir,
                                  journal=args.journal,
                                  init_model=model)
    else:
        booster = dryad.train(p, ds, backend=args.backend, init_model=model)

    if getattr(ds, "is_streamed", False):
        # the spill is a training temporary, not part of the generation
        try:
            os.unlink(ds.path)
        except OSError:
            pass

    if args.text:
        booster.save_text(args.out)
    else:
        booster.save(args.out)
    print(json.dumps({
        "retrain": args.model, "out": args.out,
        "trees_before": model.num_iterations,
        "trees_after": booster.num_iterations,
        "job_index": args.job_index,
        "fault": fault_fired,
        "profile": getattr(booster, "profile", None) is not None,
    }))
    return 0


def cmd_fleet(args) -> int:
    """Replicated serving: N serve subprocesses under lifecycle
    supervision (crash/hang detection, budgeted respawn, journal) behind
    the health-routed fleet router (dryad_tpu/fleet)."""
    from dryad_tpu.fleet import (CapacityController, FleetSupervisor,
                                 make_fleet_router, serve_argv, serve_env)
    from dryad_tpu.fleet.router import main_loop
    from dryad_tpu.obs.drift import parse_psi_budget
    from dryad_tpu.obs.slo import parse_budgets
    from dryad_tpu.resilience.policy import RetryPolicy

    # pure-argument guards FIRST (the cmd_train idiom): continual boosting
    # needs the journal (the scheduler tails drift_breach from it) and
    # STABLE model names — drift verdicts are keyed by registry alias, so
    # a bare-path spec would change label (v1 -> v2) on the first push
    # and orphan its own probation window
    continual_models = {}
    if args.continual_data:
        if not args.journal:
            raise SystemExit("--continual-data requires --journal (the "
                             "retrain scheduler tails drift_breach events "
                             "from the fleet journal)")
        for spec in args.model:
            name, _, path = spec.partition("=")
            if not path or "/" in name or "\\" in name:
                raise SystemExit(
                    f"--continual-data requires NAME=path model specs "
                    f"(got {spec!r}) — generation pushes keep the alias, "
                    "so the drift verdict survives the swap")
            continual_models[name] = path

    # router-side tracing: the merged /trace endpoint needs the router's
    # own span ring (replicas enable theirs in cmd_serve)
    if os.environ.get("DRYAD_TRACE", "1") != "0":
        from dryad_tpu.obs.trace_export import enable_tracing

        enable_tracing()

    model_caps = {}
    for spec in args.model_cap or []:
        name, _, cap = spec.partition("=")
        if not name or not cap.isdigit():
            raise SystemExit(f"--model-cap wants NAME=N, got {spec!r}")
        model_caps[name] = int(cap)

    def make_argv(index: int, port_file: str) -> list:
        return serve_argv(args.model, port_file, backend=args.backend,
                          max_batch_rows=args.max_batch_rows,
                          max_wait_ms=args.max_wait_ms,
                          queue_size=args.queue_size, warmup=args.warmup,
                          drift_window=args.drift_window,
                          auth_token=args.auth_token)

    # elastic bounds (r22): --replicas alone keeps the frozen-pool
    # behavior (min == max == replicas); explicit bounds arm the
    # capacity controller, and the pool starts inside them
    min_replicas = (args.min_replicas if args.min_replicas is not None
                    else args.replicas)
    max_replicas = (args.max_replicas if args.max_replicas is not None
                    else args.replicas)
    if not 1 <= min_replicas <= max_replicas:
        raise SystemExit("need 1 <= --min-replicas <= --max-replicas")
    n_start = min(max(args.replicas, min_replicas), max_replicas)

    policy = (RetryPolicy() if args.retry_budget is None
              else RetryPolicy(retry_budget=args.retry_budget))
    supervisor = FleetSupervisor(
        make_argv, n_start, policy=policy, journal=args.journal,
        make_env=lambda index: serve_env(index, args.backend),
        probe_interval_s=args.probe_interval,
        startup_timeout_s=args.startup_timeout)
    # a process MANAGER must not die leaving its children running: the
    # default SIGTERM kills python without unwinding, so `kill <fleet>`
    # would orphan every replica (observed).  Route TERM through the
    # KeyboardInterrupt path main_loop already handles, so the finally
    # below terminates the pool.
    import signal

    def _term(signum, frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _term)
    # start() is INSIDE the try: each replica pays a 10-20 s jax import,
    # so a TERM/Ctrl-C during startup must still reach supervisor.stop()
    # (which terminates whatever was already spawned), or the half-built
    # pool leaks serve processes
    scheduler = None
    controller = None
    try:
        supervisor.start()
        httpd = make_fleet_router(
            supervisor, args.host, args.port,
            max_inflight=args.max_inflight,
            bulk_max_inflight=args.bulk_max_inflight,
            model_caps=model_caps or None,
            request_timeout_s=args.request_timeout,
            min_healthy=args.min_healthy,
            auth_token=args.auth_token, verbose=not args.quiet,
            slo_budgets_ms=parse_budgets(args.slo_ms),
            slo_breach_after=args.slo_breach_after,
            drift_budget_psi=parse_psi_budget(args.drift_psi),
            drift_breach_after=args.drift_breach_after)
        host, port = httpd.server_address[:2]
        if max_replicas > min_replicas:
            controller = CapacityController(
                supervisor, httpd.state.capacity_signals,
                min_replicas=min_replicas, max_replicas=max_replicas,
                breach_after=args.scale_breach_after,
                cooldown_up_s=args.scale_cooldown,
                cooldown_down_s=2.0 * args.scale_cooldown).start()
            httpd.state.autoscale = controller
        if not args.quiet:
            urls = {s.name: s.state()["url"]
                    for s in supervisor.slots}
            elastic = (f", elastic {min_replicas}..{max_replicas}"
                       if controller is not None else "")
            print(f"dryad fleet on http://{host}:{port}  "
                  f"({n_start} replicas{elastic}: {urls}; POST /predict, "
                  "POST /models/push, GET /metrics aggregates the pool)")
        if continual_models:
            from dryad_tpu.continual import (JournalTailer,
                                             ProbationPublisher,
                                             RetrainScheduler,
                                             make_http_verdicts,
                                             make_subprocess_launcher,
                                             make_supervisor_push)

            out_dir = args.continual_out or os.path.join(
                os.path.dirname(os.path.abspath(args.journal)), "continual")
            launch = make_subprocess_launcher(
                args.continual_data, out_dir,
                trees=args.retrain_trees, backend=args.retrain_backend,
                timeout_s=args.retrain_timeout,
                refit_decay=args.retrain_refit_decay,
                supervise=args.retrain_supervise)
            publisher = ProbationPublisher(
                make_supervisor_push(supervisor, auth_token=args.auth_token),
                make_http_verdicts(host, port, auth_token=args.auth_token),
                journal=supervisor.journal,
                probation_polls=args.probation_polls,
                poll_interval_s=args.probation_interval)
            scheduler = RetrainScheduler(
                continual_models, launch,
                journal=supervisor.journal, publisher=publisher,
                policy=policy, cooldown_s=args.retrain_cooldown,
                max_concurrent=args.retrain_max_concurrent,
                source=JournalTailer(args.journal)).start()
            if not args.quiet:
                print(f"continual boosting armed: {sorted(continual_models)} "
                      f"-> {out_dir} (drift_breach triggers a warm-start "
                      "retrain; probationed rolling publish + rollback)")
        main_loop(httpd, quiet=args.quiet)
    finally:
        if scheduler is not None:
            scheduler.stop(timeout_s=5.0)
        if controller is not None:
            # signal first with a short join: an in-flight scale-up
            # unblocks when supervisor.stop() below reaps its child
            controller.stop(timeout_s=2.0)
        supervisor.stop()
        if controller is not None:
            controller.stop(timeout_s=5.0)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="dryad_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("train", help="train a booster")
    t.add_argument("--config", help="params JSON file")
    t.add_argument("--data", required=True)
    t.add_argument("--label", required=True)
    t.add_argument("--group", help="query sizes for ranking")
    t.add_argument("--valid")
    t.add_argument("--valid-label")
    t.add_argument("--valid-group")
    t.add_argument("--model", help="output model path")
    t.add_argument("--backend", default="auto", choices=["auto", "tpu", "cpu"])
    t.add_argument("--checkpoint-dir")
    t.add_argument("--checkpoint-every", type=int, default=10)
    t.add_argument("--resume", action="store_true")
    t.add_argument("--supervise", action="store_true",
                   help="resilient run: classify device faults, "
                        "degrade chunking, auto-resume from checkpoints "
                        "(requires --checkpoint-dir)")
    t.add_argument("--journal",
                   help="supervised-run journal JSONL path (with --supervise)")
    t.add_argument("--retry-budget", type=int, default=None,
                   help="supervised-run fault budget before failing closed")
    t.add_argument("--log-jsonl",
                   help="per-iteration metrics JSONL path (under "
                        "--supervise, post-fault segments re-log the "
                        "replayed iterations — identical values; dedupe by "
                        "keeping the highest supervise_attempt per "
                        "iteration)")
    t.add_argument("--profile-dir", help="capture a jax.profiler trace here")
    t.add_argument("--trace-out",
                   help="write a Chrome trace_event JSON (Perfetto-"
                        "loadable) of the run's span tree — plus the "
                        "journal events under --supervise --journal — "
                        "here (obs/trace_export.py)")
    t.add_argument("--log-period", type=int, default=1)
    t.add_argument("--metrics-port", type=int, default=None,
                   help="mount the live observability endpoint on this "
                        "port for the duration of the run (0 = any free "
                        "port; GET /stats, /metrics, /healthz — "
                        "dryad_tpu/obs); with --supervise --journal the "
                        "journal is tailed into the live series")
    t.add_argument("--metrics-host", default="127.0.0.1")
    t.add_argument("--auth-token", default=os.environ.get("DRYAD_AUTH_TOKEN"),
                   help="bearer token for the metrics endpoint (env "
                        "DRYAD_AUTH_TOKEN; /healthz stays open)")
    t.add_argument("--quiet", action="store_true")
    t.set_defaults(fn=cmd_train)

    pf = sub.add_parser("profile",
                        help="stage-level device profiler (timed-fori "
                             "harness with runtime liveness proofs)")
    pf.add_argument("--selftest", action="store_true",
                    help="prove the liveness proof: the seeded dead probe "
                         "must be rejected, every shipped probe must pass "
                         "(the ci.sh gate; CPU, seconds)")
    pf.add_argument("--list", action="store_true",
                    help="print the stage-probe catalog and exit")
    pf.add_argument("--stage", action="append", default=None,
                    help="restrict to the named stage(s); repeatable")
    pf.add_argument("--rows", type=int, default=None,
                    help="probe row count (default: 1M on device, 8192 CPU)")
    pf.add_argument("--k", type=int, default=3,
                    help="dependent iterations inside the timed fori")
    pf.add_argument("--reps", type=int, default=2,
                    help="timed programs per probe (min is the estimator)")
    pf.add_argument("--slots", type=int, default=64,
                    help="segment/slot count P for the per-level stages")
    pf.add_argument("--out",
                    help="also write the stamped PROFILE JSON here")
    pf.add_argument("--trend-root", default=None,
                    help="compare against the PROFILE_r*.json history in "
                         "this directory (newest-vs-median, spread veto)")
    pf.add_argument("--check-trend", action="store_true",
                    help="exit 1 on a profile-trend regression verdict")
    pf.add_argument("--quiet", action="store_true")
    pf.set_defaults(fn=cmd_profile)

    pr = sub.add_parser("predict", help="predict with a saved model")
    pr.add_argument("--model", required=True)
    pr.add_argument("--data", required=True)
    pr.add_argument("--out", required=True)
    pr.add_argument("--raw", action="store_true", help="raw scores (no link)")
    pr.add_argument("--backend", default="cpu", choices=["tpu", "cpu"])
    pr.set_defaults(fn=cmd_predict)

    d = sub.add_parser("dump", help="dump model structure as JSON")
    d.add_argument("--model", required=True)
    d.add_argument("--out")
    d.add_argument("--text", action="store_true",
                   help="versioned round-trippable text format "
                        "(Booster.load_text)")
    d.set_defaults(fn=cmd_dump)

    s = sub.add_parser("serve", help="online inference service")
    s.add_argument("--model", required=True, action="append",
                   help="model path (.dryad binary or text dump), or "
                        "NAME=path to register a routing alias; repeat to "
                        "co-serve several models — the last one is active")
    s.add_argument("--backend", default="auto", choices=["auto", "tpu", "cpu"])
    s.add_argument("--host", default="127.0.0.1")
    s.add_argument("--port", type=int, default=8000)
    s.add_argument("--max-batch-rows", type=int, default=4096,
                   help="micro-batch row cap (also the largest predict bucket)")
    s.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="batch coalescing deadline")
    s.add_argument("--queue-size", type=int, default=256,
                   help="bounded request queue (backpressure)")
    s.add_argument("--pipeline-depth", type=int, default=2,
                   help="overlapped dispatch run-ahead (1 = serial loop)")
    s.add_argument("--sharded", default="auto", choices=["auto", "on", "off"],
                   help="shard big predict buckets over the device mesh "
                        "(auto: rows×outputs threshold)")
    s.add_argument("--device-budget-mb", type=int, default=0,
                   help="staged-model memory budget; 0 = unlimited "
                        "(LRU eviction, active version pinned)")
    s.add_argument("--warmup", action="store_true",
                   help="compile every (version, bucket) predict program "
                        "at startup and arm the recompile tripwire "
                        "(unexpected compiles then degrade /healthz)")
    s.add_argument("--drift-window", type=int, default=8192,
                   help="model-drift monitor window (rows of recent "
                        "traffic compared against the model's embedded "
                        "reference profile; 0 disables drift telemetry)")
    s.add_argument("--log-requests", action="store_true",
                   help="structured JSON request log on stderr")
    s.add_argument("--auth-token", default=os.environ.get("DRYAD_AUTH_TOKEN"),
                   help="bearer token required on every endpoint except "
                        "/healthz (env DRYAD_AUTH_TOKEN)")
    s.add_argument("--request", help="one-shot mode: predict this matrix "
                                     "through the serving stack and exit")
    s.add_argument("--out", help="one-shot mode: output .npy path")
    s.add_argument("--raw", action="store_true", help="raw scores (no link)")
    s.add_argument("--port-file",
                   help="write 'host port' here once listening (atomic "
                        "rename) — the fleet supervisor's readiness "
                        "handshake for --port 0 replicas")
    s.add_argument("--quiet", action="store_true")
    s.set_defaults(fn=cmd_serve)

    rt = sub.add_parser("retrain",
                        help="continual-boosting retrain worker: warm-start "
                             "append on fresh rows (the scheduler's "
                             "subprocess; dryad_tpu/continual)")
    rt.add_argument("--model", required=True,
                    help="served artifact to warm-start from (binary or "
                         "text format)")
    rt.add_argument("--data", required=True,
                    help="fresh rows: an .npz with X and y, or a DIRECTORY "
                         "of .npz shards streamed out-of-core (both binned "
                         "through the model's frozen mapper)")
    rt.add_argument("--out", required=True, help="new-generation artifact path")
    rt.add_argument("--trees", type=int, default=20,
                    help="NEW trees to append (0 = a no-op generation, "
                         "predict-identical to --model)")
    rt.add_argument("--backend", default="cpu",
                    choices=["auto", "tpu", "cpu"])
    rt.add_argument("--refit-decay", type=float, default=0.0,
                    help="re-weight the old trees' leaves toward the fresh "
                         "rows first (Booster.refit decay_rate; 0 skips)")
    rt.add_argument("--supervise", action="store_true",
                    help="run the append under resilience.supervise_train "
                         "(fault classes degrade and resume bitwise)")
    rt.add_argument("--checkpoint-dir",
                    help="supervised-run checkpoint dir (default: "
                         "<out>.ckpt)")
    rt.add_argument("--journal",
                    help="supervised-run journal JSONL (with --supervise)")
    rt.add_argument("--job-index", type=int, default=0,
                    help="global retrain-job index — the "
                         "DRYAD_CONTINUAL_FAULTS iteration the injector "
                         "matches against")
    rt.add_argument("--text", action="store_true",
                    help="save the generation in the text format")
    rt.set_defaults(fn=cmd_retrain)

    fl = sub.add_parser("fleet",
                        help="replicated serving: supervised replica pool "
                             "behind a health-routed router (dryad_tpu/fleet)")
    fl.add_argument("--model", required=True, action="append",
                    help="model path or NAME=path alias; repeat to co-serve "
                         "(every replica loads the same set)")
    fl.add_argument("--replicas", type=int, default=2,
                    help="serve subprocesses in the pool (with elastic "
                         "bounds unset this is also min == max: the "
                         "frozen pre-r22 pool)")
    fl.add_argument("--min-replicas", type=int, default=None,
                    help="elastic floor (r22): the capacity loop never "
                         "drains below this many slots (default "
                         "--replicas)")
    fl.add_argument("--max-replicas", type=int, default=None,
                    help="elastic ceiling (r22): the capacity loop never "
                         "grows past this many slots (default "
                         "--replicas; max > min arms the controller)")
    fl.add_argument("--scale-cooldown", type=float, default=60.0,
                    help="seconds after a scale-up before the next one "
                         "(scale-downs wait 2x this) — one breach burst "
                         "buys one replica, not a ramp-to-max")
    fl.add_argument("--scale-breach-after", type=int, default=2,
                    help="consecutive pressure polls (sustained SLO "
                         "breach or admission saturation) before a "
                         "scale-up is admitted")
    fl.add_argument("--backend", default="auto",
                    choices=["auto", "tpu", "cpu"],
                    help="every replica's serve backend; a device backend "
                         "gives replica i chip i of this host and nothing "
                         "else (one process per chip) — a replica without "
                         "a chip fails its start-up, it never serves from "
                         "the CPU instead")
    fl.add_argument("--host", default="127.0.0.1")
    fl.add_argument("--port", type=int, default=8000,
                    help="router port (also serves the aggregated /metrics "
                         "and fleet /healthz; replicas bind free ports)")
    fl.add_argument("--max-batch-rows", type=int, default=4096)
    fl.add_argument("--max-wait-ms", type=float, default=2.0)
    fl.add_argument("--queue-size", type=int, default=256)
    fl.add_argument("--warmup", action="store_true",
                    help="each replica compiles its buckets and arms the "
                         "recompile tripwire at startup")
    fl.add_argument("--max-inflight", type=int, default=64,
                    help="fleet admission cap: beyond this every request "
                         "sheds (503)")
    fl.add_argument("--bulk-max-inflight", type=int, default=None,
                    help="bulk requests shed beyond this in-flight count "
                         "(default max-inflight/2) — interactive survives "
                         "overload first")
    fl.add_argument("--model-cap", action="append", default=None,
                    help="NAME=N per-model in-flight admission cap; "
                         "repeatable")
    fl.add_argument("--request-timeout", type=float, default=30.0,
                    help="per-forward timeout; one retry on a different "
                         "healthy replica")
    fl.add_argument("--min-healthy", type=int, default=1,
                    help="fleet /healthz answers 503 below this many "
                         "routable replicas")
    fl.add_argument("--probe-interval", type=float, default=0.25,
                    help="supervisor health-probe cadence (seconds)")
    fl.add_argument("--slo-ms", default="",
                    help="per-priority p99 budgets as "
                         "'interactive=250,bulk=2000' (ms; the defaults) — "
                         "a SUSTAINED breach degrades the router /healthz; "
                         "'off' disables SLO health-gating")
    fl.add_argument("--slo-breach-after", type=int, default=3,
                    help="consecutive over-budget /healthz evaluations "
                         "before the SLO degrades the router")
    fl.add_argument("--drift-psi", default="",
                    help="PSI budget for the model-drift layer (default "
                         "0.2, the 'significant shift' rule; replicas' "
                         "window counts merge exactly, GET /drift "
                         "reports verdicts, a sustained breach journals "
                         "drift_breach + warns in /healthz payloads — "
                         "warn-only; 'off' disables drift reporting)")
    fl.add_argument("--drift-breach-after", type=int, default=2,
                    help="consecutive over-budget drift windows before "
                         "the breach is sustained (journal + warning)")
    fl.add_argument("--drift-window", type=int, default=8192,
                    help="per-replica drift monitor window in rows "
                         "(serve --drift-window; 0 disables the "
                         "replica-side monitors)")
    fl.add_argument("--startup-timeout", type=float, default=120.0,
                    help="per-replica readiness deadline (device replicas "
                         "pay model load + compile here)")
    fl.add_argument("--retry-budget", type=int, default=None,
                    help="per-replica respawns before the slot fails "
                         "closed (resilience.RetryPolicy)")
    fl.add_argument("--journal",
                    help="fleet journal JSONL path (spawn/crash/respawn/"
                         "swap decisions, append-only)")
    fl.add_argument("--auth-token",
                    default=os.environ.get("DRYAD_AUTH_TOKEN"),
                    help="bearer token for router AND replicas "
                         "(/healthz stays open)")
    fl.add_argument("--continual-data", default=None,
                    help="arm continual boosting: fresh rows each drift-"
                         "triggered retrain appends on — an .npz with X/y "
                         "or a directory of .npz shards (streamed out-of-"
                         "core by the retrain worker); requires --journal "
                         "and NAME=path model specs (dryad_tpu/continual)")
    fl.add_argument("--continual-out", default=None,
                    help="generation artifact dir (default: "
                         "<journal dir>/continual)")
    fl.add_argument("--retrain-trees", type=int, default=20,
                    help="NEW trees each generation appends")
    fl.add_argument("--retrain-backend", default="cpu",
                    choices=["auto", "tpu", "cpu"],
                    help="retrain worker backend (cpu keeps retrains off "
                         "the serving devices)")
    fl.add_argument("--retrain-cooldown", type=float, default=300.0,
                    help="per-model seconds between finished retrains — "
                         "the breach debounce")
    fl.add_argument("--retrain-max-concurrent", type=int, default=1,
                    help="fleet-wide in-flight retrain budget")
    fl.add_argument("--retrain-timeout", type=float, default=1800.0,
                    help="retrain subprocess wall deadline (a wedged "
                         "worker is killed, never waited on)")
    fl.add_argument("--retrain-refit-decay", type=float, default=0.0,
                    help="Booster.refit re-weighting before each append "
                         "(0 skips)")
    fl.add_argument("--retrain-supervise", action="store_true",
                    help="run each retrain under "
                         "resilience.supervise_train")
    fl.add_argument("--probation-polls", type=int, default=5,
                    help="drift-verdict polls a pushed generation must "
                         "survive before promotion")
    fl.add_argument("--probation-interval", type=float, default=2.0,
                    help="seconds between probation polls (each poll is a "
                         "fresh replica scrape + gate evaluation)")
    fl.add_argument("--quiet", action="store_true")
    fl.set_defaults(fn=cmd_fleet)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
