"""The runner ``train_job_cat``: the best-first runner's job over a table with
categorical columns, followed by the best-first reference with subset
splits.

The job, the clock, the window, the memory reading, the checkpoint read-back
and the traced run's reduction are ``train_job``'s and
``train_job_bestfirst``'s own, imported; what differs is what categorical
columns need:

* the data's categorical columns (``data.categorical_features``, the same
  list as the job's ``params.categorical_features``) are handed to
  ``Dataset(..., categorical_features=...)``, as the CLI hands them;
* the job's trees are read back in raw space (``trees_of_cat``): a node on
  a categorical column becomes the codes its model sends left, found by
  binning every code through the job's own mapper and reading the node's
  bitset (or threshold) on the bin; the reference (``gbdt_bestfirst_cat``)
  turns them into its own bins by its own rule;
* ``facts["cat"]`` holds the window's split nodes by kind and their
  categorical share (counter ``dryad_split_nodes_total{kind}``), the
  categories the program's sketch kept and pooled (gauge
  ``dryad_cat_levels{feature, kind}``), the rows of each categorical column
  that fall in its overflow bin, by the reference's rule, and, on a traced
  run, ``cat_device_ms``: device time an iteration in scope ``dryad.cat``
  (``benchmark/layer_metrics/cat_device_ms.py``), 0.0 where the program has
  no such scope;
* ``facts["flips"]`` holds the followed trees' split nodes, the share that
  loses more than 1e-9 of the gain, and the largest losses over the split's
  terms (``gbdt_bestfirst_cat``'s docstring), which the band is set against;
* ``facts["policy"]`` holds every gate the program resolved.

A program that keeps no such counter or gauge gives ``{}`` for them and runs
the cell all the same.
"""

from __future__ import annotations

import gc
import os
import re
import shutil
import tempfile
import time

import numpy as np

from benchmark.counts import gbdt_bestfirst as counts_bestfirst
from benchmark.harness import device as devmod
from benchmark.harness.clock import CompileClock, StopJob, WindowClock
from benchmark.harness.result import judge
from benchmark.runners import train_job
from benchmark.runners.train_job import make_data, program_temp_bytes, read_layers, say
from benchmark.runners.train_job_bestfirst import (COUNTERS, NUMBERS, job_params,  # noqa: F401
                                                   leafwise_counters)
from benchmark.runners.train_job_rank import policy_choices

SPLIT_NODES = "dryad_split_nodes_total"
LEVELS = "dryad_cat_levels"


def codes_of(config: dict) -> int:
    """Width of a raw table: the largest cardinality the data states."""
    return int(max(config["data"]["cardinality"].values()))


def cat_counters() -> dict:
    """The program's split nodes by kind as they stand; {} from a program
    that keeps none."""
    from dryad_tpu.obs.registry import default_registry

    series = default_registry().snapshot()["counters"].get(SPLIT_NODES) or {}
    out = {}
    for label, value in series.items():
        kind = re.search(r'kind="([^"]*)"', str(label))
        if kind:
            out[kind.group(1)] = out.get(kind.group(1), 0.0) + float(value)
    return out


def cat_levels() -> dict:
    """``{feature: {kept, pooled}}`` of the program's sketch; {} from a
    program that keeps no such gauge."""
    from dryad_tpu.obs.registry import default_registry

    series = default_registry().snapshot()["gauges"].get(LEVELS) or {}
    out: dict = {}
    for label, value in series.items():
        keys = dict(re.findall(r'(\w+)="([^"]*)"', str(label)))
        if "feature" in keys and "kind" in keys:
            out.setdefault(keys["feature"], {})[keys["kind"]] = float(value)
    return out


def trees_of_cat(booster, codes: int) -> list:
    """The job's trees in raw space, as its model applies them to raw rows:
    a numeric threshold bin ``t`` means ``x <= edges[t - 1]``; a node on a
    categorical column sends left ``cat_left[n, code]`` (the last column: a
    missing value), read off its bitset (or its threshold, for a node that
    is not marked categorical) at the bin the job's mapper gives the code."""
    from benchmark.reference.gbdt_bestfirst_cat import CatTree

    feats = booster.mapper.features
    raw = np.arange(codes, dtype=np.float32)
    bin_of = {f: np.append(booster.mapper.transform_column(raw, f), 0).astype(np.int64)
              for f, fb in enumerate(feats) if fb.is_categorical}
    trees = []
    for t in range(booster.num_total_trees):
        feature = np.append(np.asarray(booster.feature[t], np.int32), -1)
        tb = np.append(np.asarray(booster.threshold[t], np.int64), 0)
        is_cat = np.append(np.asarray(booster.is_cat[t], bool), False)
        dleft = np.append(np.asarray(booster.default_left[t], bool), True)
        bitset = np.asarray(booster.cat_bitset[t], np.uint64)
        thr = np.full(feature.shape, -np.inf, np.float32)
        cat_left = np.zeros((feature.shape[0], codes + 1), bool)
        for n in np.flatnonzero(feature >= 0):
            f = int(feature[n])
            if f in bin_of:
                b = bin_of[f]
                if is_cat[n]:
                    cat_left[n] = ((bitset[n, b >> 5] >> (b & 31).astype(np.uint64)) & 1) == 1
                else:
                    cat_left[n] = (b <= tb[n]) & (dleft[n] | (b != 0))
            elif tb[n] >= 1:
                thr[n] = feats[f].edges[tb[n] - 1]
        trees.append(CatTree(feature, thr,
                             np.append(np.asarray(booster.left[t], np.int32), 0),
                             np.append(np.asarray(booster.right[t], np.int32), 0),
                             np.append(np.asarray(booster.value[t], np.float64), 0.0),
                             np.append(np.asarray(booster.cover[t], np.float64), 0.0),
                             cat_left))
    return trees


def run(cell, args, t_start: float) -> dict:
    rehearsal = bool(args.rehearse_cpu)
    devices = devmod.check(cell.chips, rehearsal)
    os.environ["DRYAD_PROG_MEMORY"] = "1"
    compile_clock = CompileClock()

    import jax

    import dryad_tpu as dryad
    import dryad_tpu.engine as engine
    from dryad_tpu.checkpoint import Checkpointer
    from dryad_tpu.obs import spans as obs_spans

    cache_dir = engine.place_compile_cache()
    config, traffic = cell.config, cell.traffic
    params, depth_cap = job_params(config, rehearsal)
    columns = [int(f) for f in config["data"]["categorical_features"]]
    codes = codes_of(config)
    say(f"[{cell.name}] {'CPU REHEARSAL, no device result; ' * rehearsal}"
        f"device {devices[0].device_kind} x{len(devices)}; compile cache {cache_dir}")

    # ---- set-up: data ----------------------------------------------------
    t0 = time.perf_counter()
    q, y, qv, yv = make_data(config, args.seed, rehearsal)
    t_gen = time.perf_counter() - t0
    ds = dryad.Dataset(q.astype(np.float32), y, max_bins=params["max_bins"],
                       categorical_features=columns)
    vds = ds.bind(qv.astype(np.float32), yv)
    data_prep_s = time.perf_counter() - t0
    say(f"[{cell.name}] data {q.shape} + valid {qv.shape}, positives {float(y.mean()):.4f}, "
        f"categorical columns {columns}: generated in {t_gen:.1f}s, sketched and binned in "
        f"{data_prep_s - t_gen:.1f}s")

    # ---- the job ---------------------------------------------------------
    tmp = tempfile.mkdtemp(prefix="bench_" + cell.name.replace(".", "_") + "_")
    ckdir, trace_dir = os.path.join(tmp, "ckpt"), os.path.join(tmp, "trace")
    span_log: list = []
    obs_spans.set_trace_sink(lambda path, t0_s, dur_s, *a, **k: span_log.append((path, t0_s, dur_s)))
    tracing = {"on": False}
    marks = {}

    def on_open():
        marks["setup_s"] = time.perf_counter() - t_start
        marks["compile"] = compile_clock.mark()
        marks["setup_compile_s"] = compile_clock.compile_s
        marks["leafwise_open"] = leafwise_counters()
        marks["cat_open"] = cat_counters()
        if args.trace:
            try:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            except (AttributeError, TypeError):
                jax.profiler.start_trace(trace_dir)
            tracing["on"] = True

    def on_close():
        marks["window_compile"] = compile_clock.since(marks["compile"])
        marks["leafwise_close"] = leafwise_counters()
        marks["cat_close"] = cat_counters()
        if tracing["on"]:
            jax.profiler.stop_trace()
            tracing["on"] = False

    warmup = int(traffic["warmup_chunks"])
    if args.trace:
        clock = WindowClock(min(args.seconds, float(traffic["trace_seconds"])), warmup,
                            min_chunks=int(traffic["trace_chunks"]),
                            on_open=on_open, on_close=on_close)
    else:
        clock = WindowClock(args.seconds, warmup, on_open=on_open, on_close=on_close)

    every = int(config["checkpoint_every"])
    kw = dict(valid_sets=[vds], backend="tpu", callbacks=[clock.on_iter],
              chunk_hook=clock.on_dispatch, checkpoint_dir=ckdir, checkpoint_every=every)
    died = None
    try:
        train_job.train_entry(params, ds, **kw)
        died = "the job ran out of trees before the window closed"
    except StopJob:
        pass
    except Exception as e:  # noqa: BLE001 - a job that dies fails its window
        died = f"{type(e).__name__}: {e}"
    finally:
        if tracing["on"]:
            jax.profiler.stop_trace()
        obs_spans.set_trace_sink(None)
    t_end = time.perf_counter()

    # ---- what the device held --------------------------------------------
    live = devmod.live_peak_bytes(devices)
    temp = program_temp_bytes()
    limit = devmod.bytes_limit(devices)
    say(f"[{cell.name}] device memory: live peak {live} + training program temporaries {temp} "
        f"= {live + temp} of {limit} bytes")
    policy, levels = policy_choices(), cat_levels()

    # ---- read the checkpoint back, free the program's state ---------------
    booster, ckpt_iter = None, 0
    if os.path.isdir(ckdir):
        latest = Checkpointer(ckdir, every=every).latest()
        if latest is not None:
            booster, ckpt_iter = latest
    job = None
    if booster is not None:
        job = {"trees": trees_of_cat(booster, codes), "init_score": float(booster.init_score[0]),
               "evals": dict(clock.evals)}
    features = int(ds.num_features)
    bins = int(ds.mapper.total_bins)
    bin_bytes = int(ds.X_binned.dtype.itemsize)
    del ds, vds, booster, kw
    gc.collect()

    # ---- the reference follows the job -------------------------------------
    from benchmark.reference.gbdt_bestfirst_cat import BestFirstCat, Categories, Rows

    cats = Categories(q, columns, codes, int(params["max_bins"]))
    pooled = cats.pooled_share(q)
    due = clock.iters_done - clock.iters_done % every    # the last boundary the job passed
    numbers = {"job_died": 1.0 if died else 0.0,
               "checkpoint_iters_gap": float(abs(ckpt_iter - due))}
    t_ref = time.perf_counter()
    detail = {}
    passes = counts_bestfirst.level_passes(params["num_leaves"])
    rows_needed_share = None
    if job is not None and job["trees"]:
        ref = BestFirstCat(params, Rows(cats.bins(q), y), Rows(cats.bins(qv), yv), depth_cap, cats)
        detail = ref.follow(job, int(traffic["reference_iterations"]))
        detail.update(ref.follow_window(job, int(traffic["window_iterations"])))
        for key in NUMBERS:
            numbers[key] = float(detail[key])
        del ref
        window_trees = job["trees"][-max(clock.window_iters, 1):]
        ceiling = q.shape[0] * (1 + (passes - 1) / 2.0)
        rows_needed_share = float(np.mean([counts_bestfirst.rows_needed(t.left, t.right, t.cover)
                                           for t in window_trees]) / ceiling)
    ref_s = time.perf_counter() - t_ref
    shown = {k: v for k, v in detail.items() if k not in ("per_tree", "window_trees", "flip_rel")}
    say(f"[{cell.name}] reference followed the job in {ref_s:.1f}s: {shown}")
    for row in detail.get("per_tree", []):
        say(f"[{cell.name}]   tree {row}")
    for row in detail.get("window_trees", []):
        say(f"[{cell.name}]   window tree {row}")
    if died:
        say(f"[{cell.name}] the job died: {died}")
    correct, compared = judge(numbers, cell.limits)

    # ---- metrics -----------------------------------------------------------
    window_iters, window_s = clock.window_iters, clock.window_s
    attempted = window_iters
    failed = 0
    if died:
        lost = clock.chunks[-1]["n"] if clock.chunks and clock.chunks[-1]["done"] is None else 0
        failed = max(1, lost or every)
        attempted += failed
    metrics = {}
    if not rehearsal and window_s > 0:
        metrics["iters_per_s"] = {"value": window_iters / window_s, "unit": "iters/s"}
        metrics["setup_s"] = {"value": marks["setup_s"], "unit": "s"}
    opened, closed = marks.get("leafwise_open", {}), marks.get("leafwise_close", {})
    leafwise = {name: closed[name] - opened.get(name, 0.0) for name in COUNTERS if name in closed}
    if "depth_cap" in closed:
        leafwise["depth_cap"] = closed["depth_cap"]
    c_open, c_close = marks.get("cat_open", {}), marks.get("cat_close", {})
    split_nodes = {kind: c_close[kind] - c_open.get(kind, 0.0) for kind in c_close}
    total = sum(split_nodes.values())
    cat = {"split_nodes": split_nodes,
           "categorical_share": split_nodes.get("categorical", 0.0) / total if total else None,
           "levels": levels, "pooled_rows_share": {str(f): s for f, s in pooled.items()}}
    memory_peak = live + temp
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": devmod.describe(devices, memory_peak),
           "compared": compared, "numbers": numbers, "job": job, "rehearsal": rehearsal,
           "facts": {"window_s": window_s, "window_iters": window_iters,
                     "chunks": [c["n"] for c in clock.window_chunks], "ckpt_iter": ckpt_iter,
                     "reference_s": ref_s, "run_s": t_end - t_start,
                     "level_passes": passes, "rows_needed_share": rows_needed_share,
                     "cap_stopped_steps": detail.get("cap_stopped_steps"),
                     "tree_depths": detail.get("tree_depths"), "leafwise": leafwise,
                     "cat": cat, "policy": policy,
                     "flips": {key: detail.get(key) for key in
                               ("split_nodes", "strict_flip_share", "flip_rel")},
                     "last_valid_metric": detail.get("last_valid_metric")}}

    if args.trace:
        from benchmark.layer_metrics import cat_device_ms

        window_spans = [sp for sp in span_log
                        if clock.t_open is not None and sp[1] >= clock.t_open
                        and sp[1] + sp[2] <= (clock.t_close or t_end) + 1e-3]
        facts = {
            "spans": window_spans, "window_s": window_s, "window_iters": window_iters,
            "window_chunks": len(clock.window_chunks),
            "shape": {"rows": int(q.shape[0]), "features": features, "bins": bins,
                      "depth": passes, "bin_bytes": bin_bytes, "trees": 1},
            "peaks": None if rehearsal else devmod.peaks(devices[0].device_kind),
            "memory": {"live_peak_bytes": live, "program_temp_bytes": temp, "bytes_limit": limit},
            "compile": {"setup_compile_s": marks.get("setup_compile_s"),
                        "window_compiles": marks.get("window_compile", (0.0, 0))[1]},
            "data_prep_s": data_prep_s,
            "leafwise": leafwise,
        }
        read_layers(cell, args, trace_dir, facts, out)
        cat["cat_device_ms"] = cat_device_ms.read(facts)
    shutil.rmtree(tmp, ignore_errors=True)
    return out
