"""The runner ``train_job_missing``: the best-first runner's job over a table
with empty cells, followed by the best-first reference with both planes.

The job, the clock, the window, the memory reading, the checkpoint read-back
and the traced run's reduction are ``train_job``'s and
``train_job_bestfirst``'s, imported; ``run_with`` is the best-first job's run
with what differs from one table to the next in named hooks (``Hooks``), so
that one run can serve every best-first cell.  For this cell:

* ``dataset``: the family's bins become float32 values with ``NaN`` in every
  empty cell (``datagen/bosch.as_float``), handed to ``Dataset`` as the CLI
  hands a table; the program finds the empty cells itself
  (``Dataset.has_missing``) and scans both planes;
* ``trees``: the job's trees are read back in raw space with each node's
  ``default_left`` (``trees_of_missing``);
* ``reference``: ``gbdt_bestfirst_missing.BestFirstMissing`` over the
  family's own bins;
* ``facts``: ``facts["missing"]`` holds the train rows' share of empty
  cells, the window's numeric split nodes by the side their empty cells
  take (counter ``dryad_split_default_total{dir}``) and the share that
  learned "right", the depth cap the program ran (gauge
  ``dryad_leafwise_depth_cap``), and on a traced run ``missing_device_ms``
  (``benchmark/layer_metrics/missing_device_ms.py``); ``facts["flips"]``
  the followed trees' split nodes and the largest losses over the split's
  terms, as the categorical cell's.

A program that keeps no such counter gives ``{}`` and ``None`` for them and
runs the cell all the same.  Before any data is made the runner refuses
(exit 2) a program whose growth policy gives this shape another depth cap
than the configuration's: that program would run another job.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import re
import shutil
import tempfile
import time
from typing import Callable

import numpy as np

from benchmark.counts import gbdt_bestfirst as counts_bestfirst
from benchmark.harness import device as devmod
from benchmark.harness.clock import CompileClock, StopJob, WindowClock
from benchmark.harness.result import judge
from benchmark.runners import train_job
from benchmark.runners.train_job import make_data, program_temp_bytes, read_layers, say
from benchmark.runners.train_job_bestfirst import (COUNTERS, NUMBERS, job_params,  # noqa: F401
                                                   leafwise_counters)
from benchmark.runners.train_job_rank import check_policy, policy_choices

DEFAULT_DIRS = "dryad_split_default_total"


def missing_counters() -> dict:
    """The program's numeric split nodes by the side of their empty cells as
    they stand; {} from a program that keeps none."""
    from dryad_tpu.obs.registry import default_registry

    series = default_registry().snapshot()["counters"].get(DEFAULT_DIRS) or {}
    out = {}
    for label, value in series.items():
        side = re.search(r'dir="([^"]*)"', str(label))
        if side:
            out[side.group(1)] = out.get(side.group(1), 0.0) + float(value)
    return out


def trees_of_missing(booster) -> list:
    """The job's trees in raw space, as its model applies them to raw rows:
    bin threshold ``t`` means ``x <= edges[t - 1]`` (``-inf`` for ``t = 0``:
    no value goes left), and an empty cell goes where ``default_left``
    says."""
    from benchmark.reference.gbdt_bestfirst_missing import MissTree

    trees = train_job.trees_of(booster)
    return [MissTree(*dataclasses.astuple(tree),
                     np.append(np.asarray(booster.default_left[t], bool), True))
            for t, tree in enumerate(trees)]


@dataclasses.dataclass
class Hooks:
    """What a best-first cell's table brings to ``run_with``.

    ``dataset(dryad, config, params, q, y, qv, yv)`` -> ``(ds, vds, note)``;
    ``trees(booster)`` -> the job's trees as the reference reads them;
    ``reference(params, q, y, qv, yv, depth_cap)`` -> the follower;
    ``counters()`` -> the program's own counters at the window's edges;
    ``facts(q, opened, closed)`` -> the cell's own facts, and
    ``traced(facts_of_cell, layer_facts)`` adds what a trace reads."""
    dataset: Callable
    trees: Callable
    reference: Callable
    counters: Callable
    facts: Callable
    traced: Callable


def _dataset(dryad, config, params, q, y, qv, yv):
    from benchmark.datagen import bosch

    ds = dryad.Dataset(bosch.as_float(q), y, max_bins=params["max_bins"])
    vds = ds.bind(bosch.as_float(qv), yv)
    return ds, vds, f"has_missing {ds.has_missing}"


def _reference(params, q, y, qv, yv, depth_cap):
    from benchmark.reference.gbdt_bestfirst_missing import BestFirstMissing, Rows

    return BestFirstMissing(params, Rows(q, y), Rows(qv, yv), depth_cap)


def _facts(q, opened, closed):
    dirs = {side: closed[side] - opened.get(side, 0.0) for side in closed}
    total = sum(dirs.values())
    return {"empty_share": float(np.mean(q == 0)), "split_dirs": dirs,
            "learned_right_share": dirs.get("right", 0.0) / total if total else None}


def _traced(missing, facts):
    from benchmark.layer_metrics import missing_device_ms

    missing["missing_device_ms"] = missing_device_ms.read(facts)


MISSING = Hooks(dataset=_dataset, trees=trees_of_missing, reference=_reference,
                counters=missing_counters, facts=_facts, traced=_traced)


def run(cell, args, t_start: float) -> dict:
    return run_with(MISSING, cell, args, t_start)


def run_with(hooks: Hooks, cell, args, t_start: float) -> dict:
    rehearsal = bool(args.rehearse_cpu)
    config, traffic = cell.config, cell.traffic
    params, depth_cap = job_params(config, rehearsal)
    data = {**config["data"], **(config.get("rehearsal", {}) if rehearsal else {})}
    check_policy(params, depth_cap, int(data["features"]), int(data["train_rows"]))
    devices = devmod.check(cell.chips, rehearsal)
    os.environ["DRYAD_PROG_MEMORY"] = "1"
    compile_clock = CompileClock()

    import jax

    import dryad_tpu as dryad
    import dryad_tpu.engine as engine
    from dryad_tpu.checkpoint import Checkpointer
    from dryad_tpu.obs import spans as obs_spans

    cache_dir = engine.place_compile_cache()
    say(f"[{cell.name}] {'CPU REHEARSAL, no device result; ' * rehearsal}"
        f"device {devices[0].device_kind} x{len(devices)}; compile cache {cache_dir}")

    # ---- set-up: data ----------------------------------------------------
    t0 = time.perf_counter()
    q, y, qv, yv = make_data(config, args.seed, rehearsal)
    t_gen = time.perf_counter() - t0
    ds, vds, note = hooks.dataset(dryad, config, params, q, y, qv, yv)
    data_prep_s = time.perf_counter() - t0
    say(f"[{cell.name}] data {q.shape} + valid {qv.shape}, positives {float(y.mean()):.5f}, "
        f"{note}: generated in {t_gen:.1f}s, sketched and binned in {data_prep_s - t_gen:.1f}s")

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
        marks["cell_open"] = hooks.counters()
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
        marks["cell_close"] = hooks.counters()
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
    policy = policy_choices()

    # ---- read the checkpoint back, free the program's state ---------------
    booster, ckpt_iter = None, 0
    if os.path.isdir(ckdir):
        latest = Checkpointer(ckdir, every=every).latest()
        if latest is not None:
            booster, ckpt_iter = latest
    job = None
    if booster is not None:
        job = {"trees": hooks.trees(booster), "init_score": float(booster.init_score[0]),
               "evals": dict(clock.evals)}
    features = int(ds.num_features)
    bins = int(ds.mapper.total_bins)
    bin_bytes = int(ds.X_binned.dtype.itemsize)
    del ds, vds, booster, kw
    gc.collect()

    # ---- the reference follows the job -------------------------------------
    due = clock.iters_done - clock.iters_done % every    # the last boundary the job passed
    numbers = {"job_died": 1.0 if died else 0.0,
               "checkpoint_iters_gap": float(abs(ckpt_iter - due))}
    t_ref = time.perf_counter()
    detail = {}
    passes = counts_bestfirst.level_passes(params["num_leaves"])
    rows_needed_share = None
    if job is not None and job["trees"]:
        ref = hooks.reference(params, q, y, qv, yv, depth_cap)
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
    own = hooks.facts(q, marks.get("cell_open", {}), marks.get("cell_close", {}))
    own["dryad_leafwise_depth_cap"] = leafwise.get("depth_cap")
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
                     "missing": own, "policy": policy,
                     "flips": {key: detail.get(key) for key in
                               ("split_nodes", "strict_flip_share", "flip_rel")},
                     "last_valid_metric": detail.get("last_valid_metric")}}

    if args.trace:
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
        hooks.traced(own, facts)
    shutil.rmtree(tmp, ignore_errors=True)
    return out
