"""The runner ``train_job_bestfirst``: ``train_job``'s job, followed by the
best-first reference.

The job, the clock, the window, the memory reading, the checkpoint read-back
and the traced run's reduction are ``train_job``'s own, imported (its
docstring has the picture); what differs is what a leaf-wise configuration
needs:

* the reference is ``benchmark/reference/gbdt_bestfirst.py``, given the
  configuration's ``depth_cap``; it adds ``order_gain_gap`` to the numbers
  and prints ``cap_stopped_steps`` and the trees' depths;
* ``facts["shape"]["depth"]`` is the level passes of the algorithm's least
  work (``benchmark/counts/gbdt_bestfirst.py``: 9 for 255 leaves), not the
  levels this implementation expands, so that ``step_mfu`` and
  ``hist_roofline`` read the cell unedited and give no credit for expanded
  nodes the selection drops; ``facts["rows_needed_share"]`` is what the
  window's trees really needed, from their covers, over that ceiling;
* ``facts["leafwise"]`` holds the window's part of the program's counters
  ``dryad_leafwise_{expanded,selected}_splits_total`` and the gauge
  ``dryad_leafwise_depth_cap``, where the program keeps them.

A ``benchmark`` issue should name the reference and the count in the traffic
file and fold the two runners (ROADMAP Queue 3).
"""

from __future__ import annotations

import gc
import os
import shutil
import tempfile
import time

import numpy as np

from benchmark.counts import gbdt_bestfirst as counts_bestfirst
from benchmark.harness import device as devmod
from benchmark.harness.clock import CompileClock, StopJob, WindowClock
from benchmark.harness.result import judge
from benchmark.runners import train_job
from benchmark.runners.train_job import (make_data, program_temp_bytes, read_layers, say,
                                         trees_of)

# what the best-first reference's ``follow`` and ``follow_window`` give; a
# cell's limits say which are compared
NUMBERS = ("init_score_gap", "order_gain_gap", "split_flip_share", "leaf_value_gap",
           "valid_metric_gap", "window_cover_gap", "window_leaf_value_gap",
           "window_root_gain_gap")
COUNTERS = ("dryad_leafwise_expanded_splits_total", "dryad_leafwise_selected_splits_total")


def leafwise_counters() -> dict:
    """The program's leaf-wise counters as they stand; {} from a program
    that keeps none."""
    from dryad_tpu.obs.registry import default_registry

    snap = default_registry().snapshot()
    out = {name: float(sum(snap["counters"][name].values()))
           for name in COUNTERS if snap["counters"].get(name)}
    cap = snap["gauges"].get("dryad_leafwise_depth_cap")
    if cap:
        out["depth_cap"] = float(max(cap.values()))
    return out


def job_params(config: dict, rehearsal: bool) -> tuple[dict, int]:
    """The job's parameters and the depth cap the reference is given.  A
    rehearsal may state smaller ones (``rehearsal.params``,
    ``rehearsal.depth_cap``): at 255 leaves the expansion's 2048 columns take
    the CPU's interpreter minutes an iteration."""
    params, cap = dict(config["params"]), int(config["depth_cap"])
    if rehearsal:
        small = config.get("rehearsal", {})
        params.update(small.get("params", {}))
        cap = int(small.get("depth_cap", cap))
    return params, cap


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
    say(f"[{cell.name}] {'CPU REHEARSAL, no device result; ' * rehearsal}"
        f"device {devices[0].device_kind} x{len(devices)}; compile cache {cache_dir}")

    # ---- set-up: data ----------------------------------------------------
    t0 = time.perf_counter()
    q, y, qv, yv = make_data(config, args.seed, rehearsal)
    t_gen = time.perf_counter() - t0
    ds = dryad.Dataset(q.astype(np.float32), y, max_bins=params["max_bins"])
    vds = ds.bind(qv.astype(np.float32), yv)
    data_prep_s = time.perf_counter() - t0
    say(f"[{cell.name}] data {q.shape} + valid {qv.shape}: generated in {t_gen:.1f}s, "
        f"sketched and binned in {data_prep_s - t_gen:.1f}s")

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

    # ---- read the checkpoint back, free the program's state ---------------
    booster, ckpt_iter = None, 0
    if os.path.isdir(ckdir):
        latest = Checkpointer(ckdir, every=every).latest()
        if latest is not None:
            booster, ckpt_iter = latest
    job = None
    if booster is not None:
        job = {"trees": trees_of(booster), "init_score": float(booster.init_score[0]),
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
        from benchmark.reference.gbdt_bestfirst import BestFirst, Rows

        ref = BestFirst(params, Rows(q, y), Rows(qv, yv), depth_cap)
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
    say(f"[{cell.name}] reference followed the job in {ref_s:.1f}s: "
        f"{ {k: v for k, v in detail.items() if k not in ('per_tree', 'window_trees')} }")
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
    memory_peak = live + temp
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": devmod.describe(devices, memory_peak),
           "compared": compared, "numbers": numbers, "job": job, "rehearsal": rehearsal,
           "facts": {"window_s": window_s, "window_iters": window_iters,
                     "chunks": [c["n"] for c in clock.window_chunks], "ckpt_iter": ckpt_iter,
                     "reference_s": ref_s, "run_s": t_end - t_start,
                     "level_passes": passes, "rows_needed_share": rows_needed_share,
                     "cap_stopped_steps": detail.get("cap_stopped_steps"),
                     "tree_depths": detail.get("tree_depths"), "leafwise": leafwise}}

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
    shutil.rmtree(tmp, ignore_errors=True)
    return out
