"""The runner ``train_job``: one ``dryad.train`` job, stopped by the clock.

What a run does (PERF.md section 3 has the picture):

1. Set-up.  Data from ``--seed`` (``benchmark/datagen/<family>.py``), handed
   to the program as float32 columns: ``Dataset(...)`` sketches and bins
   them, ``ds.bind(...)`` bins the valid set.  The compile cache is the one
   ``dryad_tpu.engine`` places (``JAX_COMPILATION_CACHE_DIR`` if set, else
   ``<checkout>/.jax_cache``).  ``DRYAD_PROG_MEMORY=1`` is the one variable
   set: it makes the program record ``memory_analysis()`` of the chunk
   program at chunk 0's compile boundary, and chooses nothing.
2. The job: ``dryad.train(params, ds, valid_sets=[vds], backend="tpu",
   checkpoint_dir=..., checkpoint_every=..., callbacks=[clock.on_iter],
   chunk_hook=clock.on_dispatch)`` with the configuration's ``num_trees``
   always (another count is another program).  The first ``warmup_chunks``
   chunks are set-up (compile or cache retrieval, then the trainer's own
   calibration); the window opens when the last of them is complete.
3. The window closes at the first chunk completion at or after
   ``--seconds``; the job is stopped at the next dispatch, after that
   chunk's checkpoint is on disk.  With ``--trace 1`` the profiler runs
   from the open and the window is the traffic's ``trace_seconds`` long (and
   at least ``trace_chunks`` chunks).
4. After the window: device memory is read, the last checkpoint is loaded
   from disk, the program's state is freed, and the plain reference
   (``benchmark/reference/gbdt.py``) follows the first
   ``reference_iterations`` trees of what the timed job grew (every level's
   histograms), the last ``window_iterations`` trees of the checkpoint,
   which a full run grew inside the window (the rows of every node, every
   leaf's value, the root's split), and scores all its trees on the valid
   set.  ``correct`` is each number under its limit.
"""

from __future__ import annotations

import gc
import importlib
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from benchmark.harness import device as devmod
from benchmark.harness import trace as tracemod
from benchmark.harness.clock import CompileClock, StopJob, WindowClock
from benchmark.harness.manifest import metric_reader
from benchmark.harness.result import judge


# what the reference's ``follow`` and ``follow_window`` give; a cell's limits say
# which are compared
NUMBERS = ("init_score_gap", "level_gain_gap", "split_flip_share", "leaf_value_gap",
           "valid_metric_gap", "window_cover_gap", "window_leaf_value_gap",
           "window_root_gain_gap")


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_data(config: dict, seed: int, rehearsal: bool):
    data = dict(config["data"])
    if rehearsal:
        data.update(config.get("rehearsal", {}))
    family = importlib.import_module("benchmark.datagen." + data["family"])
    q, y = family.make(seed, data["train_rows"], data["features"], stream=0)
    qv, yv = family.make(seed, data["valid_rows"], data["features"], stream=1)
    return q, y, qv, yv


def trees_of(booster) -> list:
    """The job's trees in raw feature space, as its model applies them to
    raw rows: bin threshold t means ``x <= edges[t - 1]``."""
    from benchmark.reference.gbdt import Tree

    trees = []
    feats = booster.mapper.features
    for t in range(booster.num_total_trees):
        feature = np.append(np.asarray(booster.feature[t], np.int32), -1)
        tb = np.append(np.asarray(booster.threshold[t], np.int64), 0)
        thr = np.full(feature.shape, -np.inf, np.float32)
        for n in np.flatnonzero(feature >= 0):
            if tb[n] >= 1:
                thr[n] = feats[feature[n]].edges[tb[n] - 1]
        trees.append(Tree(feature, thr,
                          np.append(np.asarray(booster.left[t], np.int32), 0),
                          np.append(np.asarray(booster.right[t], np.int32), 0),
                          np.append(np.asarray(booster.value[t], np.float64), 0.0),
                          np.append(np.asarray(booster.cover[t], np.float64), 0.0)))
    return trees


def program_temp_bytes() -> int:
    """``memory_analysis().temp_size_in_bytes`` of the training program the
    job dispatched (the chunk program, or the step program where the trainer
    chose per-iteration dispatch), as the program's own registry recorded it
    at the compile boundary; 0 where it did not."""
    from dryad_tpu.obs.registry import default_registry

    series = default_registry().snapshot()["gauges"].get("dryad_prog_memory_bytes", {})
    vals = [v for lbl, v in series.items()
            if 'kind="temp"' in str(lbl) and 'program="train.' in str(lbl)]
    return int(max(vals)) if vals else 0


def train_entry(*args, **kw):
    """The entry the window drives.  A seam for the tests that break the
    timed path underneath; the benchmark never passes through anything else."""
    import dryad_tpu as dryad

    return dryad.train(*args, **kw)


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
    params = dict(config["params"])
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
        train_entry(params, ds, **kw)
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
    if job is not None and job["trees"]:
        from benchmark.reference.gbdt import Reference, Rows

        ref = Reference(params, Rows(q, y), Rows(qv, yv))
        detail = ref.follow(job, int(traffic["reference_iterations"]))
        detail.update(ref.follow_window(job, int(traffic["window_iterations"])))
        for key in NUMBERS:
            numbers[key] = float(detail[key])
        del ref
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
    memory_peak = live + temp
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": devmod.describe(devices, memory_peak),
           "compared": compared, "numbers": numbers, "job": job, "rehearsal": rehearsal,
           "facts": {"window_s": window_s, "window_iters": window_iters,
                     "chunks": [c["n"] for c in clock.window_chunks], "ckpt_iter": ckpt_iter,
                     "reference_s": ref_s, "run_s": t_end - t_start}}

    if args.trace:
        window_spans = [sp for sp in span_log
                        if clock.t_open is not None and sp[1] >= clock.t_open
                        and sp[1] + sp[2] <= (clock.t_close or t_end) + 1e-3]
        facts = {
            "spans": window_spans, "window_s": window_s, "window_iters": window_iters,
            "window_chunks": len(clock.window_chunks),
            "shape": {"rows": int(q.shape[0]), "features": features, "bins": bins,
                      "depth": int(params["max_depth"]), "bin_bytes": bin_bytes, "trees": 1},
            "peaks": None if rehearsal else devmod.peaks(devices[0].device_kind),
            "memory": {"live_peak_bytes": live, "program_temp_bytes": temp, "bytes_limit": limit},
            "compile": {"setup_compile_s": marks.get("setup_compile_s"),
                        "window_compiles": marks.get("window_compile", (0.0, 0))[1]},
            "data_prep_s": data_prep_s,
        }
        read_layers(cell, args, trace_dir, facts, out)
    shutil.rmtree(tmp, ignore_errors=True)
    return out


def read_layers(cell, args, trace_dir: str, facts: dict, out: dict) -> None:
    """The traced run's half of the result: reduce the trace, let each of the
    cell's per-layer readers take its number from ``facts``, and put the
    table, ``busy_s``/``window_s`` and the breakdown into ``out``.  A
    rehearsal runs the readers too and prints none of their numbers."""
    window_s = facts["window_s"]
    readers = {m["name"]: metric_reader(m["name"]) for m in cell.per_layer}
    kernels = {}
    for mod in readers.values():
        kernels.update(getattr(mod, "KERNELS", {}))
    reduced = {}
    if os.path.isdir(trace_dir):
        events = tracemod.load_xplane(trace_dir)
        reduced = tracemod.reduce(events, kernels)
        if reduced:
            reduced["idle_by"] = idle_by(window_s, reduced, facts["spans"])
    facts["trace"] = reduced
    units = {m["name"]: m["unit"] for m in cell.per_layer}
    table = {}
    for name, mod in readers.items():
        value = mod.read(facts)
        if value is not None:
            table[name] = {"value": float(value), "unit": units[name]}
    out["facts"]["layer_metrics_read"] = sorted(table)
    out["facts"]["trace"] = {k: v for k, v in reduced.items() if k != "top_ops"}
    if out["rehearsal"]:
        return
    out["metrics"] = table
    if reduced:
        out["device"]["busy_s"] = reduced["busy_s"]
        out["device"]["window_s"] = window_s
        gaps = sorted(reduced["idle_by"].items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": reduced["top_ops"],
                            "idle_gaps": [[k, v] for k, v in gaps]}


def idle_by(window_s: float, reduced: dict, spans: list) -> dict:
    """The window's idle seconds by what the host was doing.  Inside a
    program: ``in_program``.  Between programs the job runs in lockstep, so
    the device waits while the host materialises and writes a checkpoint
    (span ``train.fetch.checkpoint``); what is left between programs is
    the eval fetch's tail, the callbacks and the next dispatch."""
    between = max(window_s - reduced["programs_s"], 0.0)
    ckpt = min(sum(d for p, _, d in spans if p.endswith("train.fetch.checkpoint")), between)
    return {"in_program": max(reduced["programs_s"] - reduced["busy_s"], 0.0),
            "train.fetch.checkpoint": ckpt, "fetch_and_dispatch": between - ckpt}
