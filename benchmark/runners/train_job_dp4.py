"""The runner ``train_job_dp4``: the best-first runner's job, data-parallel
over the cell's chips.

The job, the clock, the window, the memory reading, the checkpoint read-back
and the traced run's reduction are ``train_job``'s and
``train_job_bestfirst``'s own, imported; what differs is what rows sharded
over a mesh need:

* the job is ``dryad.train(..., mesh=make_mesh(devices))`` over the cell's
  ``chips`` devices: rows sharded, histograms all-reduced, every chip growing
  the same tree;
* the program's growth policy is asked first, before any data is made,
  whether it gives this shape on this many shards the depth cap the
  configuration states (``check_policy``); a program whose envelope counts the
  global rows on one device (every commit before PR 34) sends the job to the
  sequential grower, which is another job: refused at once, exit 2;
* ``facts["shape"]`` and ``facts["peaks"]`` are **one chip's**: its share of
  the train rows against one chip's peaks, as ``trace.reduce`` averages
  device time over the devices, so ``step_mfu`` and ``hist_roofline`` are
  shares of one chip's roofline.  The all-reduce is no part of the least
  work.  Memory is the fullest device's;
* the reference is ``gbdt_bestfirst_dp.BestFirstSpread``: the plain
  best-first reference over all rows as one table, its histogram passes
  spread over the same chips once the program's state is freed;
* ``facts["comm"]`` holds the program's ``dryad_comm_*`` gauges (the
  exchange's arm, shards, calls and payload bytes an iteration) and
  ``facts["policy"]`` every gate the program resolved.

A rehearsal (``--rehearse-cpu``) shards over as many host devices as the cell
has chips; it asks XLA for them unless ``XLA_FLAGS`` is already set.
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
from benchmark.runners.train_job import (make_data, program_temp_bytes, read_layers, say,
                                         trees_of)
from benchmark.runners.train_job_bestfirst import (COUNTERS, NUMBERS, job_params,  # noqa: F401
                                                   leafwise_counters)
from benchmark.runners.train_job_rank import NotThisConfiguration, policy_choices

COMM_GAUGES = ("dryad_comm_psum_bytes_per_iter", "dryad_comm_collective_calls_per_iter",
               "dryad_comm_reduce_scatter_bytes_per_iter",
               "dryad_comm_all_gather_bytes_per_iter",
               "dryad_comm_collective_bytes_per_iter")


def data_sizes(config: dict, rehearsal: bool) -> dict:
    """The sizes ``train_job.make_data`` will draw."""
    return {**config["data"], **(config.get("rehearsal", {}) if rehearsal else {})}


def check_policy(params: dict, depth_cap: int, features: int, rows: int, shards: int) -> None:
    """The configuration states the depth cap its job is grown under
    (``depth_cap``, ``guarantee``), on ``shards`` chips.  A program whose
    growth policy gives this shape another one is not running this
    configuration: refuse at once, before any data is made, so that a
    comparison sees a clean failure and not a run of another job."""
    from dryad_tpu.config import effective_depth_params, make_params

    try:
        got = effective_depth_params(make_params(params), features, int(params["max_bins"]),
                                     rows, shards).max_depth
    except TypeError as e:
        raise NotThisConfiguration(
            f"the program's growth policy takes no shard count ({e}): it counts the "
            f"global {rows} rows on one device") from e
    if got != depth_cap:
        raise NotThisConfiguration(
            f"the program's growth policy gives max_depth {got} at {rows} x {features} on "
            f"{shards} shards, the configuration states depth_cap {depth_cap}")


def comm_gauges() -> dict:
    """The program's gauges of the exchange as they stand: per gauge its
    value, and the labels (arm, growth, shards) they share; {} from a program
    that keeps none or a job with no mesh."""
    from dryad_tpu.obs.registry import default_registry

    gauges = default_registry().snapshot()["gauges"]
    out = {}
    for name in COMM_GAUGES:
        for label, value in (gauges.get(name) or {}).items():
            out[name] = float(value)
            for key, val in re.findall(r'(\w+)="([^"]*)"', str(label)):
                out[key] = val
    return out


def run(cell, args, t_start: float) -> dict:
    rehearsal = bool(args.rehearse_cpu)
    config, traffic = cell.config, cell.traffic
    params, depth_cap = job_params(config, rehearsal)
    sizes = data_sizes(config, rehearsal)
    check_policy(params, depth_cap, int(sizes["features"]), int(sizes["train_rows"]), cell.chips)
    if rehearsal:
        os.environ.setdefault("XLA_FLAGS",
                              f"--xla_force_host_platform_device_count={cell.chips}")
    devices = devmod.check(cell.chips, rehearsal)
    os.environ["DRYAD_PROG_MEMORY"] = "1"
    compile_clock = CompileClock()

    import jax

    import dryad_tpu as dryad
    import dryad_tpu.engine as engine
    from dryad_tpu.checkpoint import Checkpointer
    from dryad_tpu.engine.distributed import make_mesh
    from dryad_tpu.obs import spans as obs_spans

    if rehearsal:
        devices = jax.devices()[:cell.chips]
        if len(devices) < cell.chips:
            raise devmod.NoChip(f"the rehearsal wants {cell.chips} host devices, jax gives "
                                f"{len(devices)} (XLA_FLAGS={os.environ.get('XLA_FLAGS')!r})")
    mesh = make_mesh(devices)
    cache_dir = engine.place_compile_cache()
    say(f"[{cell.name}] {'CPU REHEARSAL, no device result; ' * rehearsal}"
        f"device {devices[0].device_kind} x{len(devices)}, one mesh; compile cache {cache_dir}")

    # ---- set-up: data ----------------------------------------------------
    t0 = time.perf_counter()
    q, y, qv, yv = make_data(config, args.seed, rehearsal)
    t_gen = time.perf_counter() - t0
    ds = dryad.Dataset(q.astype(np.float32), y, max_bins=params["max_bins"])
    vds = ds.bind(qv.astype(np.float32), yv)
    data_prep_s = time.perf_counter() - t0
    say(f"[{cell.name}] data {q.shape} + valid {qv.shape}, positives {float(y.mean()):.4f}: "
        f"generated in {t_gen:.1f}s, sketched and binned in {data_prep_s - t_gen:.1f}s")

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
    kw = dict(valid_sets=[vds], backend="tpu", mesh=mesh, callbacks=[clock.on_iter],
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

    # ---- what the fullest device held --------------------------------------
    live = devmod.live_peak_bytes(devices)
    temp = program_temp_bytes()
    limit = devmod.bytes_limit(devices)
    say(f"[{cell.name}] device memory, the fullest of {len(devices)}: live peak {live} + "
        f"training program temporaries {temp} = {live + temp} of {limit} bytes")
    comm, policy = comm_gauges(), policy_choices()

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
        from benchmark.reference.gbdt_bestfirst_dp import BestFirstSpread, Rows

        ref = BestFirstSpread(params, Rows(q, y), Rows(qv, yv), depth_cap, devices)
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
    shard_rows = -(-int(q.shape[0]) // len(devices))
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": devmod.describe(devices, memory_peak),
           "compared": compared, "numbers": numbers, "job": job, "rehearsal": rehearsal,
           "facts": {"window_s": window_s, "window_iters": window_iters,
                     "chunks": [c["n"] for c in clock.window_chunks], "ckpt_iter": ckpt_iter,
                     "reference_s": ref_s, "run_s": t_end - t_start,
                     "level_passes": passes, "rows_needed_share": rows_needed_share,
                     "cap_stopped_steps": detail.get("cap_stopped_steps"),
                     "tree_depths": detail.get("tree_depths"), "leafwise": leafwise,
                     "shard_rows": shard_rows, "comm": comm, "policy": policy}}

    if args.trace:
        window_spans = [sp for sp in span_log
                        if clock.t_open is not None and sp[1] >= clock.t_open
                        and sp[1] + sp[2] <= (clock.t_close or t_end) + 1e-3]
        facts = {
            "spans": window_spans, "window_s": window_s, "window_iters": window_iters,
            "window_chunks": len(clock.window_chunks),
            # one chip's share of the rows against one chip's peaks
            "shape": {"rows": shard_rows, "features": features, "bins": bins,
                      "depth": passes, "bin_bytes": bin_bytes, "trees": 1},
            "peaks": None if rehearsal else devmod.peaks(devices[0].device_kind),
            "memory": {"live_peak_bytes": live, "program_temp_bytes": temp, "bytes_limit": limit},
            "compile": {"setup_compile_s": marks.get("setup_compile_s"),
                        "window_compiles": marks.get("window_compile", (0.0, 0))[1]},
            "data_prep_s": data_prep_s,
            "leafwise": leafwise, "comm": comm,
        }
        read_layers(cell, args, trace_dir, facts, out)
    shutil.rmtree(tmp, ignore_errors=True)
    return out
