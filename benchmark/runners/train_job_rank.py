"""The runner ``train_job_rank``: ``train_job``'s job for a LambdaMART
configuration, followed by the λ-gradient reference.

The job, the clock, the window, the memory reading, the checkpoint read-back
and the traced run's reduction are ``train_job``'s own (its docstring has the
picture), the leaf-wise counters, parameters and count of least work
``train_job_bestfirst``'s; what differs is what rows in query groups need:

* the data family returns query lengths beside the rows
  (``benchmark/datagen/mslr.py``), and the job is ``Dataset(X, y,
  group=lengths)`` with the valid set bound in its own groups;
* the reference is ``benchmark/reference/gbdt_rank.py``: best-first growth
  under its own λ-gradients, NDCG@k in float64;
* ``facts["rank"]`` holds the program's gauges of the λ-plan where it keeps
  them (``dryad_rank_queries``, ``dryad_rank_plan_width``,
  ``dryad_rank_pair_cells{kind}``) and ``facts["policy"]`` every dispatch
  gate the program resolved (``dryad_tpu.policy.gates.decisions``);
* ``facts["shape"]`` states the histogram work alone (rows, features, bins,
  ``level_passes(num_leaves)``): the λ-pass is no part of the least work, so
  ``step_mfu`` and ``hist_roofline`` read the cell unedited.

A ``benchmark`` issue should fold the three runners (ROADMAP Queue 3).
"""

from __future__ import annotations

import gc
import importlib
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
from benchmark.runners.train_job import program_temp_bytes, read_layers, say, trees_of
from benchmark.runners.train_job_bestfirst import (COUNTERS, NUMBERS, job_params,
                                                   leafwise_counters)

RANK_GAUGES = ("dryad_rank_queries", "dryad_rank_plan_width", "dryad_rank_pair_cells")


def data_sizes(config: dict, rehearsal: bool) -> dict:
    """The configuration's ``data``, with the rehearsal's sizes laid over it."""
    return {**config["data"], **(config["rehearsal"]["data"] if rehearsal else {})}


def make_data(config: dict, seed: int, rehearsal: bool):
    """``(q, y, lengths)`` of the train set and of the valid set."""
    data = data_sizes(config, rehearsal)
    family = importlib.import_module("benchmark.datagen." + data["family"])
    return tuple(family.make(seed, data[rows], data["features"], stream=stream,
                             query_length=data["query_length"])
                 for stream, rows in enumerate(("train_rows", "valid_rows")))


class NotThisConfiguration(devmod.NoChip):
    """The program would not run the configuration as its file states it."""


def check_policy(params: dict, depth_cap: int, features: int, rows: int) -> None:
    """The configuration states the depth cap its job is grown under
    (``depth_cap``, ``guarantee``).  A program whose growth policy gives this
    shape another (one that sends it to the sequential grower with no cap, as
    the program did before PR 32) is not running this configuration: refuse
    at once, before any data is made, so that a comparison sees a clean
    failure and not a run of another job."""
    from dryad_tpu.config import effective_depth_params, make_params

    got = effective_depth_params(make_params(params), features, int(params["max_bins"]),
                                 rows).max_depth
    if got != depth_cap:
        raise NotThisConfiguration(
            f"the program's growth policy gives max_depth {got} at {rows} x {features}, "
            f"the configuration states depth_cap {depth_cap}")


def rank_gauges() -> dict:
    """The program's gauges of the λ-plan as they stand; {} from a program
    that keeps none."""
    from dryad_tpu.obs.registry import default_registry

    gauges = default_registry().snapshot()["gauges"]
    out = {}
    for name in RANK_GAUGES:
        for label, value in (gauges.get(name) or {}).items():
            kind = re.search(r'kind="([^"]*)"', str(label))
            out[name + ("." + kind.group(1) if kind else "")] = float(value)
    return out


def policy_choices() -> dict:
    """Every dispatch gate the program resolved in this process, by arm."""
    from dryad_tpu.policy.gates import decisions

    return {gate: rec["arm"] for gate, rec in decisions().items()}


def run(cell, args, t_start: float) -> dict:
    rehearsal = bool(args.rehearse_cpu)
    config, traffic = cell.config, cell.traffic
    params, depth_cap = job_params(config, rehearsal)
    sizes = data_sizes(config, rehearsal)
    check_policy(params, depth_cap, int(sizes["features"]), int(sizes["train_rows"]))
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
    (q, y, lengths), (qv, yv, lengths_v) = make_data(config, args.seed, rehearsal)
    t_gen = time.perf_counter() - t0
    ds = dryad.Dataset(q.astype(np.float32), y, group=lengths, max_bins=params["max_bins"])
    vds = ds.bind(qv.astype(np.float32), yv, group=lengths_v)
    data_prep_s = time.perf_counter() - t0
    say(f"[{cell.name}] data {q.shape} in {lengths.size} queries (longest {int(lengths.max())}) "
        f"+ valid {qv.shape} in {lengths_v.size}: generated in {t_gen:.1f}s, "
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
        from benchmark.reference.gbdt_rank import RankBestFirst, RankRows

        ref = RankBestFirst(params, RankRows(q, y, lengths), RankRows(qv, yv, lengths_v),
                            depth_cap)
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
                     "tree_depths": detail.get("tree_depths"), "leafwise": leafwise,
                     "rank": rank_gauges(), "policy": policy_choices(),
                     "memory": {"live_peak_bytes": live, "program_temp_bytes": temp,
                                "bytes_limit": limit}}}

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
