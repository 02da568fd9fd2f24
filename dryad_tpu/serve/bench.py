"""Closed-loop serving benchmark (the engine behind scripts/bench_serve.py).

``clients`` threads each run a closed loop — submit a request of a
random size, wait for the answer, repeat — against one PredictServer,
so concurrency (and therefore batch fill) is controlled exactly.

Warmup touches EVERY bucket the cache can ever produce (cache.buckets()),
not just the request sizes: coalescing means batch totals land on
arbitrary buckets up to the row cap, so warming only the request sizes
would leave cold buckets for the measured phase.  Routing to the sharded
entry family is a pure function of the bucket, so the same warmup warms
both shard arms.  After that structural warmup, a warm cache can never
compile again — ``recompiles_after_warmup`` must be 0, and
tests/test_serve.py asserts it on a forced-CPU run (scripts/ci.sh smokes
it across the bucketed AND sharded arms).

Measurement discipline follows bench.py / CLAUDE.md: the closed loop
runs ``arms`` times and the report carries the per-arm spread
(max/min - 1) next to the headline rows/s — a spread over 5% means the
capture is suspect (host contention, cold cache) and the
report says so (``suspect_capture``) instead of letting a noisy point
masquerade as a trend.  ``run_bench_compare`` measures the overlapped
dispatch pipeline against the strictly serial loop on otherwise
identical servers and reports the speedup (ISSUE r7 acceptance:
pipeline ≥ 1.3× serial on CPU).
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Sequence

import numpy as np

from dryad_tpu.booster import Booster
from dryad_tpu.serve.server import PredictServer

SPREAD_SUSPECT = 0.05    # per-arm spread above this flags the capture


def run_bench(model, *, backend: str = "cpu", clients: int = 4,
              duration_s: float = 2.0, sizes: Sequence[int] = (1, 3, 9, 17, 40),
              max_batch_rows: int = 256, max_wait_ms: float = 1.0,
              queue_size: int = 1024, min_bucket: int = 8, seed: int = 0,
              pipeline_depth: int = 2, sharded="auto",
              sharded_threshold: Optional[int] = None, arms: int = 1,
              feature_pool: Optional[np.ndarray] = None,
              drift="auto", drift_window: int = 4096,
              verbose: bool = False) -> dict:
    """Run the closed loop; returns the stats snapshot plus bench fields
    (throughput, per-arm spread, recompiles_after_warmup).  ``model`` is a
    Booster or a model path (binary or text)."""
    booster = model if isinstance(model, Booster) else Booster.load_any(model)
    server = PredictServer(backend=backend, max_batch_rows=max_batch_rows,
                           max_wait_ms=max_wait_ms, queue_size=queue_size,
                           min_bucket=min_bucket,
                           pipeline_depth=pipeline_depth, sharded=sharded,
                           sharded_threshold=sharded_threshold,
                           drift=drift, drift_window=drift_window)
    server.registry.add(booster)
    rng = np.random.default_rng(seed)
    if feature_pool is None:
        feature_pool = rng.standard_normal(
            (max(int(max_batch_rows), 512), booster.mapper.num_features)
        ).astype(np.float32)
    pool_n = feature_pool.shape[0]
    sizes = [int(s) for s in sizes if 0 < int(s) <= pool_n]

    with server:
        # ---- structural warmup: one request per possible bucket ------------
        for b in server.cache.buckets():
            server.predict(feature_pool[:min(b, pool_n)])
        # arm the recompile tripwire: from here on a cold compiled-entry
        # key is not just counted in recompiles_after_warmup below but
        # fires dryad_recompile_unexpected_total and degrades /healthz
        server.warmup_complete()
        warm = server.stats()
        compiles_at_warmup = warm["cache_compiles"]
        if verbose:
            print(f"warmed {warm['compiled_buckets']} buckets "
                  f"({compiles_at_warmup} compiles, "
                  f"{server.cache.n_shards} shards, "
                  f"threshold {server.cache.sharded_threshold})")

        # ---- measured closed loop, `arms` repetitions ----------------------
        arm_reqs, arm_rows, arm_rows_per_s, arm_reqs_per_s = [], [], [], []
        for arm in range(max(1, int(arms))):
            counts = [0] * clients
            row_counts = [0] * clients
            barrier = threading.Barrier(clients + 1)
            # the deadline must be set BEFORE the barrier releases anyone,
            # or a fast client could read it unset and exit with zero
            # requests
            stop_at = [float("inf")]

            def client(ci: int) -> None:
                crng = np.random.default_rng(seed + 1000 * (arm + 1) + ci)
                barrier.wait()
                while time.perf_counter() < stop_at[0]:
                    n = int(crng.choice(sizes))
                    start = int(crng.integers(0, pool_n - n + 1))
                    server.predict(feature_pool[start:start + n])
                    counts[ci] += 1
                    row_counts[ci] += n

            threads = [threading.Thread(target=client, args=(ci,),
                                        daemon=True)
                       for ci in range(clients)]
            for t in threads:
                t.start()
            stop_at[0] = time.perf_counter() + float(duration_s)
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            arm_reqs.append(sum(counts))
            arm_rows.append(sum(row_counts))
            # one denominator for BOTH rates: the measured elapsed, which
            # includes in-flight batches completing past the deadline
            arm_rows_per_s.append(sum(row_counts) / elapsed
                                  if elapsed > 0 else 0.0)
            arm_reqs_per_s.append(sum(counts) / elapsed
                                  if elapsed > 0 else 0.0)
        snap = server.stats()

    spread = (max(arm_rows_per_s) / min(arm_rows_per_s) - 1
              if len(arm_rows_per_s) > 1 and min(arm_rows_per_s) > 0 else 0.0)
    snap["bench_clients"] = clients
    snap["bench_arms"] = len(arm_rows_per_s)
    snap["bench_requests"] = sum(arm_reqs)
    snap["bench_rows"] = sum(arm_rows)
    snap["requests_per_s"] = float(np.mean(arm_reqs_per_s))
    snap["rows_per_s"] = float(np.mean(arm_rows_per_s))
    snap["rows_per_s_arms"] = [round(r, 1) for r in arm_rows_per_s]
    snap["spread_rows_per_s"] = round(spread, 3)
    snap["suspect_capture"] = bool(spread > SPREAD_SUSPECT)
    snap["recompiles_after_warmup"] = (snap["cache_compiles"]
                                       - compiles_at_warmup)
    return snap


def summary_line(report: dict, label: str = "serve") -> dict:
    """The one-line JSON summary (bench.py's format: flat dict, printed as
    a single ``json.dumps`` line) distilled from a full report."""
    return {
        "bench": label,
        "rows_per_s": round(report["rows_per_s"], 1),
        "requests_per_s": round(report["requests_per_s"], 1),
        "p50_ms": round(report["p50_ms"], 3),
        "p99_ms": round(report["p99_ms"], 3),
        "batch_fill_ratio": round(report["batch_fill_ratio"], 3),
        "recompiles_after_warmup": report["recompiles_after_warmup"],
        "spread_rows_per_s": report["spread_rows_per_s"],
        "suspect_capture": report["suspect_capture"],
        "pipeline_depth": report["pipeline_depth"],
        "mesh_shards": report["mesh_shards"],
    }


def run_bench_drift(model, *, arms: int = 2, **kw) -> dict:
    """Drift-monitor overhead A/B (the obs_overhead_ms shape, r18): the
    SAME closed loop on two otherwise identical servers — drift
    monitoring on (model carrying a reference profile) vs off — reports
    ``drift_overhead_ms`` (per request), ``drift_overhead_pct`` (rows/s
    cost) and ``drift_overhead_spread`` (the max of both arms' per-arm
    spreads: a noisy capture vetoes the number, never fakes a verdict).
    The acceptance gate is <= 2% — the monitor is one vectorized
    bincount per batch, and a model-quality layer that taxes serving
    more than that would be disabled in anger."""
    booster = model if isinstance(model, Booster) else Booster.load_any(model)
    if getattr(booster, "profile", None) is None:
        # the arm must measure a LIVE monitor: synthesize a baseline over
        # a pool binned through the model's own mapper
        from dryad_tpu.data.profile import profile_from_binned

        rng = np.random.default_rng(kw.get("seed", 0))
        pool = rng.standard_normal(
            (2048, booster.mapper.num_features)).astype(np.float32)
        booster.profile = profile_from_binned(
            booster, booster.mapper.transform(pool))
    on = run_bench(booster, drift="auto", arms=arms, **kw)
    off = run_bench(booster, drift=False, arms=arms, **kw)
    if not on.get("drift"):
        raise RuntimeError("the instrumented arm never built a drift "
                           "monitor — the overhead A/B measured nothing")
    pct = (off["rows_per_s"] / on["rows_per_s"] - 1
           if on["rows_per_s"] > 0 else 0.0)
    ms = ((1.0 / on["requests_per_s"] - 1.0 / off["requests_per_s"]) * 1e3
          if on["requests_per_s"] > 0 and off["requests_per_s"] > 0 else 0.0)
    return {
        "drift_overhead_ms": round(ms, 4),
        "drift_overhead_pct": round(pct, 4),
        "drift_overhead_spread": round(max(on["spread_rows_per_s"],
                                           off["spread_rows_per_s"]), 3),
        "drift_rows_per_s_on": round(on["rows_per_s"], 1),
        "drift_rows_per_s_off": round(off["rows_per_s"], 1),
        "drift_windows": {m: d for m, d in on["drift"].items()},
    }


def run_bench_layout(model, *, arms: int = 2, backend: str = "tpu",
                     **kw) -> dict:
    """Packed-vs-legacy traversal layout A/B (r21): the SAME closed loop
    on two otherwise identical jax-backend servers, one forcing
    ``predict_layout='packed'`` (one node-word table gather per level),
    one ``'legacy'`` (the structure-of-arrays ~7).  The registry stages
    each arm's layout once at model add; everything downstream (cache
    programs, batcher dispatch, sharded family) inherits it, so the
    rows/s gap is the per-level gather saving measured end to end.
    ``layout_spread_*`` carries each arm's per-arm spread — the veto
    convention of every A/B here.  Defaults to the 'tpu' (jax) backend:
    the CPU predict path never stages device tables, so a cpu-backend
    A/B would measure nothing.  Forcing 'packed' raises on a model whose
    fields exceed the packed widths — a bench must not silently fall
    back to measuring legacy twice."""
    booster = model if isinstance(model, Booster) else Booster.load_any(model)
    orig = booster.params
    try:
        booster.params = orig.replace(predict_layout="packed")
        packed = run_bench(booster, backend=backend, arms=arms, **kw)
        booster.params = orig.replace(predict_layout="legacy")
        legacy = run_bench(booster, backend=backend, arms=arms, **kw)
    finally:
        booster.params = orig
    speedup = (packed["rows_per_s"] / legacy["rows_per_s"]
               if legacy["rows_per_s"] > 0 else 0.0)
    return {
        "layout_rows_per_s_packed": round(packed["rows_per_s"], 1),
        "layout_rows_per_s_legacy": round(legacy["rows_per_s"], 1),
        "predict_layout_speedup": round(speedup, 3),
        "layout_spread_packed": packed["spread_rows_per_s"],
        "layout_spread_legacy": legacy["spread_rows_per_s"],
        "layout_recompiles_after_warmup": (
            packed["recompiles_after_warmup"]
            + legacy["recompiles_after_warmup"]),
        "suspect_capture": (packed["suspect_capture"]
                            or legacy["suspect_capture"]),
    }


def run_bench_compare(model, *, pipeline_depth: int = 2, **kw) -> dict:
    """Pipeline-vs-serial A/B on otherwise identical servers: the serial
    arm pins ``pipeline_depth=1`` (the strictly sequential dispatch loop),
    the pipeline arm uses ``pipeline_depth``.  Returns both reports plus
    ``pipeline_speedup`` (rows/s ratio)."""
    serial = run_bench(model, pipeline_depth=1, **kw)
    pipeline = run_bench(model, pipeline_depth=pipeline_depth, **kw)
    speedup = (pipeline["rows_per_s"] / serial["rows_per_s"]
               if serial["rows_per_s"] > 0 else 0.0)
    return {
        "serial": serial,
        "pipeline": pipeline,
        "pipeline_speedup": round(speedup, 3),
        "recompiles_after_warmup": (serial["recompiles_after_warmup"]
                                    + pipeline["recompiles_after_warmup"]),
        "suspect_capture": (serial["suspect_capture"]
                            or pipeline["suspect_capture"]),
    }
