"""Headline benchmark: boosting iters/sec on the Higgs-shaped config
(BASELINE.json:2 — "boosting iters/sec + final AUC, Higgs, depth-8").

Runs the device trainer on the attached accelerator, measures
steady-state boosting iterations/second after a warm-up that absorbs jit
compilation, and prints ONE JSON line.  Without an accelerator, or when a
phase fails, it exits non-zero: a number from XLA-CPU or the Pallas
interpreter is not a device measurement.

``vs_baseline`` is the speedup over the CPU canonical reference trainer on
an identical (sub-sampled) config — no published Dryad-on-A100 number exists
in this environment (BASELINE.md), so the CPU reference is the recorded
baseline the driver tracks across rounds.

The north-star metric (BASELINE.json:2) is defined at Higgs-10M scale, so
the same line also carries ``iters_per_sec_10m``: the warm MARGINAL
iteration cost at 10,000,000 rows measured as the (8-tree − 2-tree) warm
wall delta / 6 — fixed per-run costs (compile, upload, fetch) cancel in
the difference, leaving the steady-state per-iteration cost the asymptote
is made of.  Set BENCH_10M=0 to skip (~5 min: two compiles + four runs).

Env knobs: BENCH_ROWS (default 200000), BENCH_TREES (default 50),
BENCH_LEAVES (default 255), BENCH_GROWTH (default depthwise),
BENCH_10M (default 1), BENCH_DEEP / BENCH_LEAFWISE / BENCH_WIDE /
BENCH_PREDICT (default 1 — the wired-vs-legacy level probes, the r16
Epsilon-shaped hist_reduce fused-vs-feature scan probe, and the r21
packed-vs-legacy predict traversal probe).

r9 adds ``obs_overhead_ms``/``obs_overhead_pct``: instrumented-vs-
disabled telemetry registry (dryad_tpu/obs) on the 200k series, min-of-3
spread-checked arms — the zero-cost-when-disabled contract as a measured
number (acceptance: <= 2%).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np


# The timed-fori scaffolding (K dependent reps inside ONE jit, carried
# perturbation, terminal REAL fetch, min-of-reps + spread) lives in
# engine/probes.timed_fori since r13 — the canonical harness, which adds
# the runtime LIVENESS PROOF: each probe runs at two perturbation seeds
# before timing and a dead/hoisted stage raises instead of measuring a
# lie.  dryadlint's ``unharnessed-timed-fori`` rule keeps hand copies of
# the discipline from growing back here.  (Imported inside the probes —
# bench.py defers every dryad/jax import past main()'s env setup.)


def deep_level_probe(rows: int, P: int = 64, B: int = 256,
                     F: int = 28, K: int = 3, reps: int = 2) -> dict | None:
    """Per-arm wall of ONE deep level's data movement + smaller-children
    histogram: the wired leaf-ordered-layout pipeline (move_level ->
    hist_from_layout) vs the legacy plan pipeline
    (packed aligned sort -> record gather -> hist_from_plan).  Both arms
    exclude the natural-order partition the two paths share, so the
    numbers isolate exactly the stage the r6 wiring replaced.

    CLAUDE.md methodology: K dependent reps inside ONE jit; the
    perturbation reaches every stage (the wired arm's SIDE threshold and
    the legacy arm's SORT KEY rotate with the carried scalar, advanced by
    whole units); ends with a host fetch of the carried scalars, which
    waits for the device.  Returns None on CPU — interpret-mode
    kernel walls are meaningless.
    """
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform == "cpu":
        return None
    from dryad_tpu.engine import leafperm, pallas_hist
    from dryad_tpu.engine.histogram import build_hist_segmented
    from dryad_tpu.engine.probes import timed_fori

    T = leafperm._TILE_ROWS
    rng = np.random.default_rng(5)
    Xb = jnp.asarray(rng.integers(0, B, (rows, F), dtype=np.uint8))
    g_np = rng.normal(size=rows).astype(np.float32)
    g = jnp.asarray(g_np)
    h = jnp.asarray(rng.uniform(0.1, 1, rows).astype(np.float32))
    slot_np = rng.integers(0, P, rows).astype(np.int32)
    half_np = rng.random(rows) < 0.5

    # ---- wired arm --------------------------------------------------------
    rec_nat = leafperm.make_layout_records(Xb, g, h)
    n_buf = leafperm.wired_tiles_bound(-(-rows // T), P)
    # the histogrammed selection (all LEFT children below) must provably
    # cover < half the rows for the shared half-bound: thresholds stay
    # under 0.45*B on uniform bins, so P(bin <= thr) < 0.5 with margin
    n_sel = leafperm.wired_sel_tiles_bound(-(-rows // T), n_buf, P,
                                           half=True)
    rec_lay, tile_run, run_slot = leafperm.initial_layout(
        rec_nat, jnp.asarray(slot_np), jnp.ones((P,), bool), P, n_buf)

    def wired_step(s, rec_lay, tile_run, run_slot):
        smod = s - jnp.floor(s / 8) * 8          # live: period-8 walk (a
        # period that fits inside K would repeat the same contrib multiset
        # at both liveness seeds — the harness would reject it as dead)
        # the grower's full per-level layout work rides in the arm: the
        # run -> packed-record compose, move_level (per-tile parameters,
        # counting pass, level_moves, move kernel) and advance_runs — the
        # probe must price the level the GROWER pays, not just the kernel.
        # The per-run table is ROLLED by the carried scalar (whole units)
        # and its threshold walks with it: a non-carried table would let
        # XLA's while-loop LICM hoist the bookkeeping out of the timed
        # fori (the CLAUDE.md dead-input trap, r10)
        si = s.astype(jnp.int32)
        rs_i = jnp.roll(run_slot, si)
        odd = (rs_i & 1).astype(jnp.float32)
        # thresholds on feature 0 (bins uniform in [0, B)) stay under
        # 0.45*B: the left children provably cover < half the rows
        # every run's rows split (the half bound needs it); advance_runs
        # below keeps the right child of ~half the runs, as before
        run_do = (rs_i & 1) == 0
        run_rec = leafperm.pack_run_records(
            jnp.ones((P,)), jnp.zeros((P,)),
            B * (0.05 + 0.025 * smod + 0.1 * odd))
        out, base_l, base_r = leafperm.move_level(
            rec_lay, tile_run, run_rec, bin_dtype=jnp.uint8)
        tr2, rs2 = leafperm.advance_runs(run_slot, run_do,
                                         jnp.arange(P, dtype=jnp.int32),
                                         base_l, base_r, n_buf)
        hist = leafperm.hist_from_layout(
            out, base_l[:P], base_l[1:] - base_l[:-1], P, B, F,
            jnp.uint8, n_sel)
        # every stage feeds the contrib at FULL magnitude — the harness
        # accumulates it apart from s, so no 1e-20 scaling (under which
        # the liveness signal would round away below fp32 resolution)
        return s + 1.0, (out[0, 0].astype(jnp.float32)
                         + hist[0, 0].sum()
                         + (tr2[0] + rs2[0] + base_l[P])
                         .astype(jnp.float32))

    t_wired, sp_wired = timed_fori(wired_step, K, reps,
                                   rec_lay, tile_run, run_slot,
                                   label="deep_level_wired")

    # ---- legacy arm -------------------------------------------------------
    records = pallas_hist.make_records(Xb, g, h)
    cnt0 = np.bincount(slot_np[half_np], minlength=P).astype(np.int32)
    sel0 = jnp.asarray(np.where(half_np, slot_np, P).astype(np.int32))
    cnt0_d = jnp.asarray(cnt0)

    # rows_bound must be MATHEMATICALLY guaranteed (tile_plan contract —
    # rows beyond it drop silently): the perturbation below only rotates
    # slot ids, never the selected SET, so the exact draw count is the
    # bound (a binomial ~N/2 draw can exceed N//2 itself)
    sel_rows = int(cnt0.sum())

    # Xb/g/h ride as ARGUMENTS, never closures: closure arrays lower as
    # jit constants, serialized into every compile of the program
    # (CLAUDE.md lowering facts; at 10M rows the three arrays are ~360 MB)
    def legacy_step(s, sel0, cnt0_d, records, Xb, g, h):
        si = s.astype(jnp.int32)
        sel = jnp.where(sel0 < P, (sel0 + si) % P, P)  # perturb the SORT KEY
        cnt = jnp.roll(cnt0_d, si)               # exact counts, rotated too
        hist = build_hist_segmented(
            Xb, g, h, sel, P, B, backend="pallas",
            rows_bound=sel_rows, records=records, sel_counts=cnt)
        return s + 1.0, hist[0, 0, 0, 0]

    t_legacy, sp_legacy = timed_fori(legacy_step, K, reps,
                                     sel0, cnt0_d, records, Xb, g, h,
                                     label="deep_level_legacy")
    return {
        "deep_level_ms_wired": round(t_wired, 1),
        "deep_level_ms_legacy": round(t_legacy, 1),
        "deep_level_spread_wired": round(sp_wired, 3),
        "deep_level_spread_legacy": round(sp_legacy, 3),
        "deep_level_rows": rows,
    }


def leafwise_level_probe(rows: int, D: int = 7, B: int = 256,
                         F: int = 28, K: int = 3,
                         reps: int = 2) -> dict | None:
    """Per-arm wall of ONE batched leaf-wise EXPANSION level's data
    movement + smaller-children histogram, wired vs legacy — the r10
    counterpart of ``deep_level_probe`` for the second consumer of the
    layout.  The expansion differs from a levelwise deep level in its run
    bookkeeping (heap-node ids with sentinel HN, run capacity NR = 2^D =
    twice the candidate width, hence twice the mandated empty segments),
    so the wired arm prices exactly the level the expansion fori pays at
    its widest width P = 2^(D-1); the legacy arm is the per-level
    sort+gather segmented pass the wiring deletes.

    Same CLAUDE.md timed-fori rules as deep_level_probe (the perturbation
    rotates the wired SIDE threshold / the legacy SORT KEY by whole
    units; every timed program ends in a real host fetch), plus per-arm
    spread: each arm runs ``reps`` timed programs, reports the MIN (host
    stalls only ever add time) and max/min-1 as the suspect-capture
    signal (>5% = suspect, CLAUDE.md).  None on CPU — interpret-mode
    kernel walls are meaningless."""
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform == "cpu":
        return None
    from dryad_tpu.engine import leafperm, pallas_hist
    from dryad_tpu.engine.histogram import build_hist_segmented
    from dryad_tpu.engine.probes import timed_fori

    T = leafperm._TILE_ROWS
    P = 1 << (D - 1)                  # widest expansion level
    NR = 1 << D                       # run capacity (leafwise wiring)
    HN = 1 << (D + 1)                 # heap sentinel
    rng = np.random.default_rng(17)
    Xb = jnp.asarray(rng.integers(0, B, (rows, F), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=rows).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, rows).astype(np.float32))
    slot_np = rng.integers(0, P, rows).astype(np.int32)
    half_np = rng.random(rows) < 0.5

    # ---- wired arm: the expansion level at heap-id bookkeeping ------------
    rec_nat = leafperm.make_layout_records(Xb, g, h)
    n_buf = leafperm.wired_tiles_bound(-(-rows // T), NR)
    # thresholds stay under 0.45*B so the histogrammed left children
    # provably cover < half the rows (shared half-bound rule)
    n_sel = leafperm.wired_sel_tiles_bound(-(-rows // T), n_buf, P,
                                           half=True)
    rec_lay, tile_run, run_slot_p = leafperm.initial_layout(
        rec_nat, jnp.asarray(slot_np), jnp.ones((P,), bool), P, n_buf)
    # lift the (P,) slot table to the expansion's (NR,) heap-node table:
    # level-(D-1) nodes are [P, 2P), unused run indices hold sentinel HN
    run_slot = jnp.concatenate([
        jnp.where(run_slot_p < P, P + run_slot_p, HN),
        jnp.full((NR - P,), HN, jnp.int32)]).astype(jnp.int32)

    def wired_step(s, rec_lay, tile_run, run_slot):
        smod = s - jnp.floor(s / 8) * 8        # live: period-8 walk (see
        # deep_level_probe — a period inside K repeats the contrib
        # multiset across the liveness seeds and reads as dead)
        # the grower's per-level layout work: node -> packed record
        # composed at the (NR,) level, move_level + advance_runs.  Table
        # ROLLED by the carried scalar and its threshold walks with it — a
        # non-carried table would let while-loop LICM hoist the
        # bookkeeping out of the timed fori (the CLAUDE.md dead-input
        # trap, r10; same fix as deep_level_probe)
        si = s.astype(jnp.int32)
        rs_i = jnp.roll(run_slot, si)
        odd = (rs_i & 1).astype(jnp.float32)
        # thresholds stay under 0.45*B (half bound, see deep_level_probe)
        run_do = ((rs_i & 1) == 0) & (rs_i < HN)           # ~half split
        run_rec = leafperm.pack_run_records(
            jnp.ones((NR,)), jnp.zeros((NR,)),
            B * (0.05 + 0.025 * smod + 0.1 * odd))
        out, base_l, base_r = leafperm.move_level(
            rec_lay, tile_run, run_rec, bin_dtype=jnp.uint8)
        ns2 = jnp.where(run_do, 2 * rs_i, rs_i)
        tr2, rs2 = leafperm.advance_runs(ns2, run_do, 2 * rs_i + 1,
                                         base_l, base_r, n_buf,
                                         sentinel=HN)
        hist = leafperm.hist_from_layout(
            out, base_l[:P], base_l[1:P + 1] - base_l[:P], P, B, F,
            jnp.uint8, n_sel)
        # full-magnitude contrib, accumulated apart from s by the harness
        # (the retired s + x*1e-20 idiom could not carry a liveness signal)
        return s + 1.0, (out[0, 0].astype(jnp.float32)
                         + hist[0, 0].sum()
                         + (tr2[0] + rs2[0] + base_l[P])
                         .astype(jnp.float32))

    t_wired, sp_wired = timed_fori(wired_step, K, reps,
                                   rec_lay, tile_run, run_slot,
                                   label="leafwise_level_wired")

    # ---- legacy arm: the per-expansion-level sort+gather pass -------------
    records = pallas_hist.make_records(Xb, g, h)
    cnt0 = np.bincount(slot_np[half_np], minlength=P).astype(np.int32)
    sel0 = jnp.asarray(np.where(half_np, slot_np, P).astype(np.int32))
    cnt0_d = jnp.asarray(cnt0)
    sel_rows = int(cnt0.sum())       # exact draw count (tile_plan contract)

    # Xb/g/h as ARGUMENTS, never closures (HTTP 413 jit-constant rule —
    # see deep_level_probe's legacy arm)
    def legacy_step(s, sel0, cnt0_d, records, Xb, g, h):
        si = s.astype(jnp.int32)
        sel = jnp.where(sel0 < P, (sel0 + si) % P, P)  # perturb the SORT KEY
        cnt = jnp.roll(cnt0_d, si)
        hist = build_hist_segmented(
            Xb, g, h, sel, P, B, backend="pallas",
            rows_bound=sel_rows, records=records, sel_counts=cnt)
        return s + 1.0, hist[0, 0, 0, 0]

    t_legacy, sp_legacy = timed_fori(legacy_step, K, reps,
                                     sel0, cnt0_d, records, Xb, g, h,
                                     label="leafwise_level_legacy")
    return {
        "leafwise_level_ms_wired": round(t_wired, 1),
        "leafwise_level_ms_legacy": round(t_legacy, 1),
        "leafwise_level_spread_wired": round(sp_wired, 3),
        "leafwise_level_spread_legacy": round(sp_legacy, 3),
        "leafwise_level_rows": rows,
    }


def hist_reduce_probe(rows: int = 400_000, F: int = 2000, B: int = 256,
                      P: int = 32, K: int = 3, reps: int = 2) -> dict | None:
    """Epsilon-shaped (2000 x 256) per-arm wall of the split-finding stage
    the r16 feature-parallel reduction changes: the fused full-F scan
    (the split_scan registry probe at this width — each device scans every
    feature) vs the feature arm's per-device stage (the hist_reduce
    registry probe: sliced F/8 scan + packed record combine).  Both ride
    ``engine/probes`` — liveness-proven timed-fori programs with the
    histogram arrays as jit ARGUMENTS and a scale-class perturbation that
    must reach the gains (run_probe applies the harness rules).  The wire
    win itself ((n-1)/n of the reduced payload) is static accounting
    (train._comm_stats, jaxpr-census-verified), not a single-device wall
    — these fields track the compute side of the trade across rounds.
    None on CPU — Epsilon-width scans take minutes there and the walls
    mean nothing."""
    import jax

    if jax.devices()[0].platform == "cpu":
        return None
    from dryad_tpu.engine.probes import run_probe

    fused = run_probe("split_scan", rows=rows, K=K, reps=reps,
                      num_features=F, total_bins=B, num_slots=P)
    feat = run_probe("hist_reduce", rows=rows, K=K, reps=reps,
                     num_features=F, total_bins=B, num_slots=P)
    return {
        "hist_reduce_ms_fused": round(fused["ms"], 2),
        "hist_reduce_ms_feature": round(feat["ms"], 2),
        "hist_reduce_spread_fused": round(fused["spread"], 3),
        "hist_reduce_spread_feature": round(feat["spread"], 3),
        "hist_reduce_features": F,
        "hist_reduce_bins": B,
        "hist_reduce_slots": P,
    }


def predict_layout_probe(rows: int = 1_000_000, K: int = 4,
                         reps: int = 2) -> dict | None:
    """Per-tree traversal wall per predict table layout (r21): the legacy
    structure-of-arrays arm (~7 small-table gathers per level) vs the
    packed node-word arm (ONE (M,2)-uint32 limb-table gather per level) on
    the same synthetic depth-6 tree.  Gather cost on TPU is per-ACCESS,
    so the packed/legacy gap here is the real per-level lookup saving the
    jaxpr census pins statically (18 vs 126 trip-weighted table gathers).
    Both arms ride ``engine/probes`` liveness-proven timed-fori programs;
    fields are us/row so serve-side percentiles have a unit to compare
    against.  None on CPU."""
    import jax

    if jax.devices()[0].platform == "cpu":
        return None
    from dryad_tpu.engine.probes import run_probe

    legacy = run_probe("predict_traversal", rows=rows, K=K, reps=reps)
    packed = run_probe("predict_traversal_packed", rows=rows, K=K, reps=reps)
    return {
        "predict_us_per_row_packed": round(packed["ms"] * 1000.0 / rows, 4),
        "predict_us_per_row_legacy": round(legacy["ms"] * 1000.0 / rows, 4),
        "predict_spread_packed": round(packed["spread"], 3),
        "predict_spread_legacy": round(legacy["spread"], 3),
        "predict_probe_rows": rows,
    }


def main() -> None:
    # Pin the device-resident chunked boosting path: the bench estimates the
    # LONG-run (500-tree-scale) steady state from short timed runs, and the
    # compile-vs-work heuristic (train.py, VERDICT r3 #5) would route runs
    # this short to per-iteration dispatch — a different program than the
    # one a long run uses.  Forcing the chunk path keeps the marginal arms
    # measuring the steady state the metric is defined on (and keeps the
    # BENCH series comparable with rounds 1-3, which always chunked here).
    os.environ.setdefault("DRYAD_CHUNK", "1")
    rows = int(os.environ.get("BENCH_ROWS", 200_000))
    # 50 trees: long enough that the steady-state chunked pipeline dominates
    # (20 trees left ~30% of wall in fixed per-run costs), short enough for
    # a ~2-minute bench incl. the identical-shape warmup run
    trees = int(os.environ.get("BENCH_TREES", 50))
    leaves = int(os.environ.get("BENCH_LEAVES", 255))
    growth = os.environ.get("BENCH_GROWTH", "depthwise")

    import jax

    if jax.devices()[0].platform == "cpu":
        raise SystemExit("bench.py measures the attached accelerator; jax "
                         "initialised with platform 'cpu' only")

    import dryad_tpu as dryad
    from dryad_tpu.config import make_params
    from dryad_tpu.datasets import higgs_like
    from dryad_tpu.metrics import auc

    X, y = higgs_like(rows, seed=7)
    ds = dryad.Dataset(X, y, max_bins=256)
    params = make_params(dict(
        objective="binary", num_trees=trees, num_leaves=leaves,
        max_depth=8, growth=growth, max_bins=256, learning_rate=0.1,
    ))

    from dryad_tpu.engine.train import train_device

    # iterations dispatch asynchronously (no per-iteration device sync), so
    # per-callback deltas are meaningless — time the full run wall-to-wall
    # (train_device's final fetch blocks on the whole pipeline) and subtract
    # a warmup run that absorbs jit compilation.
    # warmup with identical shapes (the output tree table is (num_trees, M)
    # — a different tree count would recompile in the timed run)
    train_device(params, ds)

    t0 = time.perf_counter()
    booster = train_device(params, ds)
    total_time = time.perf_counter() - t0
    iters_per_sec = trees / total_time

    train_auc = auc(y, booster.predict(X, raw_score=True))

    # CPU-reference baseline on a subsample, scaled to the full row count
    # (histogram work is linear in rows; SURVEY.md §3 hot loops)
    base_rows = min(rows, 50_000)
    Xs = X[:base_rows]
    ys = y[:base_rows]
    ds_s = dryad.Dataset(Xs, ys, max_bins=256)
    cpu_params = params.replace(num_trees=2)
    t0 = time.perf_counter()
    dryad.train(cpu_params, ds_s, backend="cpu")
    cpu_time = (time.perf_counter() - t0) / 2 * (rows / base_rows)
    vs_baseline = iters_per_sec * cpu_time  # = cpu_time_per_iter / dev_time_per_iter

    out = {
        "metric": f"boosting_iters_per_sec_higgs{rows // 1000}k_depth8_{leaves}leaves",
        "value": round(iters_per_sec, 3),
        "unit": "iters/s",
        "vs_baseline": round(vs_baseline, 3),
        "final_train_auc": round(float(train_auc), 5),
        "rows": rows,
        "trees_timed": trees,
    }

    # ---- artifact stamp (r12: the trend ledger keys history off data) -------
    # schema_version + git rev + device kind in the JSON itself, so
    # obs/trends.py never parses filenames; the reader stays tolerant of
    # the unstamped r1-r7 artifacts.  r23: device_kind comes from the ONE
    # derivation (policy/device.py) instead of a hand-rolled probe.
    from dryad_tpu.obs.trends import artifact_stamp

    out.update(artifact_stamp(
        root=os.path.dirname(os.path.abspath(__file__))))

    # ---- supervisor overhead (r8: the wrapper must be free on the hot path)
    # supervised vs direct short run, NO faults, BOTH arms checkpointed the
    # same way so the delta isolates the supervisor wrapper itself
    # (classification plumbing, journal-less hook threading, the retry
    # loop's bookkeeping) — not checkpoint I/O.
    import tempfile

    from dryad_tpu.resilience import supervise_train

    # a deliberately SHORT config (sub-second arms) so the wrapper's fixed
    # per-run cost is measured against a small noise floor — the wrapper
    # adds only host bookkeeping (one Checkpointer.latest probe, a hook
    # call per chunk/fetch, the retry-loop frame), none of it scaling with
    # rows, so a short run bounds the long-run overhead from above.
    # Per-arm min of 3 (stalls only ever ADD time) + spread observability.
    p_sup = params.replace(num_trees=8, num_leaves=15, max_depth=4)
    ds_sup = dryad.Dataset(X[:10_000], y[:10_000], max_bins=64)
    with tempfile.TemporaryDirectory() as td:
        dryad.train(p_sup, ds_sup, backend="tpu",                # warm/compile
                    checkpoint_dir=td + "/w", checkpoint_every=4)

        def arm(kind: str, i: int) -> float:
            ck = f"{td}/{kind}{i}"
            t0 = time.perf_counter()
            if kind == "sup":
                supervise_train(p_sup, ds_sup, backend="tpu",
                                checkpoint_dir=ck, checkpoint_every=4)
            else:
                dryad.train(p_sup, ds_sup, backend="tpu",
                            checkpoint_dir=ck, checkpoint_every=4)
            return time.perf_counter() - t0

        directs = [arm("direct", i) for i in range(3)]
        sups = [arm("sup", i) for i in range(3)]
    out["supervisor_overhead_ms"] = round(
        (min(sups) - min(directs)) * 1000, 1)
    out["supervisor_overhead_spread"] = round(
        max(max(directs) / min(directs), max(sups) / min(sups)) - 1, 3)

    # ---- observability overhead (r9: the zero-cost contract, measured) ------
    # Instrumented vs disabled on the SAME 200k series the headline times:
    # the obs wiring is a handful of host-side clock reads per chunk (and
    # per iteration on the dispatch path), so the delta must be noise-level
    # (acceptance: <= 2% of the arm wall).  Min-of-3 per arm — stalls only
    # ever ADD time — with the per-arm spread recorded next to the number.
    from dryad_tpu.obs.registry import default_registry

    _reg = default_registry()
    _was_enabled = _reg.enabled
    p_obs = params.replace(num_trees=12)
    train_device(p_obs, ds)                    # warm/compile the T=12 shape

    def obs_arm(enabled: bool) -> float:
        (_reg.enable if enabled else _reg.disable)()
        t0 = time.perf_counter()
        train_device(p_obs, ds)
        return time.perf_counter() - t0

    try:
        ons = [obs_arm(True) for _ in range(3)]
        offs = [obs_arm(False) for _ in range(3)]
    finally:
        # restore what the process started with (DRYAD_OBS=0 must keep the
        # 10M arm below uninstrumented)
        (_reg.enable if _was_enabled else _reg.disable)()
    out["obs_overhead_ms"] = round((min(ons) - min(offs)) * 1000, 2)
    out["obs_overhead_pct"] = round((min(ons) / min(offs) - 1) * 100, 3)
    out["obs_overhead_spread"] = round(
        max(max(ons) / min(ons), max(offs) / min(offs)) - 1, 3)

    # ---- 10M-row warm marginal (the BASELINE.json:2 scale) ------------------
    if os.environ.get("BENCH_10M", "1") != "0" and rows == 200_000:
        del X, y, ds  # host copies of the 200k run are dead weight now
        X10, y10 = higgs_like(10_000_000, seed=11)
        ds10 = dryad.Dataset(X10, y10, max_bins=256)
        del X10

        # Stall-robust pair methodology: a host stall anywhere in a timed
        # run ADDS seconds and poisons the (8 - 2)-tree delta, and the old
        # "< 0.5 s" guard only caught the opposite failure.  Stalls are one-sided (they only ever ADD
        # time), so each arm is measured TWICE unconditionally and the
        # per-arm MINIMUM is the estimator; a third round is added only
        # when the two rounds of an arm disagree badly (> 15%), i.e. when
        # a stall visibly hit both attempts or the first was poisoned.
        p2 = params.replace(num_trees=2)
        p8 = params.replace(num_trees=8)
        train_device(p2, ds10)                 # compile + warm (own T shape)
        train_device(p8, ds10)

        def wall(p10) -> float:
            t0 = time.perf_counter()
            train_device(p10, ds10)
            return time.perf_counter() - t0

        walls2 = [wall(p2), wall(p2)]
        walls8 = [wall(p8), wall(p8)]
        for ws, p10 in ((walls2, p2), (walls8, p8)):
            if max(ws) > 1.15 * min(ws):
                ws.append(wall(p10))
        t2, t8 = min(walls2), min(walls8)
        marginal = max((t8 - t2) / 6.0, 1e-9)
        out["iters_per_sec_10m"] = round(1.0 / marginal, 4)
        out["marginal_s_per_iter_10m"] = round(marginal, 3)
        out["wall_2tree_10m"] = round(t2, 2)
        out["wall_8tree_10m"] = round(t8, 2)
        # observability: per-arm spread (max/min - 1) so a noisy capture is
        # visible in the artifact instead of silently shifting the headline
        out["spread_2tree_10m"] = round(max(walls2) / min(walls2) - 1, 3)
        out["spread_8tree_10m"] = round(max(walls8) / min(walls8) - 1, 3)
        out["rows_10m"] = 10_000_000
        del ds10                       # free HBM before the level probe

    # ---- wired-vs-legacy deep-level walls (the r6 trajectory field) ---------
    # Recorded per arm next to the spread fields so the wiring shows up as
    # a TREND across BENCH_*.json rounds, not a point.  BENCH_DEEP=0 skips.
    if os.environ.get("BENCH_DEEP", "1") != "0":
        probe_rows = out.get("rows_10m", rows)
        probe = deep_level_probe(probe_rows)
        if probe:
            out.update(probe)

    # ---- wired-vs-legacy leaf-wise expansion-level walls (r10) --------------
    # Same trend-not-point rule as BENCH_DEEP; BENCH_LEAFWISE=0 skips.
    if os.environ.get("BENCH_LEAFWISE", "1") != "0":
        probe_rows = out.get("rows_10m", rows)
        probe = leafwise_level_probe(probe_rows)
        if probe:
            out.update(probe)

    # ---- wide-shape split-scan walls per hist-reduce arm (r16) --------------
    # Epsilon-shaped fused vs feature-parallel scan stage; trend fields
    # like the wired/legacy pairs above.  BENCH_WIDE=0 skips.
    if os.environ.get("BENCH_WIDE", "1") != "0":
        probe = hist_reduce_probe()
        if probe:
            out.update(probe)

    # ---- packed-vs-legacy predict traversal walls (r21) ---------------------
    # One node-word table gather per level vs the structure-of-arrays ~7;
    # same trend-not-point rule as the arms above.  BENCH_PREDICT=0 skips.
    if os.environ.get("BENCH_PREDICT", "1") != "0":
        probe = predict_layout_probe()
        if probe:
            out.update(probe)

    # ---- out-of-core streamed-training arm (r20) ----------------------------
    # Resident-vs-streamed CPU walls + bitwise check via the standalone
    # probe (pure host work — run as a subprocess so its RSS accounting
    # and numpy temporaries never contaminate the TPU walls above).  The
    # 1e7-row RSS proof is heavy; opt in with BENCH_STREAM_RSS=1 or run
    # scripts/stream_rss_probe.py directly.  BENCH_STREAM=0 skips.
    if os.environ.get("BENCH_STREAM", "1") != "0":
        import subprocess as _sp
        import sys as _sys

        argv = [_sys.executable,
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "scripts", "stream_rss_probe.py")]
        if os.environ.get("BENCH_STREAM_RSS", "0") != "1":
            argv.append("--skip-rss")
        # this process holds the chip and a chip has one owner: the probe
        # is host-only work, and the child's jax is held to the CPU so it
        # stays that way
        r = _sp.run(argv, capture_output=True, text=True,
                    env=dict(os.environ, JAX_PLATFORMS="cpu"))
        if r.returncode == 0 and r.stdout.strip():
            probe = json.loads(r.stdout.strip().splitlines()[-1])
            out.update({k: v for k, v in probe.items()
                        if k.startswith(("stream_", "resident_"))})
        else:
            out["stream_probe_error"] = (r.stderr or "").strip()[-400:]

    print(json.dumps(out))
    if "stream_probe_error" in out:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
