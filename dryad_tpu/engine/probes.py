"""Canonical timed-fori measurement harness + the named stage-probe registry.

Every device measurement in this repo rides ONE harness (``timed_fori``)
that codifies the CLAUDE.md measuring rules as code instead of as five
hand copies of the discipline (bench.py's private ``_timed_fori`` and the
four ``scripts/profile_*.py`` loop_time clones, retired in r13):

* K dependent iterations inside ONE jit via ``lax.fori_loop`` — per-call
  host timing of a stage includes its dispatch, and separately jitted
  parts fuse differently than they do inside the program (parts measure
  slower OR faster than their sum);
* a carried perturbation scalar ``s`` the probe advances by WHOLE units
  (fractional advances round away in integer consumers — the r5 failure);
* every timed program ends in a host fetch of the carried scalars
  (``float(...)``): it waits for the device as ``block_until_ready``
  does, and the fetched accumulator is what the liveness proof compares;
* min-of-reps + spread capture: host stalls only ever ADD time, so the
  per-arm MIN is the estimator and max/min - 1 > 5% flags the capture.

The harness adds what the AST lint (``dead-perturbation``) can only
approximate: a **runtime liveness proof**.  A probe's step returns
``(s_next, contrib)`` where ``contrib`` is a scalar derived from the
timed stage's OUTPUT; the harness carries ``(s, acc)`` with
``acc += contrib`` and, before timing, runs the program at two
perturbation seeds.  A stage whose perturbation is dead — rounded away
(r5) or reachable only through non-carried inputs that while-loop LICM
hoists out of the loop (r10, the 2x-too-fast lies) — produces the SAME
fetched accumulator at both seeds and is **rejected at runtime** with
``DeadProbeError``, not discovered in review.  Because ``contrib`` is
accumulated separately from ``s``, the old ``s + out * 1e-20`` idiom
(whose stage term vanished below fp32 resolution, making the fetch
differ only through the trivially-live counter) cannot mask a hoist.

Seed choice: the two liveness seeds differ by 7 — probes that perturb by
rotation must have a period that does not divide the gap (every modular
period in this file is a power of two).  And because the accumulator is
order-independent, a PERIODIC perturbation must not make the two seeds'
K-trip windows the same multiset (a period-2 alternation under K=2 does
exactly that — caught by this very proof while building it): the modular
walks here all use period 8; keep K below the walk period.

``PROBES`` names one probe per hot-path stage (masked + segmented Pallas
histogram, split scan, the leafperm move + layout histogram, the packed
route gather, predict traversal, the GOSS/renewal sort arms); run them
via ``run_probe`` / ``python -m dryad_tpu profile``.  ``run_selftest``
(ci.sh) proves the proof: a seeded dead probe MUST be caught, and every
shipped probe must pass liveness on the CPU backend in seconds.

This module touches jax, so it lives in the engine; the jax-free
aggregation layer (gauges, stamped PROFILE artifacts, trend ingestion)
is ``dryad_tpu/obs/profiler.py``.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

#: default probe shape knobs (the Higgs bench shape, scaled per platform)
DEFAULT_K = 3
DEFAULT_REPS = 2
DEFAULT_ROWS_DEVICE = 1_000_000
DEFAULT_ROWS_CPU = 8_192
#: the two liveness seeds; gap 7 is coprime to every power-of-two period
LIVENESS_SEEDS = (0.0, 7.0)
#: every registry probe's modular perturbation walk uses this period; at
#: K >= period the two seeds' K-trip windows are the same multiset and
#: the proof would false-fire on a LIVE stage (run_probe rejects such K)
WALK_PERIOD = 8
#: per-arm spread above this flags the capture (CLAUDE.md)
SPREAD_SUSPECT = 0.05


class DeadProbeError(RuntimeError):
    """The probe's perturbation never reached the timed stage — the stage
    would be hoisted/folded by XLA and the wall would be a lie."""


def timed_fori(step, K: int, reps: int, *args,
               label: str = "probe",
               seeds: tuple = LIVENESS_SEEDS,
               check_live: bool = True) -> tuple:
    """Time ``step`` under the canonical discipline; return (min_ms, spread).

    ``step(s, *args) -> (s_next, contrib)``: advance the carried scalar by
    whole units and return a scalar derived from the stage's OUTPUT.  The
    harness folds ``contrib`` into a separate fp32 accumulator (so the
    liveness signal cannot vanish under the counter, unlike ``s + x*1e-20``)
    and rejects the probe with ``DeadProbeError`` when two different seeds
    fetch identical accumulators (dead perturbation / hoisted stage) or a
    non-finite one (the perturbation broke the stage's domain).
    """
    import jax
    import jax.numpy as jnp

    def prog(s0, *a):
        def body(i, carry):
            s, acc = carry
            s2, contrib = step(s, *a)
            return s2, acc + jnp.asarray(contrib).astype(jnp.float32)
        return jax.lax.fori_loop(0, K, body, (s0, jnp.float32(0.0)))

    f = jax.jit(prog)
    out = f(jnp.float32(seeds[0]), *args)
    acc_a = float(out[1])                  # compile + warm; REAL fetch
    if check_live:
        out = f(jnp.float32(seeds[1]), *args)
        acc_b = float(out[1])
        if not (math.isfinite(acc_a) and math.isfinite(acc_b)):
            raise DeadProbeError(
                f"{label}: non-finite liveness accumulator "
                f"({acc_a!r} / {acc_b!r}) — the perturbation left the "
                "stage's numeric domain; rescale it")
        if acc_a == acc_b:
            raise DeadProbeError(
                f"{label}: identical fetched results at seeds {seeds} — "
                "the perturbation is DEAD (rounded away or hoisted by "
                "while-loop LICM; CLAUDE.md r5/r10) and the wall would "
                "measure a lie.  Make the carried scalar reach the stage "
                "and the stage's output reach the contrib")
    walls = []
    for r in range(reps):
        t0 = time.perf_counter()
        out = f(jnp.float32(seeds[0] + 2.0 * (r + 1)), *args)
        float(out[1])                      # real fetch ends the timed region
        walls.append((time.perf_counter() - t0) / K * 1000.0)
    return min(walls), max(walls) / min(walls) - 1.0


# ---------------------------------------------------------------------------
# the stage-probe registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StageProbe:
    """One named hot-path stage.  ``build(rows, num_features, total_bins,
    num_slots, seed)`` returns ``(step, args, meta)`` — arrays ride as jit
    ARGUMENTS (never closures: the HTTP-413 jit-constant rule)."""

    name: str
    doc: str
    build: Callable
    cheap: bool = True      # eligible for the smoke/selftest tier


def _synth(rows: int, F: int, B: int, seed: int):
    rng = np.random.default_rng(seed)
    Xb = rng.integers(0, B, size=(rows, F),
                      dtype=np.uint8 if B <= 256 else np.uint16)
    g = rng.normal(size=rows).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=rows).astype(np.float32)
    return rng, Xb, g, h


def _build_hist_masked(rows, F, B, P, seed):
    """Masked histogram (build_hist): the root/shallow-level pass.  The
    perturbation rolls the MASK by the carried scalar — it must reach the
    kernel, not the weights (records carry g/h on the wired paths)."""
    import jax.numpy as jnp

    from dryad_tpu.engine.histogram import build_hist

    rng, Xb, g, h = _synth(rows, F, B, seed)
    mask = jnp.asarray(rng.random(rows) < 0.8)
    Xb, g, h = jnp.asarray(Xb), jnp.asarray(g), jnp.asarray(h)

    def step(s, Xb, g, h, mask):
        si = s.astype(jnp.int32)
        hist = build_hist(Xb, g, h, jnp.roll(mask, si), B, backend="auto")
        # slice-plane SUM, not a single bin: bin 0 can be empty in real
        # binned data and a constant-zero contrib reads as dead
        return s + 1.0, hist[0].sum()

    return step, (Xb, g, h, mask), {"rows": rows}


def _build_hist_segmented(rows, F, B, P, seed):
    """Segmented histogram (the per-level kernel call incl. its plan):
    perturb the SORT KEY — slot ids rotate mod P, the selected SET stays
    fixed so the exact draw count is the rows_bound (tile_plan contract)."""
    import jax.numpy as jnp

    from dryad_tpu.engine.histogram import build_hist_segmented

    rng, Xb, g, h = _synth(rows, F, B, seed)
    sel_np = rng.integers(0, 2 * P, size=rows).astype(np.int32)
    sel_np = np.where(sel_np < P, sel_np, P)
    bound = int((sel_np < P).sum())
    Xb, g, h = jnp.asarray(Xb), jnp.asarray(g), jnp.asarray(h)
    sel = jnp.asarray(sel_np)

    def step(s, Xb, g, h, sel):
        si = s.astype(jnp.int32)
        sel2 = jnp.where(sel < P, (sel + si) % P, P)
        hist = build_hist_segmented(Xb, g, h, sel2, P, B, backend="auto",
                                    rows_bound=bound)
        # slot-0 plane sum: the ALL-slot total is rotation-invariant and
        # a single bin can be empty — both would read as dead
        return s + 1.0, hist[0, 0].sum()

    return step, (Xb, g, h, sel), {"rows": rows, "num_slots": P}


def _build_split_scan(rows, F, B, P, seed):
    """vmapped best-split scan over 2P children.  ``rows`` only scales the
    synthetic histogram magnitudes — the scan is row-count independent."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.engine.split import find_best_split

    rng = np.random.default_rng(seed)
    hists = np.stack([
        rng.normal(size=(2 * P, F, B)),
        rng.uniform(0.1, 1.0, size=(2 * P, F, B)),
        rng.uniform(0.5, 2.0, size=(2 * P, F, B)),
    ], axis=1).astype(np.float32) * (rows / max(B, 1))
    hh0 = jnp.asarray(hists)
    fmask = jnp.ones((F,), bool)
    iscat = jnp.zeros((F,), bool)
    allow = jnp.ones((2 * P,), bool)

    def step(s, hh, fmask, iscat, allow):
        # period-8 walk (the module rule: a period inside K would repeat
        # the contrib multiset across the liveness seeds — gap 7 mod 4
        # collides at K=4, which the K < WALK_PERIOD guard admits)
        smod = s - jnp.floor(s / 8.0) * 8.0
        hh2 = hh * (1.0 + 0.01 * smod)       # gains are scale-sensitive
        G = hh2[:, 0].sum(axis=(1, 2))       # (lambda_l2 breaks homogeneity)
        H = hh2[:, 1].sum(axis=(1, 2))
        C = hh2[:, 2].sum(axis=(1, 2))

        def best(hh_, G_, H_, C_, a_):
            return find_best_split(
                hh_, G_, H_, C_, lambda_l2=1.0, min_child_weight=1e-3,
                min_data_in_leaf=20, min_split_gain=0.0, feat_mask=fmask,
                is_cat_feat=iscat, allow=a_, has_cat=False)

        res = jax.vmap(best)(hh2, G, H, C, allow)
        return s + 1.0, res.gain[0] + res.gain[-1]

    return step, (hh0, fmask, iscat, allow), {"rows": rows, "num_slots": P}


def _layout_fixture(rows, F, B, P, seed):
    """Shared wired-path setup: a P-slot leaf-ordered layout (the bench
    probes' initial_layout construction — the growers are root-anchored,
    the probes build mid-tree states directly)."""
    import jax.numpy as jnp

    from dryad_tpu.engine import leafperm

    T = leafperm._TILE_ROWS
    rng, Xb, g, h = _synth(rows, F, B, seed)
    rec_nat = leafperm.make_layout_records(
        jnp.asarray(Xb), jnp.asarray(g), jnp.asarray(h))
    slot = jnp.asarray(rng.integers(0, P, rows).astype(np.int32))
    n_buf = leafperm.wired_tiles_bound(-(-rows // T), P)
    rec_lay, tile_run, run_slot = leafperm.initial_layout(
        rec_nat, slot, jnp.ones((P,), bool), P, n_buf)
    return leafperm, T, n_buf, rec_lay, tile_run, run_slot


def _build_permute_records(rows, F, B, P, seed):
    """The leafperm level move (``move_level``: per-tile parameters,
    counting pass, ``level_moves``, the move kernel).  The runs' split
    threshold walks with the carried scalar, so the whole move chain
    stays in the loop."""
    import jax.numpy as jnp

    leafperm, T, n_buf, rec_lay, tile_run, _ = _layout_fixture(
        rows, F, B, P, seed)
    bin_dtype = jnp.uint8 if B <= 256 else jnp.uint16
    # the contrib must be PERMUTATION-sensitive: a plain sum over records
    # is invariant under the move, and a single byte + the (tile-granular)
    # segment bases can coincide across nearby thresholds — so sample
    # ~256 records and weight them by position (a <=257-element gather,
    # noise next to the full-buffer move being timed)
    stride = max(1, (n_buf * T) // 256)

    def step(s, rec_lay, tile_run):
        # period-8 threshold walk: a period-2 alternation summed over K
        # trips gives the SAME contrib multiset at both liveness seeds
        # (the accumulator is order-independent) and reads as dead.
        # Every run splits on feature 0; bins are uniform in [0, B), so
        # the walk moves ~1/20 of the rows across per step
        smod = s - jnp.floor(s / 8.0) * 8.0
        run_rec = leafperm.pack_run_records(
            jnp.ones((P,)), jnp.zeros((P,)),
            jnp.full((P,), B * (0.30 + 0.05 * smod)))
        out, base_l, _ = leafperm.move_level(
            rec_lay, tile_run, run_rec, bin_dtype=bin_dtype)
        samp = out[::stride, 0].astype(jnp.float32)
        pos_w = jnp.arange(samp.shape[0], dtype=jnp.float32) + 1.0
        return (s + 1.0,
                jnp.dot(samp, pos_w) + base_l[P].astype(jnp.float32))

    return step, (rec_lay, tile_run), {"rows": rows, "num_slots": P}


def _build_hist_from_layout(rows, F, B, P, seed):
    """The layout histogram read (tile plan + in-place kernel): the
    selection rotates over the P runs, so a different run is segment 0
    every trip."""
    import jax.numpy as jnp

    leafperm, T, n_buf, rec_lay, tile_run, _ = _layout_fixture(
        rows, F, B, P, seed)
    bin_dtype = jnp.uint8 if B <= 256 else jnp.uint16
    tr = np.asarray(tile_run)
    first = np.zeros(P, np.int32)
    ntiles = np.zeros(P, np.int32)
    for r_ in range(P):
        w = np.nonzero(tr == r_)[0]
        if w.size:
            first[r_], ntiles[r_] = w[0], w.size
    n_sel = int(np.maximum(ntiles, 1).sum())   # rotation-invariant bound
    sf0, sn0 = jnp.asarray(first), jnp.asarray(ntiles)

    def step(s, rec_lay, sf, sn):
        si = s.astype(jnp.int32)
        hist = leafperm.hist_from_layout(
            rec_lay, jnp.roll(sf, si), jnp.roll(sn, si), P, B, F,
            bin_dtype, n_sel)
        return s + 1.0, hist[0, 0].sum()

    return step, (rec_lay, sf0, sn0), {"rows": rows, "num_slots": P}


def _build_route_gather(rows, F, B, P, seed):
    """The wired growers' per-level route: run->packed-word compose + ONE
    per-row small-table gather (the dominant wired-only bookkeeping cost).
    The run table is ROLLED by the carried scalar — a non-carried table is
    exactly the r10 LICM hoist this harness exists to reject."""
    import jax.numpy as jnp

    leafperm, T, n_buf, _, tile_run, run_slot = _layout_fixture(
        rows, F, B, P, seed)

    def step(s, tile_run, run_slot):
        si = s.astype(jnp.int32)
        rs_i = jnp.roll(run_slot, si)
        w0 = (jnp.uint32(1) << 31) | jnp.arange(P, dtype=jnp.uint32)
        tab = jnp.concatenate([w0, jnp.zeros((1,), jnp.uint32)])
        rr = tab[jnp.minimum(rs_i, P)][jnp.repeat(tile_run, T)]
        lo = (rr & jnp.uint32(0xFFFF)).astype(jnp.float32)
        return s + 1.0, lo[0] + lo[lo.shape[0] // 2] + lo[-1]

    return step, (tile_run, run_slot), {"rows": rows, "num_slots": P}


def _build_partition_reduce(rows, F, B, P, seed):
    """The partition column-select's masked-reduce arm (levelwise
    ``select_bins`` when ``partition_prefers_reduce`` admits): max over
    the CONTIGUOUS (N, F) matrix where the per-row feature id matches.
    The rf vector is ROLLED by the carried scalar so the selected column
    set changes every iteration — a whole-unit advance into integer
    indices, the r5 dead-input class this harness rejects.  Comparison
    arm for the r23 ``partition`` calibration gate (vs the gather probe
    below at the same shape)."""
    import jax.numpy as jnp

    rng, Xb, _, _ = _synth(rows, F, B, seed)
    rf = jnp.asarray(rng.integers(0, F, size=rows).astype(np.int32))
    Xb = jnp.asarray(Xb)

    def step(s, Xb, rf):
        si = s.astype(jnp.int32)
        rfi = jnp.roll(rf, si)
        iota_f = jnp.arange(Xb.shape[1], dtype=jnp.int32)
        sel = jnp.max(
            jnp.where(rfi[:, None] == iota_f[None, :], Xb,
                      jnp.zeros((), Xb.dtype)),
            axis=1).astype(jnp.float32)
        # whole-column SUM: the rolled rf re-selects random bins, so the
        # contrib moves by far more than its fp32 ulp
        return s + 1.0, sel[0] + jnp.sum(sel) / rows

    return step, (Xb, rf), {"rows": rows}


def _build_partition_gather(rows, F, B, P, seed):
    """The partition column-select's per-row gather arm
    (``take_along_axis`` into (N, F) — the ~per-ACCESS-cost formulation;
    CLAUDE.md gather facts).  Same fixture, perturbation, and contrib as
    the reduce probe so the pair is a clean A/B at any width."""
    import jax.numpy as jnp

    rng, Xb, _, _ = _synth(rows, F, B, seed)
    rf = jnp.asarray(rng.integers(0, F, size=rows).astype(np.int32))
    Xb = jnp.asarray(Xb)

    def step(s, Xb, rf):
        si = s.astype(jnp.int32)
        rfi = jnp.roll(rf, si)
        sel = jnp.take_along_axis(
            Xb, rfi[:, None], axis=1)[:, 0].astype(jnp.float32)
        return s + 1.0, sel[0] + jnp.sum(sel) / rows

    return step, (Xb, rf), {"rows": rows}


def _build_hist_reduce_scan(rows, F, B, P, seed, n_shards: int = 8):
    """The feature-parallel reduction's per-device scan stage (r16): the
    sliced best-split scan over ONE owned F/n feature slice + the packed
    record combine over all n shards' records — exactly what each shard
    computes per level under hist_reduce="feature" (the n-fold wire-
    payload cut itself is static accounting, _comm_stats / jaxpr census,
    not a single-device wall).  The other shards' records ride as fixed
    all-masked (-inf) args, so the perturbed owned slice always wins and
    the liveness signal flows scan -> combine -> contrib; the
    perturbation scales the histogram (gains are lambda_l2-inhomogeneous,
    same class as the split_scan probe — its fused scan is this probe's
    comparison arm at the same shape, bench.py hist_reduce_probe)."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.engine.split import (
        combine_local_splits,
        find_best_split_sliced,
        pack_local_split,
    )

    rng = np.random.default_rng(seed)
    Fs = -(-F // n_shards)
    # the jit ARGUMENT is the per-device operand — the OWNED (2P, 3, Fs,
    # B) slice, exactly what each shard scans under the feature arm (the
    # full-width stack would make the perturbation multiply ~n_shards
    # times wider than the measured stage and bias the fused-vs-feature
    # bench comparison toward parity, besides uploading n times the
    # bytes)
    hists = np.stack([
        rng.normal(size=(2 * P, Fs, B)),
        rng.uniform(0.1, 1.0, size=(2 * P, Fs, B)),
        rng.uniform(0.5, 2.0, size=(2 * P, Fs, B)),
    ], axis=1).astype(np.float32) * (rows / max(B, 1))
    hh0 = jnp.asarray(hists)
    fmask0 = jnp.ones((Fs,), bool)
    iscat0 = jnp.zeros((Fs,), bool)
    allow = jnp.ones((2 * P,), bool)
    # global node stats: scalars in the real arm (root/prefix records,
    # never histogram re-sums) — scaled with the perturbation below so
    # the gain grids stay consistent with the perturbed slice
    G0 = jnp.asarray(hists[:, 0].sum(axis=(1, 2)) * n_shards)
    H0 = jnp.asarray(hists[:, 1].sum(axis=(1, 2)) * n_shards)
    C0 = jnp.asarray(hists[:, 2].sum(axis=(1, 2)) * n_shards)

    def sliced(hh_slice, G_, H_, C_, fmask):
        def one(hh_, g_, h_, c_):
            return find_best_split_sliced(
                hh_, g_, h_, c_, feat_offset=jnp.int32(0),
                num_features_total=F, lambda_l2=1.0, min_child_weight=1e-3,
                min_data_in_leaf=20, feat_mask=fmask, is_cat_feat=iscat0,
                has_cat=False)
        return jax.vmap(one)(hh_slice, G_, H_, C_)

    # the non-owned shards' records: the SAME sliced scan, fully masked
    # (-inf gains) — realistic combine width, deterministic loser rows
    dead = pack_local_split(sliced(hh0, G0, H0, C0,
                                   jnp.zeros((Fs,), bool)))
    other_words = jnp.broadcast_to(dead[None],
                                   (n_shards - 1,) + dead.shape)

    def step(s, hh, G_, H_, C_, other):
        smod = s - jnp.floor(s / 8.0) * 8.0  # period-8 walk (module rule)
        scale = 1.0 + 0.01 * smod
        hh2 = hh * scale                     # gains are scale-sensitive
        words0 = pack_local_split(sliced(hh2, G_ * scale, H_ * scale,
                                         C_ * scale, fmask0))
        words = jnp.concatenate([words0[None], other], axis=0)
        res = combine_local_splits(words, None, allow=allow,
                                   min_split_gain=0.0, has_cat=False)
        return s + 1.0, res.gain[0] + res.gain[-1]

    return step, (hh0, G0, H0, C0, other_words), {"rows": rows,
                                                  "num_slots": P,
                                                  "n_shards": n_shards}


def _build_predict_traversal(rows, F, B, P, seed, depth: int = 6):
    """Per-tree traversal (tree_leaves) on a synthetic complete tree.  The
    thresholds shift by the carried parity — ~N/B rows per node change
    sides, so the leaf-id SUM moves by far more than its fp32 ulp (the
    contrib must not round the liveness signal away)."""
    import jax.numpy as jnp

    from dryad_tpu.engine.predict import tree_leaves

    rng, Xb, _, _ = _synth(rows, F, B, seed)
    n_internal = (1 << depth) - 1
    M = (1 << (depth + 1)) - 1
    feature = np.full(M, -1, np.int32)
    feature[:n_internal] = rng.integers(0, F, n_internal)
    threshold = np.zeros(M, np.int32)
    threshold[:n_internal] = rng.integers(B // 4, (3 * B) // 4, n_internal)
    nodes = np.arange(M, dtype=np.int32)
    tree = {
        "feature": jnp.asarray(feature),
        "threshold": jnp.asarray(threshold),
        "left": jnp.asarray(np.minimum(2 * nodes + 1, M - 1)),
        "right": jnp.asarray(np.minimum(2 * nodes + 2, M - 1)),
        "default_left": jnp.ones((M,), bool),
        "is_cat": jnp.zeros((M,), bool),
        "cat_bitset": jnp.zeros((M, max(1, -(-B // 32))), jnp.uint32),
    }
    Xb = jnp.asarray(Xb)

    def step(s, Xb, tr):
        si = s.astype(jnp.int32)
        # period-8 shift (not parity): seed windows must differ as
        # multisets, not just in order — see the permute probe's note
        lv = tree_leaves({**tr, "threshold": tr["threshold"] + si % 8},
                         Xb, depth)
        return s + 1.0, jnp.sum(lv.astype(jnp.float32))

    return step, (Xb, tree), {"rows": rows, "depth": depth}


def _build_predict_traversal_packed(rows, F, B, P, seed, depth: int = 6):
    """The r21 packed node-word twin of ``predict_traversal``: the SAME
    synthetic tree packed into the (M, 2)-uint32 limb table, numeric
    program (no cat_bitset key), so the per-level body is one node-word
    gather + the row's bin through ``select_bins``.  The perturbation bumps limb1's
    threshold field (low 16 bits) by the carried period-8 parity — the
    synthetic thresholds top out at 3B/4, so +7 can never carry into the
    feature bits, and the liveness signal is the legacy probe's exactly."""
    import jax.numpy as jnp

    from dryad_tpu.engine.predict import pack_node_words, tree_leaves

    rng, Xb, _, _ = _synth(rows, F, B, seed)
    n_internal = (1 << depth) - 1
    M = (1 << (depth + 1)) - 1
    feature = np.full(M, -1, np.int32)
    feature[:n_internal] = rng.integers(0, F, n_internal)
    threshold = np.zeros(M, np.int32)
    threshold[:n_internal] = rng.integers(B // 4, (3 * B) // 4, n_internal)
    nodes = np.arange(M, dtype=np.int32)
    words = pack_node_words(
        feature, threshold,
        np.minimum(2 * nodes + 1, M - 1), np.minimum(2 * nodes + 2, M - 1),
        np.ones(M, bool), np.zeros(M, bool))
    Xb = jnp.asarray(Xb)
    nw = jnp.asarray(words)

    def step(s, Xb, nw):
        si = s.astype(jnp.int32)
        bump = jnp.array([0, 1], jnp.uint32) * (si % 8).astype(jnp.uint32)
        lv = tree_leaves({"node_word": nw + bump}, Xb, depth)
        return s + 1.0, jnp.sum(lv.astype(jnp.float32))

    return step, (Xb, nw), {"rows": rows, "depth": depth}


def _build_goss_sort(rows, F, B, P, seed):
    """The GOSS arm's +1 global sort per iteration (threshold quantile).
    Perturb the SORT KEY itself — a rolled key would sort to the same
    output and read as dead (sort(roll(x)) == sort(x))."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    absg = jnp.asarray(np.abs(rng.normal(size=rows)).astype(np.float32))
    u = jnp.asarray(rng.uniform(0.0, 1.0, rows).astype(np.float32))
    top_n = max(1, int(round(0.2 * rows)))

    def step(s, absg, u):
        smod = s - jnp.floor(s / 8.0) * 8.0
        key = absg + 0.125 * smod * u        # perturb the SORT KEY
        thr = jnp.sort(key)[key.shape[0] - top_n]
        return s + 1.0, thr

    return step, (absg, u), {"rows": rows}


def _build_renewal_sort(rows, F, B, P, seed, M: int = 256):
    """The L1-family renewal's +1 global (leaf, residual) two-key sort per
    tree + the segment searchsorted.  Leaf ids rotate mod M, so a
    different leaf's residuals sort first every trip."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    lv = jnp.asarray(rng.integers(0, M, rows).astype(np.int32))
    r = jnp.asarray(rng.normal(size=rows).astype(np.float32))

    def step(s, lv, r):
        si = s.astype(jnp.int32)
        lv2 = (lv + si) % M
        lv_s, r_s = jax.lax.sort((lv2, r), num_keys=2)
        bounds = jnp.searchsorted(lv_s, jnp.arange(M + 1, dtype=jnp.int32))
        return s + 1.0, r_s[0] + bounds[1].astype(jnp.float32)

    return step, (lv, r), {"rows": rows}


PROBES: dict[str, StageProbe] = {p.name: p for p in (
    StageProbe("hist_masked",
               "masked Pallas/XLA histogram (root & shallow levels)",
               _build_hist_masked),
    StageProbe("hist_segmented",
               "segmented Pallas/XLA histogram incl. its tile plan",
               _build_hist_segmented),
    StageProbe("split_scan",
               "vmapped best-split scan over 2P children",
               _build_split_scan),
    StageProbe("hist_reduce",
               "feature-parallel per-device stage: sliced F/8 split scan "
               "+ packed record combine (hist_reduce='feature')",
               _build_hist_reduce_scan),
    StageProbe("permute_records",
               "leafperm level move (count + level_moves + permute)",
               _build_permute_records),
    StageProbe("hist_from_layout",
               "layout histogram read (tile plan + in-place kernel)",
               _build_hist_from_layout),
    StageProbe("route_gather",
               "wired per-level packed route small-table gather",
               _build_route_gather),
    StageProbe("partition_reduce",
               "partition column-select, masked-reduce arm (select_bins)",
               _build_partition_reduce),
    StageProbe("partition_gather",
               "partition column-select, per-row gather arm",
               _build_partition_gather),
    StageProbe("predict_traversal",
               "per-tree traversal (tree_leaves) on a depth-6 tree",
               _build_predict_traversal),
    StageProbe("predict_traversal_packed",
               "packed node-word traversal (one table gather/level, r21)",
               _build_predict_traversal_packed),
    StageProbe("goss_sort",
               "GOSS global quantile sort (+1 sort/iteration arm)",
               _build_goss_sort),
    StageProbe("renewal_sort",
               "L1-renewal global (leaf, residual) two-key sort (+1/tree)",
               _build_renewal_sort),
)}

#: the cheap on-device smoke tier (scripts/smoke_tpu.py --gate)
SMOKE_PROBES = ("hist_segmented", "split_scan", "route_gather")


def run_probe(name: str, rows: Optional[int] = None, K: int = DEFAULT_K,
              reps: int = DEFAULT_REPS, *, num_features: int = 28,
              total_bins: int = 256, num_slots: int = 64, seed: int = 5,
              check_live: bool = True) -> dict:
    """Build + liveness-prove + time one named stage probe."""
    import jax

    probe = PROBES[name]
    if check_live and K >= WALK_PERIOD:
        # a full walk cycle per window makes the two liveness windows the
        # same multiset — the proof would reject a LIVE stage; fail the
        # configuration loudly instead of reporting a misleading "dead"
        raise ValueError(
            f"K={K} >= the probes' perturbation walk period "
            f"({WALK_PERIOD}): the liveness proof cannot distinguish "
            "seeds over whole cycles; use K < "
            f"{WALK_PERIOD} (or check_live=False)")
    platform = jax.devices()[0].platform
    if rows is None:
        rows = DEFAULT_ROWS_CPU if platform == "cpu" else DEFAULT_ROWS_DEVICE
    step, args, meta = probe.build(rows, num_features, total_bins,
                                   num_slots, seed)
    ms, spread = timed_fori(step, K, reps, *args, label=name,
                            check_live=check_live)
    out = {"stage": name, "ms": round(ms, 3), "spread": round(spread, 4),
           "K": K, "reps": reps, "platform": platform}
    out.update(meta)
    return out


def dead_probe_step():
    """The selftest fixture: the r5/r10 failure class reproduced on
    purpose.  The perturbation is consumed only through a rounded-away
    integer cast (``* 1e-30`` rather than ``+ tiny`` so the AST
    ``dead-perturbation`` rule stays silent — the RUNTIME proof must
    catch what the lint cannot), so the sort is loop-invariant and the
    fetched accumulator is seed-independent."""
    import jax.numpy as jnp

    def step(s, x):
        si = (s * 1e-30).astype(jnp.int32)       # always 0 — a dead input
        y = jnp.sort(x + si.astype(jnp.float32))  # hoistable stage
        return s + 1.0, y[0]

    return step


def run_selftest(rows: int = 4096, num_slots: int = 8,
                 quiet: bool = False) -> int:
    """The ci.sh gate: the liveness proof must FIRE on the seeded dead
    probe and PASS on every shipped probe (CPU, seconds).  Returns a
    process exit code."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=rows).astype(np.float32))
    try:
        timed_fori(dead_probe_step(), 2, 1, x, label="seeded-dead")
    except DeadProbeError as e:
        if not quiet:
            print(f"selftest: seeded dead probe rejected ({e})")
    else:
        print("PROFILE SELFTEST FAIL: the seeded dead-perturbation probe "
              "was NOT caught — the liveness proof is broken")
        return 1
    failed = 0
    for name in PROBES:
        try:
            r = run_probe(name, rows=rows, K=2, reps=1,
                          num_slots=num_slots)
        except Exception as e:  # noqa: BLE001 — aggregate, report, exit 1
            failed += 1
            print(f"PROFILE SELFTEST FAIL: {name}: {e}")
            continue
        if not quiet:
            print(f"selftest: {name} live "
                  f"({r['ms']:.2f} ms on {r['platform']})")
    if failed:
        return 1
    print(f"PROFILE SELFTEST OK: dead probe caught, "
          f"{len(PROBES)} probes liveness-proven")
    return 0
