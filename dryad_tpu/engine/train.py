"""``dryad.train`` device backend: the boosting loop driving the compiled
grower (SURVEY.md §3 train call stack).

Orchestration (objective dispatch, bagging draw, early stopping, callbacks,
resume) stays on the host — it is O(1) per iteration; every O(N) step
(grad/hess, histogramming, partition, traversal, score update) runs on
device under one jit program per (shapes, params) pair.

**No per-iteration host↔device synchronization.**  A fetch makes the host
wait for everything queued before it and leaves the device idle until the
next dispatch arrives, so the trained tree arrays live on device (written
into preallocated (T, ...) output buffers) and are fetched exactly once
when training ends.  Iterations therefore dispatch
asynchronously and pipeline; the only forced syncs are per-iteration metric
evaluation when a validation set is supplied.

Bagging/colsample masks come from the same host-side Philox draw as the CPU
reference trainer (``cpu/trainer.py::sample_masks``), so sampling can never
break cross-backend parity.
"""

from __future__ import annotations

import contextlib
import os
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.booster import CAT_WORDS, Booster
from dryad_tpu.config import Params, effective_depth_params
from dryad_tpu.cpu.trainer import (
    dart_drop_set,
    goss_uniform,
    sample_masks,
    update_best,
)
from dryad_tpu.dataset import Dataset

# compile-boundary introspection (r12): dryad_prog_* cost/memory capture
# + the recompile-tripwire key notes.  Called ONLY at compile boundaries
# (dryadlint introspect-compile-only); observation-only — the traced
# programs are untouched (the analysis goldens are the proof)
from dryad_tpu.engine import introspect
from dryad_tpu.engine.grower import grow_any
from dryad_tpu.engine.predict import (_accumulate, pack_node_words_device,
                                      packed_shapes_fit, tree_leaves)
from dryad_tpu.objectives import get_objective

# per-stage span series (dryad_tpu/obs): host wall around work this loop
# already does — dispatch cost on the async sites, real fetch wall on the
# fetch sites.  Never a new device fetch; zero-cost when disabled.  Each
# span is also an annotation on the jax profiler's clock (engine/__init__),
# so a profile lays them beside the dryad.* stages of the device programs.
from dryad_tpu.obs.registry import default_registry
from dryad_tpu.obs.spans import annotation, record_at, span
from dryad_tpu.obs.tripwire import default_tripwire

# fetch-stall watchdog (r12): every REAL device->host fetch below is
# bracketed so the in-flight age is a live gauge and a stall flips
# /healthz while the run is still recoverable.  Null context when obs is
# disabled.
from dryad_tpu.obs.watchdog import watch_fetch

_TREE_KEYS = ("feature", "threshold", "left", "right", "value", "is_cat",
              "cat_bitset", "gain", "default_left", "cover")
# what a tree's traversal reads, in ``pack_node_words``'s argument order
_WALK_KEYS = ("feature", "threshold", "left", "right", "default_left",
              "is_cat")
# per-tree statistics only the batched leaf-wise grower returns; kept in
# ``out`` beside the trees where that grower runs (see _count_grow_stats)
_GROW_STATS = ("expanded_splits", "selected_splits")

# widest (features * bins) program the chunked fori wrapper may compile.
# Round 2 measured Epsilon-shaped (2000 x 256) chunk programs failing
# to compile; after the round-3 pipeline shrink (8-row weight buffers,
# no sentinel concatenates, u8 tiles) the same shape compiles in ~70 s and
# runs, so the limit is now the VERIFIED 2000*256 with headroom kept as a
# guard, not a cliff (VERDICT r2 #6)
_CHUNK_FB_LIMIT = 1 << 19


def _renew_values(value, feature, leaves, y, score_k, bag, alpha, lr, M):
    """Post-growth leaf renewal (objectives.renew_alpha): replace each
    leaf's Newton value with the type-1 (inverse-CDF, no interpolation)
    alpha-quantile of its in-bag residuals y - score, times the shrinkage.

    Convention shared BITWISE with cpu/trainer.renew_leaf_values_np: the
    order statistic at index clip(ceil(f32(alpha)·f32(cnt)) - 1, 0, cnt-1)
    is a pure element selection — no interpolation arithmetic — so both
    backends pick the identical f32 value and the only cross-backend
    wobble is the residuals' own ulp-level score differences.  One global
    two-key sort (leaf id primary, residual secondary; out-of-bag rows get
    sentinel id M and sink to the tail) + a searchsorted for the segment
    bounds — O(N log N) per tree, paid only by the robust objectives."""
    r = y - score_k
    lv = jnp.where(bag, leaves.astype(jnp.int32), M)
    lv_s, r_s = jax.lax.sort((lv, r), num_keys=2)
    bounds = jnp.searchsorted(lv_s, jnp.arange(M + 1, dtype=jnp.int32))
    cnt = bounds[1:] - bounds[:-1]                       # (M,) per node
    kf = jnp.ceil(jnp.float32(alpha) * cnt.astype(jnp.float32))
    kidx = jnp.clip(kf.astype(jnp.int32) - 1, 0, jnp.maximum(cnt - 1, 0))
    sel = jnp.clip(bounds[:-1] + kidx, 0, r_s.shape[0] - 1)
    stat = r_s[sel] * jnp.float32(lr)
    return jnp.where((feature < 0) & (cnt > 0), stat, value)


def _row_records(key_leaf, value, row_key):
    """Each row's leaf value and leaf id, ``value[key_leaf[row_key]]`` and
    ``key_leaf[row_key]``, from ONE look-up a row.

    The two tables are tiny (leaf slots or heap nodes -> tree node ->
    value), so they are composed first, table-sized, into one two-word
    record a key — word 0 the f32 value's own bits, word 1 the leaf id —
    and the rows gather that.  A row-sized gather from a table of two-word
    rows costs a quarter of one from a 1-D table on the chip (21.0 against
    86.7 ms for 10M indices, PERF.md), and the value is bitwise the one the
    two look-ups in sequence give."""
    rec = jnp.stack(
        [jax.lax.bitcast_convert_type(value[key_leaf], jnp.uint32),
         key_leaf.astype(jnp.uint32)], axis=1)
    got = rec[jnp.clip(row_key, 0, key_leaf.shape[0] - 1)]
    # each word by a masked reduce over the record's two, not by a slice:
    # the result is rank 1, where a sliced (N, 1) column (and the score
    # column added to it) was kept in the gather's padded two-word layout,
    # 512 B a row (the compiler's account at 10M x 28: 2.5 GB of temporaries)
    bits, leaf = (jnp.bitwise_or.reduce(got & jnp.array(mask, jnp.uint32),
                                        axis=1)
                  for mask in ((0xFFFFFFFF, 0), (0, 0xFFFFFFFF)))
    return (jax.lax.bitcast_convert_type(bits, jnp.float32),
            leaf.astype(jnp.int32))


def _step_body(p, B, has_cat, mesh, platform, learn_missing, out, score, Xb,
               g_all, h_all, bag, fmask, is_cat_feat, t, k, root_hist=None,
               bmask=None, n_rows=None, value_scale=None, y=None,
               renew_alpha=None):
    """One (iteration, class) tree: grow, record into slot t, update scores.

    Shared by the per-iteration ``_step_jit`` dispatch and the chunked
    ``_chunk_jit`` fast path, so the two can never diverge.  ``root_hist``
    carries the class's slice of the shared-plan multiclass root pass
    (single-device path only).  ``renew_alpha`` (static) turns on L1-family
    leaf renewal — the residuals are taken against the PRE-update score,
    the same ensemble the gradients saw.
    """
    out = dict(out)
    with jax.named_scope("dryad.grad"):
        g = jnp.take(g_all, k, axis=1)
        h = jnp.take(h_all, k, axis=1)
    if mesh is not None:
        from dryad_tpu.engine.distributed import grow_sharded

        tree = grow_sharded(
            p, B, has_cat, mesh, Xb, g, h, bag, fmask, is_cat_feat,
            platform=platform, learn_missing=learn_missing,
            root_hist=root_hist, bundled_mask=bmask,
            # UNPADDED global N: the envelope policy must see the same
            # rows at every shard count (and as the CPU mirror)
            global_rows=n_rows,
        )
    else:
        tree = grow_any(p, B, Xb, g, h, bag, fmask, is_cat_feat,
                        has_cat=has_cat, platform=platform,
                        learn_missing=learn_missing, root_hist=root_hist,
                        bundled_mask=bmask)
    # each row's leaf comes straight out of the grower's partition state
    # (key_leaf[row_key]) — re-traversing 10M rows cost ~5 s/tree
    row_key, key_leaf = tree.pop("row_key"), tree.pop("key_leaf")
    for key in _GROW_STATS:
        if key in tree and key in out:
            out[key] = out[key].at[t].set(tree[key])
    with jax.named_scope("dryad.score"):
        value = tree["value"]
        if renew_alpha is not None:
            # renewal needs each row's leaf before the leaf has its value
            _, leaves = _row_records(key_leaf, value, row_key)
            value = _renew_values(
                value, tree["feature"], leaves, y,
                jnp.take(score, k, axis=1), bag, renew_alpha,
                p.effective_learning_rate, p.max_nodes)
        if value_scale is not None:
            # DART: the new tree lands pre-scaled by 1/(k+1) — same f32
            # multiply order as the CPU mirror (finalize with lr, then scale)
            value = value * value_scale
        tree = dict(tree, value=value)
        row_value, _ = _row_records(key_leaf, value, row_key)
        col = jnp.take(score, k, axis=1) + row_value
        score = jax.lax.dynamic_update_index_in_dim(score, col, k, axis=1)
        for key in _TREE_KEYS:
            out[key] = out[key].at[t].set(tree[key])
        out["max_depth"] = out["max_depth"].at[t].set(tree["max_depth"])
    return out, score


_step_jit = partial(jax.jit,
                    static_argnames=("p", "B", "has_cat", "mesh", "platform",
                                     "learn_missing", "n_rows",
                                     "renew_alpha"))(_step_body)
# Module-level jit keyed on the static (params, bins, mesh) triple — the
# compiled program is reused across ``train_device`` calls (a closure-local
# jit would recompile per call and dwarf the training itself).  out/score
# are NOT donated: double-buffering a 40 MB score is free next to the
# grower's working set.


@jax.named_scope("dryad.grad")
def _grads_body(p, N, K, pad, score, y, weight, qoff, rank_row_ids,
                rank_col_ids, rank_Q, rank_S):
    """Per-iteration grad/hess (N+pad, K) from the pre-iteration score.

    All K class trees of one boosting iteration share this single pass —
    exactly the CPU reference's semantics.
    """
    obj = get_objective(p)
    if p.objective == "lambdarank":
        from dryad_tpu.engine.lambdarank import PaddingPlan, grad_hess_ranking

        plan = PaddingPlan.__new__(PaddingPlan)
        plan.Q, plan.S = rank_Q, rank_S
        plan.row_ids, plan.col_ids = rank_row_ids, rank_col_ids
        w_rank = None if weight is None else weight[:N]
        g, h = grad_hess_ranking(obj, score[:N, 0], y[:N], w_rank, qoff,
                                 plan=plan)
        if pad:
            g = jnp.pad(g, (0, pad))
            h = jnp.pad(h, (0, pad))
        return g[:, None], h[:, None]
    if K > 1:
        return obj.grad_hess_jax(score, y, weight)
    g, h = obj.grad_hess_jax(score[:, 0], y, weight)
    return g[:, None], h[:, None]


_grads_jit = introspect.whole_program("dryad.grad", partial(
    jax.jit, static_argnames=("p", "N", "K", "pad", "rank_Q",
                              "rank_S"))(_grads_body))


def _grow_iteration(p, B, has_cat, mesh, platform, learn_missing, out, score,
                    Xb, y, g_all, h_all, bag_i, fmask_i, is_cat_feat, it, K,
                    bmask=None, n_rows=None, renew_alpha=None):
    """GOSS amplification + shared-plan multiclass roots + the K class
    trees of ONE boosting iteration (``it`` is the traced global iteration
    id; tree slots ``it*K + k``).  The single assembly shared by the
    chunked device loop and ``audit_iteration_fn`` — the jaxpr auditor's
    arms audit the trained program BY CONSTRUCTION, not a replica."""
    if p.boosting == "goss":
        # device-drawn uniforms (bit-identical to the host generator)
        # make GOSS chunkable: no per-iteration upload, same selection
        with jax.named_scope("dryad.grad"):
            u = _goss_uniform_dev(p.seed, it, score.shape[0])
        g_all, h_all, bag_i = _goss_body(p, n_rows, g_all, h_all, u, bag_i)
    roots = None
    if K > 1 and _shared_roots_ok(p, platform):
        # shared-plan multiclass roots: all K trees' root histograms in
        # one matmul pass (2K+1 weight rows — histogram.py).  The mesh
        # path runs the SAME builder under shard_map: the (2K+1)-row
        # MXU lowering is fusion-sensitive (measured NOT bitwise vs the
        # 3-row pass on device), so both paths must share one program
        # or near-tie root argmaxes could differ 1-shard vs N-shard.
        if mesh is not None:
            from dryad_tpu.engine.distributed import roots_sharded

            roots = roots_sharded(mesh, Xb, g_all, h_all, bag_i, B,
                                  p.rows_per_chunk, p.hist_precision)
        else:
            from dryad_tpu.engine.histogram import build_hist_classes

            roots = build_hist_classes(
                Xb, g_all, h_all, bag_i, B,
                rows_per_chunk=p.rows_per_chunk,
                precision=p.hist_precision)
    for k in range(K):
        t = it * K + k
        out, score = _step_body(
            p, B, has_cat, mesh, platform, learn_missing, out, score,
            Xb, g_all, h_all, bag_i, fmask_i, is_cat_feat, t, k,
            root_hist=None if roots is None else roots[k], bmask=bmask,
            n_rows=n_rows, y=y, renew_alpha=renew_alpha)
    return out, score


def _fresh_tree(out, t, num_features: int, B: int, has_cat: bool) -> dict:
    """Tree slot ``t`` of the output tables as ``tree_leaves`` walks it
    fastest (traced; ``t`` may be a traced scalar), with its ``value``.

    Where every tree of these static shapes fits the packed widths, the
    traversal fields are packed on the device into ``predict``'s (M, 2)
    node-word table (a few integer operations over M entries), and
    ``cat_bitset`` rides along only where the model has categorical
    splits, as ``stage_trees`` stages it: one table gather a level and
    ``select_bins``, the walk route does over the train rows.  A shape past
    a packed width keeps the structure-of-arrays tables.  Both arms compare
    the same integers, so the leaves are bitwise the same.  The arm taken
    is the gauge ``dryad_eval_walk{arm}``, set here, at trace time."""
    if packed_shapes_fit(num_features, B, out["feature"].shape[1]):
        arm = "packed"
        tree = {"node_word": pack_node_words_device(
                    *(out[key][t] for key in _WALK_KEYS)),
                "value": out["value"][t]}
        if has_cat:
            tree["cat_bitset"] = out["cat_bitset"][t]
    else:
        arm = "legacy"
        tree = {key: out[key][t] for key in _TREE_KEYS}
    reg = default_registry()
    if reg.enabled:
        walk = reg.gauge("dryad_eval_walk",
                         "Table layout the training eval's tree walk "
                         "takes (1 = active): packed node words, or the "
                         "legacy structure of arrays past a packed width")
        for name in ("packed", "legacy"):
            walk.labels(arm=name).set(float(name == arm))
    return tree


@partial(jax.jit,
         static_argnames=("p", "B", "has_cat", "mesh", "platform",
                          "learn_missing", "N", "K", "pad", "rank_Q",
                          "rank_S", "metric_names", "ndcg_at", "eval_period",
                          "total_iters", "renew_alpha"))
def _chunk_jit(p, B, has_cat, mesh, platform, learn_missing, N, K, pad,
               rank_Q, rank_S, out, score, Xb, y, weight, bag, fmask,
               is_cat_feat, qoff, rank_row, rank_col, it0, n_iters,
               bmask=None, bag_bits=None, fmask_chunk=None,
               metric_names=(), ndcg_at=10, eval_period=1, total_iters=0,
               vXbs=(), vys=(), vqids=(), vscores=(), eval_buf=None,
               eval_its=None, eval_cnt=None, init_arr=None,
               renew_alpha=None):
    """``n_iters`` whole boosting iterations inside ONE program.

    Each host dispatch is a gap in which the device may idle, so the
    boosting loop itself runs on device in blocks:
    grads are recomputed from the carried score each trip — identical
    semantics to per-iteration dispatch.  ``it0`` and ``n_iters`` are
    traced, so one compiled program serves every chunk and tail length.

    Round-3 extensions (VERDICT r2 #2) let realistic configs chunk too:

    * **Bagging/colsample** — the host's Philox draws (the CPU-parity
      anchor) upload per chunk: ``bag_bits`` (CH, ceil(NP/8)) uint8 packs
      each iteration's row mask little-endian (unpacked on device),
      ``fmask_chunk`` (CH, F) carries the per-iteration feature masks.
    * **Validation** — per-tree valid-set scores update inside the loop
      (tree_leaves on the freshly written tree slot) and every
      ``eval_period``-th iteration evaluates ALL sets on device
      (metrics.device.eval_value), appending one (n_sets,) row into the
      carried ``eval_buf`` with its iteration id in ``eval_its``.  Nothing
      is fetched here; the host decides when to look.
    """
    n_valid = len(metric_names)

    def body(i, carry):
        out, score, vscores, eval_buf, eval_its, eval_cnt = carry
        # the iteration's row sample rides the gradient stage's scope, as
        # GOSS's selection does
        with jax.named_scope("dryad.grad"):
            if bag_bits is not None:
                u8 = bag_bits[i]                   # (ceil(NP/8),) uint8
                bits = ((u8[:, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1)
                bag_i = bits.reshape(-1)[:score.shape[0]].astype(bool) & bag
            else:
                bag_i = bag
            fmask_i = fmask if fmask_chunk is None else fmask_chunk[i]
            # rf: grads at the CONSTANT init score (loop-invariant — XLA
            # hoists the computation out of the fori body); broadcast inside
            # the trace so no (NP, K) constant is baked into the program
            score_g = (jnp.broadcast_to(init_arr.astype(jnp.float32),
                                        score.shape)
                       if p.boosting == "rf" else score)
        g_all, h_all = _grads_body(p, N, K, pad, score_g, y, weight, qoff,
                                   rank_row, rank_col, rank_Q, rank_S)
        out, score = _grow_iteration(
            p, B, has_cat, mesh, platform, learn_missing, out, score, Xb, y,
            g_all, h_all, bag_i, fmask_i, is_cat_feat, it0 + i, K,
            bmask=bmask, n_rows=N, renew_alpha=renew_alpha)

        if n_valid:
            with jax.named_scope("dryad.eval"):
                slots = [(it0 + i) * K + k for k in range(K)]
                trees_k = [_fresh_tree(out, t, Xb.shape[1], B, has_cat)
                           for t in slots]
                new_vs = []
                for vi in range(n_valid):
                    vs = vscores[vi]
                    for k, (t, tree) in enumerate(zip(slots, trees_k)):
                        lv = tree_leaves(tree, vXbs[vi], out["max_depth"][t])
                        vs = vs.at[:, k].set(vs[:, k] + tree["value"][lv])
                    new_vs.append(vs)
                vscores = tuple(new_vs)

                from dryad_tpu.metrics.device import eval_value

                it_now = it0 + i
                do_eval = (((it_now + 1) % eval_period == 0)
                           | (it_now + 1 == total_iters))

                def write(args):
                    buf, its, cnt = args
                    if p.boosting == "rf":
                        # score the AVERAGED model — same fp32 transform as
                        # predict (_rf_avg_jit / cpu mirror); the reciprocal is
                        # an exact IEEE division (identical to the host's), the
                        # iteration count is traced so it can't be host-side
                        initf = init_arr.astype(jnp.float32)
                        inv_it = jnp.float32(1.0) / (it_now + 1).astype(jnp.float32)
                        vs_eval = [initf + (vscores[vi] - initf) * inv_it
                                   for vi in range(n_valid)]
                    else:
                        vs_eval = list(vscores)
                    vals = jnp.stack([
                        eval_value(metric_names[vi], ndcg_at, vys[vi],
                                   vs_eval[vi], vqids[vi])
                        for vi in range(n_valid)])
                    return (buf.at[cnt].set(vals), its.at[cnt].set(it_now),
                            cnt + 1)

                eval_buf, eval_its, eval_cnt = jax.lax.cond(
                    do_eval, write, lambda a: a, (eval_buf, eval_its, eval_cnt))
        return out, score, vscores, eval_buf, eval_its, eval_cnt

    return jax.lax.fori_loop(
        0, n_iters, body,
        (out, score, tuple(vscores), eval_buf, eval_its, eval_cnt))


def _comm_stats(p, F: int, B: int, K: int, n_shards: int,
                shared_roots: bool = False,
                num_rows: int | None = None,
                padded_rows: int | None = None,
                platform: str | None = None,
                has_cat: bool = False) -> dict:
    """Static per-iteration collective payload, PER ARM (SURVEY.md §5
    observability; r16).  The payload is a pure function of the growth
    policy's per-level candidate widths — no runtime instrumentation
    needed (and none would survive jit without a host sync) — and the
    jaxpr auditor cross-checks every call count against the traced
    program (analysis/jaxpr_audit.py).

    Byte convention: each collective is accounted by the REDUCED/GATHERED
    output it delivers per device — psum: the full (..., 3, F, B) f32
    stack (each device receives the whole reduced array; the pre-r16
    numbers are unchanged); reduce-scatter: that stack / n_shards (each
    device receives only its owned feature slice, the (n-1)/n payload cut
    the feature arm exists for); all-gather: the gathered record block
    (n_shards * records).  Exact for the histogram collectives — incl.
    shallow levels on the natural-order pass, which slices its fixed
    16-slot kernel output to the P live slots BEFORE the reduction
    (pallas_hist.build_hist_small; ADVICE r3 #1/#2); the GOSS global sort
    and init-time collectives are excluded.

    Per-arm plan (``hist_reduce`` key):
    * fused — ONE fused grad/hess/count psum per builder call (root +
      every level), the classic contract.
    * feature — the ROOT keeps its fused psum (root_stats reads feature
      0's bins and one slot is noise); every LEVEL builder call issues
      one reduce-scatter of the feature-padded stack, plus ONE combine
      all-gather of the level's 2P packed best-split records (~29 + B
      bytes each).  The sequential (per-split) grower never consults the
      knob — its arm always reports fused."""
    from dryad_tpu.config import hist_reduce_resolved

    fb = 3 * F * B * 4
    L = p.effective_num_leaves
    level_synchronous = True
    if p.growth == "depthwise" and p.max_depth > 0:
        D = p.max_depth
        # the gate predicate and phase boundary are the growers' OWN
        # helpers (pallas_hist.nat_gate_admits, levelwise.phase_plan) so
        # this accounting cannot drift from the program choice (ADVICE r4)
        from dryad_tpu.engine import levelwise, pallas_hist
        from dryad_tpu.engine.histogram import resolve_backend

        bin_bytes = 1 if B <= 256 else 2
        # the nat gate sees the PADDED global matrix (shard shapes), the
        # leafwise envelope below the UNPADDED N (grower.py rule)
        gate_rows = padded_rows if padded_rows is not None else num_rows
        # r10: a layout-wired tree never builds the nat tiles (the wired
        # gate is consulted FIRST in grow_tree_levelwise), so its phase
        # plan runs nat_live=False — mirror that here or the accounted
        # d_switch/widths drift from the executed program
        use_layout = levelwise.deep_layout_supported(p, F, B, bin_bytes,
                                                     platform)
        nat_live = (not use_layout
                    and gate_rows is not None
                    and resolve_backend(p.hist_backend, segmented=True,
                                        platform=platform) == "pallas"
                    and pallas_hist.supports(B)
                    and pallas_hist.nat_gate_admits(gate_rows, F, bin_bytes))
        d_switch, P_narrow, P_full = levelwise.phase_plan(D, L, nat_live)
        scan_widths = [P_narrow] * d_switch + [P_full] * (D - d_switch)
        widths = list(scan_widths)
        level_calls = len(widths)
        if not p.hist_subtraction:
            # both children are histogrammed (no subtraction): the wired
            # path (r10 lift) pays ONE 2P-column hist_from_layout
            # reduction per level, the legacy path a P-column small pass
            # PLUS a P-column build_hist_multi — same bytes, more calls
            widths = [2 * w for w in widths]
            if not use_layout:
                level_calls = 2 * level_calls
    else:
        from dryad_tpu.engine import leafwise_fast

        if (p.growth == "leafwise"
                and leafwise_fast.supports(p, F, B, num_rows, n_shards)):
            D = p.max_depth
            d_switch, P_narrow, Pf = leafwise_fast.phase_plan(D)
            scan_widths = [P_narrow] * d_switch + [Pf] * (D - d_switch)
            widths = list(scan_widths)
        else:
            widths = [1] * (L - 1)          # one masked pass per split
            scan_widths = list(widths)
            level_synchronous = False
        level_calls = len(widths)
    mode = (hist_reduce_resolved(p, F, B, n_shards)
            if level_synchronous else "fused")
    # multiclass shared-plan roots fold the K root passes into ONE psum of
    # the (K, 3, F, B) classes-builder output (same bytes, fewer calls)
    root_calls = 1 if (shared_roots and K > 1) else K
    if mode == "feature":
        n = max(int(n_shards), 1)
        fs = -(-F // n)                       # owned features per shard
        fb_slice = 3 * (fs * n) * B * 4 // n  # reduced slice delivered
        # one packed LocalSplit record per candidate child: the (8,)
        # uint32 word block (split.pack_local_split), plus the raw (B,)
        # bool categorical membership row on categorical configs — which
        # also rides its own gather, hence the per-level call count below
        rec_b = 8 * 4 + (B if has_cat else 0)
        ag_per_level = 2 if has_cat else 1
        psum_calls = root_calls
        psum_bytes = fb * K
        rs_calls = level_calls * K
        rs_bytes = K * sum(w * fb_slice for w in widths)
        ag_calls = len(scan_widths) * ag_per_level * K
        ag_bytes = K * sum(n * 2 * w * rec_b for w in scan_widths)
    else:
        psum_calls = root_calls + level_calls * K
        psum_bytes = (fb + sum(w * fb for w in widths)) * K  # root + levels
        rs_calls = rs_bytes = ag_calls = ag_bytes = 0
    return {
        "n_shards": int(n_shards),
        "hist_reduce": mode,
        "psum_calls_per_iter": psum_calls,
        "psum_bytes_per_iter": psum_bytes,
        "reduce_scatter_calls_per_iter": rs_calls,
        "reduce_scatter_bytes_per_iter": rs_bytes,
        "all_gather_calls_per_iter": ag_calls,
        "all_gather_bytes_per_iter": ag_bytes,
        "collective_calls_per_iter": psum_calls + rs_calls + ag_calls,
        "collective_bytes_per_iter": psum_bytes + rs_bytes + ag_bytes,
    }


def audit_iteration_fn(p, B, has_cat, mesh, platform, N, K=1, pad=0,
                       learn_missing=False, renew_alpha=None):
    """One whole boosting iteration as a pure traceable function — the
    jaxpr auditor's census hook (dryad_tpu/analysis/jaxpr_audit.py).

    Assembled from the SAME ``_grads_body`` / ``_goss_body`` /
    ``_step_body`` (plus the shared-plan multiclass root logic of
    ``_chunk_jit``) that the trainer dispatches, so the audited IR IS the
    trained program — a hand-maintained replica would drift exactly the
    way the grep lints this subsystem replaces did.  The returned function
    takes ``(out, score, Xb, y, bag, fmask, is_cat_feat)`` device arrays
    (abstract ``ShapeDtypeStruct`` values under ``jax.make_jaxpr``) and
    returns the updated ``(out, score)``; with ``mesh`` set the growers
    run under ``shard_map`` exactly as ``train_device`` runs them.
    Restricted to the arms the auditor traces: no lambdarank plan, no
    weights, no DART — those ride the per-iteration dispatch path whose
    collectives this same accounting already covers."""

    def fn(out, score, Xb, y, bag, fmask, is_cat_feat):
        g_all, h_all = _grads_body(p, N, K, pad, score, y, None, None,
                                   None, None, 0, 0)
        # iteration id traced (jnp.int32) exactly as the chunked loop's
        # it0 + i is — same program class, same dynamic tree-slot writes
        return _grow_iteration(
            p, B, has_cat, mesh, platform, learn_missing, out, score, Xb, y,
            g_all, h_all, bag, fmask, is_cat_feat, jnp.int32(0), K,
            n_rows=N, renew_alpha=renew_alpha)

    return fn


def audit_iteration_args(p, N: int, F: int, K: int = 1,
                         bin_dtype=jnp.uint8) -> tuple:
    """Abstract ``(out, score, Xb, y, bag, fmask, is_cat_feat)`` for the
    function ``audit_iteration_fn`` returns: trace or lower one boosting
    iteration at a given shape without allocating it (the jaxpr auditor's
    arms; ``chip_smoke.py``'s check that the program the device runs
    carries compiled Mosaic kernels)."""
    sds = jax.ShapeDtypeStruct
    out = jax.eval_shape(
        lambda: _empty_out_device(K, p.max_nodes, CAT_WORDS))
    return (out,
            sds((N, K), jnp.float32),    # score
            sds((N, F), bin_dtype),      # Xb
            sds((N,), jnp.float32),      # y
            sds((N,), jnp.bool_),        # bag
            sds((F,), jnp.bool_),        # fmask
            sds((F,), jnp.bool_))        # is_cat_feat


def _shared_roots_ok(p, platform) -> bool:
    """Shared-plan (XLA classes-builder) roots for multiclass ONLY where
    the masked histogram backend resolves to XLA anyway (CPU / non-TPU):
    there one fused (2K+1)-row pass beats K one-hot passes.  On TPU the
    round-4 kernel made per-class masked Pallas roots the winner — 52 vs
    103 ms at Covertype K=3, a dead tie at K=7 (exp_r4_roots.py,
    stall-robust min-of-3) — so every class simply grows its own root
    through the SAME build_hist path used everywhere else (one program,
    1-shard ≡ N-shard trivially; VERDICT r3 #8 resolved by measurement).
    """
    from dryad_tpu.engine.histogram import resolve_backend

    return resolve_backend(p.hist_backend, platform=platform) != "pallas"


@partial(introspect.whole_program, "dryad.hist")
@partial(jax.jit, static_argnames=("B", "rpc", "precision", "mesh"))
def _roots_jit(B, rpc, precision, mesh, Xb, g_all, h_all, bag):
    """Shared-plan multiclass root histograms (per-iteration dispatch path);
    with a mesh, the same builder runs under shard_map + one fused psum."""
    if mesh is not None:
        from dryad_tpu.engine.distributed import roots_sharded

        return roots_sharded(mesh, Xb, g_all, h_all, bag, B, rpc, precision)
    from dryad_tpu.engine.histogram import build_hist_classes

    return build_hist_classes(Xb, g_all, h_all, bag, B, rows_per_chunk=rpc,
                              precision=precision)


def _goss_uniform_dev(seed: int, iteration, num_rows: int) -> jnp.ndarray:
    """Device twin of ``cpu.trainer.goss_uniform`` — the same u32
    murmur3-finalizer hash of (seed, iteration, row id), traced so the
    chunked boosting program draws each iteration's uniforms ON DEVICE
    (the upload that forced GOSS onto per-iteration dispatch is gone).
    ``iteration`` is a traced int32; bit-identity with the host generator
    is pinned by test_goss_monotone."""
    M1, M2 = jnp.uint32(0x85EBCA6B), jnp.uint32(0xC2B2AE35)
    key = (jnp.uint32((seed * 0x9E3779B9 + 0x165667B1) % (1 << 32))
           + iteration.astype(jnp.uint32) * jnp.uint32(0x7FEB352D))
    key ^= key >> jnp.uint32(16)
    key = key * M1
    key ^= key >> jnp.uint32(13)
    key = key * M2
    key ^= key >> jnp.uint32(16)
    x = jnp.arange(num_rows, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
    x ^= key
    x ^= x >> jnp.uint32(16)
    x = x * M1
    x ^= x >> jnp.uint32(13)
    x = x * M2
    x ^= x >> jnp.uint32(16)
    return (x >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(
        1.0 / (1 << 24))


@jax.named_scope("dryad.grad")
def _goss_body(p, N, g_all, h_all, u, valid):
    """Device GOSS (mirrors cpu/trainer.py::goss_select_np — both run the
    selection in f32 so boundary rows classify identically): amplified
    grad/hess + the row mask.  ``valid`` excludes padded rows, whose real
    gradients must never compete in the top-quantile."""
    absg = jnp.sqrt(jnp.sum(g_all.astype(jnp.float32) ** 2, axis=1))
    absg = jnp.where(valid, absg, jnp.float32(-1.0))
    top_n = max(1, int(round(p.goss_top_rate * N)))
    thr = jnp.sort(absg)[absg.shape[0] - top_n]
    is_top = valid & (absg >= thr)
    n_top = jnp.sum(is_top.astype(jnp.int32))
    p_pick = jnp.minimum(
        jnp.float32(1.0),
        jnp.float32(p.goss_other_rate * N)
        / jnp.maximum(N - n_top, 1).astype(jnp.float32))
    picked = valid & ~is_top & (u < p_pick)
    amp = jnp.float32((1.0 - p.goss_top_rate) / p.goss_other_rate)
    w = jnp.where(picked, amp, jnp.float32(1.0))[:, None]
    return g_all * w, h_all * w, is_top | picked


_goss_jit = introspect.whole_program("dryad.grad", partial(
    jax.jit, static_argnames=("p", "N"))(_goss_body))


_dart_replay_jit = partial(jax.jit, static_argnames=("depth_bound",))(
    lambda trees, Xb, init, depth_bound: _accumulate(
        trees, Xb, init, depth_bound))


@partial(introspect.whole_program, "dryad.eval")
@jax.jit
@jax.named_scope("dryad.eval")
def _rf_avg_jit(vs, init, inv):
    """rf eval transform: averaged raw score init + (Σ - init)*(1/n) with
    the HOST-computed reciprocal — the same arithmetic as both predict
    paths (cpu/predict.py), so the metric scores the model predict would
    serve (up to device FMA fusion of the multiply-add, a 1-ulp
    tie-flip-only difference)."""
    initf = init.astype(jnp.float32)
    return initf + (vs - initf) * inv


@partial(jax.jit, static_argnames=("depth_bound", "B", "has_cat"))
def _dart_drop_jit(out, score, tids, tcls, Xb, factor_drop, depth_bound, B,
                   has_cat):
    """DART drop bookkeeping in ONE dispatch: ``tids`` (max_drop*K,)
    padded with -1 names the dropped tree slots, ``tcls`` their class
    columns, ``factor_drop`` = f32(k/(k+1)) computed HOST-side (the same
    rounding the CPU mirror uses — deriving it on device as 1 - 1/(k+1)
    lands 1 ulp off at e.g. k=2 and would let near-tie splits diverge by
    backend).  Returns (score - dcontrib, value table with dropped rows
    * factor_drop).  ``depth_bound`` is a STATIC bound >= any tree's
    depth — traversal is exact for any such bound, and out["max_depth"]
    cannot be trusted here (resume restores tree arrays but not the
    per-slot depth log, and the resumed run must reproduce the
    uninterrupted one bitwise)."""

    def body(i, acc):
        t = jnp.maximum(tids[i], 0)
        tree = _fresh_tree(out, t, Xb.shape[1], B, has_cat)
        lv = tree_leaves(tree, Xb, depth_bound)
        c = tree["value"][lv] * (tids[i] >= 0).astype(jnp.float32)
        return acc.at[:, tcls[i]].add(c)

    dcontrib = jax.lax.fori_loop(0, tids.shape[0], body,
                                 jnp.zeros_like(score))
    T = out["value"].shape[0]
    newval = out["value"].at[
        jnp.where(tids >= 0, tids, T)].multiply(factor_drop, mode="drop")
    return score - dcontrib, newval


@partial(introspect.whole_program, "dryad.eval")
@partial(jax.jit, static_argnames=("B", "has_cat"))
@jax.named_scope("dryad.eval")
def _apply_valid_jit(out, t, vXb, vs_col, depth_bound, B, has_cat):
    tree = _fresh_tree(out, t, vXb.shape[1], B, has_cat)
    leaves = tree_leaves(tree, vXb, depth_bound)
    return vs_col + tree["value"][leaves]


def _empty_out_device(T: int, M: int, cat_words: int,
                      grow_stats: bool = False) -> dict:
    stats = {key: jnp.zeros((T,), jnp.int32) for key in _GROW_STATS} \
        if grow_stats else {}
    return {
        **stats,
        "feature": jnp.full((T, M), -1, jnp.int32),
        "threshold": jnp.zeros((T, M), jnp.int32),
        "left": jnp.zeros((T, M), jnp.int32),
        "right": jnp.zeros((T, M), jnp.int32),
        "value": jnp.zeros((T, M), jnp.float32),
        "is_cat": jnp.zeros((T, M), bool),
        "cat_bitset": jnp.zeros((T, M, cat_words), jnp.uint32),
        "gain": jnp.zeros((T, M), jnp.float32),
        "default_left": jnp.ones((T, M), bool),
        "cover": jnp.zeros((T, M), jnp.float32),
        "max_depth": jnp.zeros((T,), jnp.int32),
    }


def _grow_stats_of(out, lo: int, hi: int) -> tuple:
    """Device slices of the batched leaf-wise grower's statistics of trees
    [lo, hi), for a fetch that is made anyway; () where ``out`` carries
    none, nothing is new, or nobody counts."""
    if _GROW_STATS[0] not in out or lo >= hi or not default_registry().enabled:
        return ()
    return tuple(out[key][lo:hi] for key in _GROW_STATS)


def _count_grow_stats(fetched: tuple) -> None:
    """Fetched ``_grow_stats_of`` slices into their counters: splits the
    expansion grew, and splits the best-first selection kept of them."""
    for key, vals in zip(_GROW_STATS, fetched):
        default_registry().counter(
            f"dryad_leafwise_{key}_total",
            "Splits of the batched leaf-wise grower: expanded (every valid "
            "split to the depth cap) / selected (the best-first tree's)",
        ).inc(int(np.asarray(vals).sum()))


def _materialize(p, mapper, out, T, init, max_depth_prev, best_iteration,
                 best_value=None, stale=0) -> Booster:
    """Fetch the device tree tables (the one forced sync) into a Booster.

    ``T`` grows with every checkpoint, so each one slices to new shapes and
    compiles a handful of tiny programs; they count under the family
    ``train.materialize``, not under whatever boundary came last."""
    with introspect.attributed("train.materialize"):
        host = {key: np.asarray(out[key][:T]) for key in _TREE_KEYS}
        depths = np.asarray(out["max_depth"][:T])
    max_depth_seen = max(int(depths.max(initial=0)), max_depth_prev)
    return Booster(
        p, mapper,
        host["feature"], host["threshold"], host["left"], host["right"],
        host["value"], host["is_cat"], host["cat_bitset"],
        init, max_depth_seen,
        best_iteration=best_iteration,
        gain=host["gain"],
        train_state={"best_value": best_value, "stale": int(stale)},
        default_left=host["default_left"],
        cover=host["cover"],
    )


def train_device(
    params: Params,
    data: Dataset,
    valid: Optional[Dataset] = None,
    *,
    num_trees: Optional[int] = None,
    init_booster: Optional[Booster] = None,
    callback: Optional[Callable[[int, dict], None]] = None,
    mesh=None,
    checkpointer=None,
    chunk_hook: Optional[Callable[[str, int], None]] = None,
    chunk_policy=None,
) -> Booster:
    """Device trainer.  With ``mesh`` set, rows are sharded over the mesh's
    data axis and histograms allreduced by psum (engine/distributed.py).

    ``chunk_hook(site, iteration)`` observes the boosting loop's host-side
    events — ``site`` is ``"dispatch"`` (a chunk/iteration is about to be
    enqueued) or ``"fetch"`` (a real device->host fetch is about to run:
    calibration, run-ahead throttle, eval read, checkpoint/final
    materialize).  The resilience supervisor journals these and the
    deterministic fault injector raises the recorded device error classes
    from them (resilience/faults.py); ``None`` (the default) costs nothing.
    ``chunk_policy`` is a live cap on chunk length (``cap() -> int``, 0 =
    uncapped, plus ``note_dispatch(n)`` / ``note_clean_chunk(n)`` feedback
    — the dispatch-time length report is load-bearing: the policy's
    degrade step must undercut what actually ran) consulted per chunk
    AFTER path selection and calibration, so the supervisor's mid-run
    degradation can never flip the compiled program — only shorten chunks
    (resume bit-identity is preserved by construction; chunk length is a
    traced scalar of one shared executable).

    Set-up is on the obs clock: span ``train.setup`` runs from here to the
    boosting loop, with children ``upload`` (placing the tables on the
    device or devices: the host's wall of handing them over, which waits for
    no transfer) and ``plan`` (host work over the rows); its compiles count
    under ``program="train.setup"`` until the loop's first compile boundary
    takes the sticky label, and the label is cleared when the job leaves, by
    return or by raise."""
    try:
        with contextlib.ExitStack() as setup:
            setup.enter_context(span("train.setup"))
            introspect.attribute("train.setup")
            return _train_device(
                setup, params, data, valid, num_trees=num_trees,
                init_booster=init_booster, callback=callback, mesh=mesh,
                checkpointer=checkpointer, chunk_hook=chunk_hook,
                chunk_policy=chunk_policy)
    finally:
        introspect.attribute(None)


def _train_device(setup, params, data, valid, *, num_trees, init_booster,
                  callback, mesh, checkpointer, chunk_hook, chunk_policy):
    """``train_device``'s body.  ``setup`` holds the open ``train.setup``
    span: closed here right before the boosting loop (either one), and by
    the caller if set-up raises."""
    p = params.validate()
    N, F = data.num_rows, data.num_features
    B = data.mapper.total_bins
    # documented max_depth=-1 policy (identical mapping on the CPU backend,
    # so cross-backend parity is untouched); the envelope counts what one
    # device holds, so a mesh's size enters beside the global rows
    n_shards = mesh.devices.size if mesh is not None else 1
    p = effective_depth_params(p, F, B, N, n_shards)
    obj = get_objective(p)
    K = p.num_outputs
    is_cat_np = data.mapper.is_categorical
    has_cat = bool(is_cat_np.any())
    T = (num_trees if num_trees is not None else p.num_trees) * K

    pad = 0
    shard_rows = None
    with span("upload"):
        if mesh is not None:
            if getattr(data, "is_streamed", False):
                raise ValueError(
                    "streamed datasets cannot train with mesh=...: the "
                    "sharded arm pads and shards the resident matrix "
                    "host-side — materialize() the dataset or train "
                    "unsharded (on-device streaming past HBM is the staged "
                    "follow-up)")
            from dryad_tpu.engine.distributed import padded_rows, shard_rows

            Xb_np, y_np = data.X_binned, data.y
            w_np = data.weight
            Np = padded_rows(N, mesh.devices.size)
            pad = Np - N
            if pad:
                Xb_np = np.pad(Xb_np, ((0, pad), (0, 0)))
                y_np = np.pad(y_np, (0, pad))
                if w_np is not None:
                    w_np = np.pad(w_np, (0, pad))
            # straight from the host: each device is sent its own rows, and
            # no chip ever holds the table a mesh exists to spread
            Xb, y = shard_rows(mesh, Xb_np, y_np)
            weight = shard_rows(mesh, w_np)[0] if w_np is not None else None
        else:
            # memoized on the Dataset: repeated train calls (bench arms,
            # warm restarts, parameter sweeps) skip the X upload entirely.
            # On a StreamedDataset this is the overlapped chunk-by-chunk
            # assembly (prefetch read i+1 vs async device_put of i) — the
            # jitted programs downstream are IDENTICAL to the resident path,
            # so the audit goldens and _comm_stats are untouched by
            # streaming.
            Xb, y, weight = data.device_arrays()
    NP = N + pad
    is_cat_feat = jnp.asarray(is_cat_np)
    qoff = data.query_offsets

    with span("plan"):
        init = np.asarray(obj.init_score(data.y, data.weight),
                          np.float32).reshape(-1)
    if init_booster is not None:
        # the carried base score is part of the model: a continuation (and
        # especially an r19 warm-start append on FRESH rows) must not
        # re-derive it from the current label distribution, or a 0-tree
        # append would shift every prediction.  Checkpoint resume is
        # unchanged bitwise — same labels produced the same init; this
        # runs BEFORE the rf constant-gradient capture below for the same
        # reason.
        init = np.asarray(init_booster.init_score, np.float32).reshape(-1)
    with span("upload"):
        score = jnp.broadcast_to(jnp.asarray(init),
                                 (NP, K)).astype(jnp.float32)
        if mesh is not None:
            score = shard_rows(mesh, score)[0]

    rank_row = rank_col = None
    rank_Q = rank_S = 0
    qoff_j = None
    if p.objective == "lambdarank":
        from dryad_tpu.engine.lambdarank import PaddingPlan

        with span("plan"):
            rank_plan = PaddingPlan(np.asarray(qoff),
                                    truncation=p.lambdarank_truncation)
        rank_row, rank_col = rank_plan.row_ids, rank_plan.col_ids
        rank_Q, rank_S = rank_plan.Q, rank_plan.S
        qoff_j = jnp.asarray(qoff)

    # the devices that actually run the step may differ from the process
    # default backend (e.g. a CPU mesh forced on a TPU-attached process) —
    # resolve 'auto' against the real target platform all the way down
    dev0 = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    plat = dev0.platform
    # run facts for the returned booster's train_state: backend="tpu"
    # means "the jax engine on whatever jax initialised", so callers
    # assert where a run executed from this record instead of inferring it
    run_device = {"platform": plat, "device_kind": dev0.device_kind}

    # static jit key: strip fields that cannot affect the compiled programs
    # so e.g. a warmup run with fewer trees reuses the same executables
    # (ch_max only sizes host-side chunking, so supervisor retries that
    # vary the cap keep sharing one program)
    p_key = p.replace(num_trees=1, early_stopping_rounds=0, metric="",
                      ch_max=0)

    def grads(score):
        return _grads_jit(p_key, N, K, pad, score, y, weight, qoff_j,
                          rank_row, rank_col, rank_Q, rank_S)

    # rf: grad/hess at the CONSTANT init score, computed ONCE — trees
    # de-correlate only through the per-iteration bag (config.py rf note);
    # `score` itself still accumulates tree sums (predict-time averaging)
    rf_gh = grads(score) if p.boosting == "rf" else None
    # loop-invariant device-resident init, shared by the rf eval transform
    # and every chunk dispatch (re-wrapping the host array per call is an
    # upload each); replicated explicitly on a mesh so the chunk
    # jit never sees mixed placements
    if mesh is not None:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as _PS

        init_dev = jax.device_put(np.asarray(init),
                                  NamedSharding(mesh, _PS()))
    else:
        init_dev = jnp.asarray(init)

    with span("plan"):      # the scan of the binned table for bin 0
        learn_missing = data.has_missing
    if jax.process_count() > 1:
        # multi-host: the flag is a static jit arg and rows are sharded per
        # process — agree globally (any host has missing => all scan both
        # planes) or hosts would trace divergent programs and grow
        # different trees, breaking N-shard ≡ 1-shard
        from jax.experimental import multihost_utils

        learn_missing = bool(
            multihost_utils.process_allgather(np.int32(learn_missing)).max())

    with span("plan"):
        comm = (_comm_stats(p_key, F, B, K, mesh.devices.size,
                            shared_roots=K > 1 and _shared_roots_ok(p, plat),
                            num_rows=N, padded_rows=NP, platform=plat,
                            has_cat=has_cat)
                if mesh is not None else None)
    if comm is not None:
        # comm-payload observability (r16): the static accounting becomes
        # dryad_comm_* gauges at this compile boundary, so a reduce-payload
        # regression (or an arm flip) is trend-visible on /metrics.  The
        # export is jax-free on the obs side (obs/comm.py) and a no-op on
        # a disabled registry.
        from dryad_tpu.obs.comm import export_comm_stats

        export_comm_stats(comm, growth=p.growth)

    # EFB bundle columns are masked out of the missing-right split plane
    # (their bin 0 means "all default", not "missing"); only materialized
    # when the plane is scanned at all, so NaN-free programs are unchanged
    bundled_np = getattr(data.mapper, "bundled_mask", None)
    bmask = (jnp.asarray(bundled_np)
             if learn_missing and bundled_np is not None and bundled_np.any()
             else None)

    # L1-family leaf renewal — the gate (weighted / boosting / monotone)
    # lives wholly in renew_alpha; imported LATE so test monkeypatching of
    # dryad_tpu.objectives.renew_alpha reaches this trainer too
    from dryad_tpu.objectives import renew_alpha as _obj_renew_alpha

    renew_a = _obj_renew_alpha(p, weighted=data.weight is not None)

    def step(out, score, g_all, h_all, bag, fmask, t, k, root_hist=None,
             value_scale=None):
        return _step_jit(p_key, B, has_cat, mesh, plat, learn_missing, out,
                         score, Xb, g_all, h_all, bag, fmask, is_cat_feat, t, k,
                         root_hist, bmask, n_rows=N, value_scale=value_scale,
                         y=y, renew_alpha=renew_a)

    # ---- resume / warm start -------------------------------------------------
    from dryad_tpu.engine import leafwise_fast

    batched_leafwise = (p.growth == "leafwise"
                        and leafwise_fast.supports(p, F, B, N, n_shards))
    out = _empty_out_device(T, p.max_nodes, CAT_WORDS,
                            grow_stats=batched_leafwise)
    if batched_leafwise and default_registry().enabled:
        default_registry().gauge(
            "dryad_leafwise_depth_cap",
            "Depth cap of the batched leaf-wise expansion (max_depth, or "
            "the unbounded_depth=auto policy's)").set(p.max_depth)
    stats_done = 0      # trees whose grower statistics the counters hold
    start_iter = 0
    max_depth_prev = 0
    prev_trees = None
    if init_booster is not None:
        prev = init_booster
        if prev.params.max_nodes != p.max_nodes or prev.num_outputs != K:
            raise ValueError(
                "init_booster is incompatible: num_leaves/max_depth/num_class must match"
            )
        if prev.num_total_trees > T:
            raise ValueError("new num_trees must cover the init_booster's iterations")
        if ("rf" in (prev.params.boosting, p.boosting)
                and prev.params.boosting != p.boosting):
            raise ValueError(
                "cannot continue training across rf and non-rf boosting: "
                "rf predictions AVERAGE the trees, so a mixed tree table "
                "has no sound aggregation")
        prev_trees = {
            key: jnp.asarray(v).reshape((prev.num_iterations, K) + v.shape[1:])
            for key, v in prev.tree_arrays().items()
        }
        # same fp32 order as the CPU replay: broadcast(new init) += each tree
        score = _accumulate(prev_trees, Xb, jnp.asarray(init),
                            max(prev.max_depth_seen, 1))
        for key in _TREE_KEYS:
            out[key] = out[key].at[: prev.num_total_trees].set(
                jnp.asarray(prev.tree_arrays()[key]))
        start_iter = prev.num_iterations
        stats_done = prev.num_total_trees
        max_depth_prev = prev.max_depth_seen

    # every valid set is scored ON DEVICE (metrics/device.py); the FIRST
    # drives early stopping.  When something needs the value mid-run (early
    # stopping, a callback, checkpoint state) each eval fetches ONE f32
    # scalar; otherwise all evals stay device-side until training ends and
    # best_iteration is replayed from the bulk fetch — zero per-iteration
    # syncs even with validation.
    from dryad_tpu.cpu.trainer import normalize_valids
    from dryad_tpu.metrics.device import make_evaluator

    valids = normalize_valids(valid)
    for vname, vds in valids:
        if getattr(vds, "is_streamed", False):
            raise ValueError(
                f"valid set {vname!r} is streamed: device eval scores the "
                "resident matrix — materialize() it (valid sets are small "
                "relative to the training corpus)")
    with span("plan"):      # a rank metric pads its queries; labels go up
        evaluators = [make_evaluator(p.objective, p.metric, vds, p.ndcg_at)
                      for _, vds in valids]
    # a checkpointer does NOT force per-eval syncs: deferred evals are
    # flushed (bulk fetch + replay) right before each due checkpoint so the
    # saved best_iteration/stale state is exact
    sync_eval = bool(p.early_stopping_rounds) or callback is not None
    deferred: list[tuple[int, list]] = []
    # resume keeps the prior segment's deferred history so the merged run
    # matches the uninterrupted one (CLAUDE.md resume invariant)
    eval_history: dict[str, list] | None = None
    if init_booster is not None and init_booster.train_state.get("eval_history"):
        eval_history = {k: list(v) for k, v in
                        init_booster.train_state["eval_history"].items()}
    with span("upload"):
        vXbs = [jnp.asarray(v.X_binned) for _, v in valids]
        vscores = [
            jnp.broadcast_to(jnp.asarray(init),
                             (v.num_rows, K)).astype(jnp.float32)
            for _, v in valids
        ]
        if mesh is not None:
            # a valid set is REPLICATED over the mesh, placed once: every
            # device walks all its rows inside the chunk program and holds
            # the same metric (the rank metrics sort the whole set, so a
            # row-sharded eval would need a gather of the scores anyway; the
            # walk is a few percent of an iteration).  Left uncommitted on
            # one device, each dispatch would broadcast the matrix again.
            from dryad_tpu.engine.distributed import replicate

            vXbs, vscores = replicate(mesh, (vXbs, vscores))
    if init_booster is not None:
        vscores = [
            _accumulate(prev_trees, vXb, jnp.asarray(init),
                        max(max_depth_prev, 1))
            for vXb in vXbs
        ]
    best_iteration, best_value, stale = -1, None, 0
    if init_booster is not None and p.boosting != "dart":
        # resume continues the eval/early-stop state exactly where it
        # stopped; DART continuations must NOT inherit a recorded
        # best_iteration (the coming drops rescale trees inside that
        # prefix — see update_best), and DART's own checkpoints carry -1
        best_iteration = init_booster.best_iteration
        best_value = init_booster.train_state.get("best_value")
        stale = init_booster.train_state.get("stale", 0)

    def fold_eval_row(it_d, vals):
        """Fold one eval's values into eval_history + best-iteration state —
        the ONE bookkeeping used by every deferred replay (per-iteration
        deferred flush and the chunked path's buffer flush), so the two can
        never diverge.  DART keeps eval_history but never records
        best_iteration (update_best itself is the no-op — see its
        docstring)."""
        nonlocal best_iteration, best_value, stale, eval_history
        _, higher0, _ = evaluators[0]
        if eval_history is None:
            eval_history = {}
        for vi, ((vname, _), (mname, _, _)) in enumerate(
                zip(valids, evaluators)):
            eval_history.setdefault(f"{vname}_{mname}", []).append(
                [int(it_d), float(vals[vi])])
        best_iteration, best_value, stale = update_best(
            p, best_iteration, best_value, stale, int(it_d), float(vals[0]),
            higher0)

    def flush_deferred():
        """Bulk-fetch pending deferred evals and replay the bookkeeping via
        the shared update_best — called before each due checkpoint and at
        training end, so the deferred path's state is exact wherever it is
        observed while staying fetch-free in between."""
        if not deferred:
            return
        fetched = jax.device_get([vals for _, vals in deferred])
        for (it_d, _), vals in zip(deferred, fetched):
            fold_eval_row(it_d, vals)
        deferred.clear()

    # pad rows are bagged out permanently: they must never touch a histogram
    ones_rows = jnp.asarray(np.pad(np.ones((N,), bool), (0, pad)))
    if mesh is not None:
        ones_rows = shard_rows(mesh, ones_rows)[0]
    ones_feat = jnp.ones((F,), bool)

    # ---- chunked fast path: whole iterations inside one program --------------
    # When nothing needs the host between iterations (no bagging/colsample
    # Philox draw, no GOSS uniforms, no validation sync) the boosting loop
    # runs on device in blocks, one host dispatch per chunk.
    #
    # ACCEPTED TOLERANCE (same class as the CPU↔TPU near-tie note in
    # CLAUDE.md): the chunked program compiles the boosting step into a
    # DIFFERENT fusion shape than per-iteration dispatch, so merely adding
    # a validation set or subsample<1 (which switches paths) can flip a
    # near-tie split argmax on device.  Path selection is a deterministic
    # function of (params, valids), so resume and N-shard ≡ 1-shard — which
    # never change the path mid-run — are unaffected; only configs that
    # *straddle* the condition may see ulp-level tree differences, with
    # model quality untouched.
    # The same tolerance class covers the deep-phase data-movement choice
    # (r6): ``deep_layout="auto"`` carries the leaf-ordered record layout
    # through levelwise's deep levels (levelwise.deep_layout_supported
    # gates it on params + feature/bin shape, never rows, so every shard
    # and every run of one config picks the same path deterministically),
    # while "legacy" keeps the per-level sort + record gather.  Post-
    # permute layouts regroup per-tile f32 histogram partials at ulp
    # level, so flipping the knob — like switching dispatch ↔ chunked —
    # may flip a near-tie argmax on device; counts stay exact and the
    # smoke gate (scripts/smoke_tpu.py) pins bitwise tree equality on the
    # tie-free fixture.
    # Round 3: bagged/colsampled runs chunk too (host Philox masks upload
    # bit-packed per chunk), and validated runs evaluate INSIDE the chunk
    # program.  Round 4 (VERDICT r3 #4/#6): sharded bagged runs chunk as
    # well — the packed masks replicate over the mesh and each device
    # unpacks + slices its own rows, so no shard alignment is needed —
    # and GOSS chunks too, its uniforms drawn ON DEVICE per iteration by
    # the counter-based hash shared bit-for-bit with the CPU backend
    # (_goss_uniform_dev).  Per-iteration dispatch remains only for
    # host-fallback metrics and early stopping at eval_period=1 (the
    # value gates the next iteration, so a fetch per iteration is
    # semantically required).
    bagging = p.subsample < 1.0 or p.colsample < 1.0
    host_eval = any(getattr(fn, "host_only", True) for _, _, fn in evaluators)
    chunkable = (not (valids and host_eval)
                 and not (valids and p.early_stopping_rounds
                          and p.eval_period < 2)
                 # DART mutates previously grown trees every iteration
                 # (drop + rescale) — host-orchestrated dispatch only
                 and p.boosting != "dart")
    if chunkable:
        # chunk length is budgeted in SECONDS of device time per program,
        # from a measured iteration-cost model calibrated at 10M rows x 28 (a
        # device's rows: NP // n_shards) x 256 bins (1.6e-7 s/row/class/pass), F·B-scaled,
        # since histogram work is O(N·F·B) per pass (Epsilon's 2000
        # features once packed a chunk ~70x past the budget).  Depthwise
        # pays one batched pass per level; leaf-wise one full-N masked pass
        # per SPLIT (L-1), so its estimate scales with the leaf budget.
        if p.growth == "depthwise" and p.max_depth > 0:
            passes_est = p.max_depth
        else:
            # batched_leafwise (resolved where ``out`` is made): growth is
            # leaf-wise and leafwise_fast.supports(p, F, B, N), so grow_any
            # takes the level-synchronous expansion
            if batched_leafwise:
                # batched leaf-wise: one level pass per expansion depth
                # (10M x 28, 255 leaves, cap 12: the MAC model below says
                # 4.35 s an iteration, the chip 4.8 s; my chip run, PR 27)
                passes_est = p.max_depth
            else:
                passes_est = max(8, p.effective_num_leaves - 1)
        est_iter_s = (1.6e-7 * (NP // n_shards) * K * passes_est
                      * max(F / 28.0, 1.0) * max(B / 256.0, 1.0))
        # per-MAC model (round 4): histogram work is N·K·passes·F·B MACs and
        # 5e-15 s/MAC sits mid-range of the measured configs (10M Higgs
        # 2.9 est vs 3.0 actual; Epsilon 8.2 vs 10.2; Covertype 2.3 vs
        # 1.15) — far tighter than the per-row model above, which
        # over-estimates up to 8x off its calibration point.  LambdaMART
        # keeps the over-estimating per-row model for chunk sizing: its λ
        # pass scales with query sizes the MAC model cannot see.
        est_iter_mac = 0.05 + 5e-15 * (NP // n_shards) * K * passes_est * F * B
        est_for_ch = (est_iter_s if p.objective == "lambdarank"
                      else est_iter_mac)
        # 25 s budget on the tighter model (was 40 s on the loose one),
        # which keeps a chunk under a minute even where the MAC model
        # under-estimates (Epsilon 1.25x); the second-chunk calibration
        # still re-derives CH from measurement either way
        CH = max(1, min(64, int(25.0 / max(est_for_ch, 1e-3))))
        # The chunk-length cap (initial AND calibrated) — an operational
        # escape hatch for phases in which standard-length (~20 s) chunk
        # executions die: the round-5 500-tree 10M headline runs died 6/6
        # with CH 6-8 while CH <= 2 runs sailed through (same program,
        # same data).  Off by default.  Precedence (documented on
        # Params.ch_max): the DRYAD_CH_MAX env var, when set > 0, OVERRIDES
        # the threaded param; otherwise Params.ch_max applies; the
        # supervisor's chunk_policy caps individual chunks below either,
        # inside the loop.
        _ch_env = int(os.environ.get("DRYAD_CH_MAX", "0"))
        _ch_max = _ch_env if _ch_env > 0 else int(p.ch_max)
        if _ch_max > 0:
            CH = min(CH, _ch_max)
        # The cost model overestimates (measured 1.7-4x — fixed overheads
        # amortize sublinearly), so a model-derived CH of 1 may really
        # afford 2-4 iterations: admit single-iteration chunks when the
        # ESTIMATE itself is under 40 s and let the second-chunk
        # calibration raise CH from measurement.  F*B caps program width
        # (compile-size guard, verified up to Epsilon's 2000*256).
        # (the model has only ever OVER-estimated, so an estimate under
        # the bound means a real 1-iteration program is shorter still)
        chunkable = ((CH >= 2 or est_iter_s <= 40.0)
                     and F * B <= _CHUNK_FB_LIMIT)
    if chunkable:
        # The chunk program's ONE-TIME compile scales
        # with program width (~K·F·B) and can dominate a short run (Epsilon
        # 20-tree acceptance: +204 s of compile for 204 s of training).
        # Skip chunking when the estimated total work is small next to the
        # estimated compile SURPLUS over the per-iteration path's own
        # compile.  The per-MAC work model here is separate from the
        # watchdog's est_iter_s above, which deliberately over-estimates
        # (safety); this one aims at the middle of the measured range so
        # the comparison is fair.  DRYAD_CHUNK=1 skips THIS heuristic only
        # (the base eligibility gates above — program-width limit,
        # chunk-seconds sizing — still apply: overriding them would compile
        # unverified program widths or overrun the per-chunk budget);
        # DRYAD_CHUNK=0 disables chunking outright.  bench.py pins =1 so
        # its short marginal arms measure the long-run chunked steady
        # state.  Unset keeps the deterministic (params, shapes) rule.
        _force = os.environ.get("DRYAD_CHUNK", "")
        if _force in ("0", "1"):
            chunkable = _force == "1"
        elif plat != "cpu":
            # accelerator compile only — on the CPU backend (tests, local
            # runs) compile is cheap and chunking always pays
            compile_surplus = 15.0 + 4.5e-4 * K * F * B
            # FULL-run work, not the remaining segment: path choice must be
            # a pure function of (params, shapes) or a resumed run could
            # take a different program than the uninterrupted one and break
            # the resume bit-identity invariant (fusion-shape tolerance).
            # est_for_ch, not est_iter_mac: lambdarank's λ pass is
            # invisible to the MAC model (see chunk sizing above)
            chunkable = (T // K) * est_for_ch > compile_surplus
    if chunkable:
        import time as _time

        total_iters = T // K
        if (valids and p.early_stopping_rounds
                and stale >= p.early_stopping_rounds):
            total_iters = start_iter   # resume landed ON the stop boundary

        # eval machinery (device-resident; one (n_sets,) row per eval)
        n_sets = len(valids)
        metric_names = tuple(mname for mname, _, _ in evaluators)
        vXbs_t = tuple(vXbs)
        vys_t = tuple(fn.y_dev for _, _, fn in evaluators)
        vqids_t = tuple(fn.qids for _, _, fn in evaluators)
        eval_buf = jnp.zeros((max(total_iters, 1), n_sets), jnp.float32) \
            if n_sets else None
        eval_its = jnp.full((max(total_iters, 1),), -1, jnp.int32) \
            if n_sets else None
        eval_cnt = jnp.int32(0) if n_sets else None
        vscores_t = tuple(vscores)
        host_cnt = 0        # slots the host knows are written
        flushed_cnt = 0     # slots already folded into best/history state

        def eval_iters_in(lo, hi):
            return [j for j in range(lo, hi)
                    if (j + 1) % p.eval_period == 0 or j + 1 == total_iters]

        def next_eval_end(lo):
            j = lo
            while not ((j + 1) % p.eval_period == 0 or j + 1 == total_iters):
                j += 1
            return j + 1

        def flush_chunk_evals(upto):
            """Fold fetched eval rows [flushed_cnt, upto) into
            best-iteration state + eval_history via the shared
            fold_eval_row (the deferred-path replay, exact wherever it is
            observed)."""
            nonlocal flushed_cnt
            if upto <= flushed_cnt:
                return
            with span("train.fetch.eval_flush"):
                vals, its_arr = jax.device_get(
                    (eval_buf[flushed_cnt:upto], eval_its[flushed_cnt:upto]))
            for row, it_d in zip(np.asarray(vals), np.asarray(its_arr)):
                fold_eval_row(it_d, row)
            flushed_cnt = upto

        # per-chunk Philox mask upload buffers (fixed CH0 rows: a varying
        # leading dim would recompile the chunk program per tail length)
        CH0 = CH
        nbytes = (NP + 7) // 8
        row_sampled = p.subsample < 1.0
        col_sampled = p.colsample < 1.0

        # adaptive chunk budget: the 1.6e-7 model above is only the FIRST
        # guess — the second chunk (the first one free of compile time) is
        # timed and CH re-derived from measurement at ~20 s of device time
        # per chunk.  Mask uploads pin the array shape, so
        # CH can only shrink below CH0 once those exist.
        chunk_idx = 0
        t_mark = None
        calibrated = False
        inflight: list = []
        _obs = default_registry()
        # bound handles per the registry's hot-loop contract (no per-chunk
        # family lookup); bound on FIRST enabled use — eager binding would
        # register the families on a disabled registry
        _obs_chunks = _obs_iter = None
        # recompile tripwire (r12): a fresh run legitimately compiles its
        # chunk program once; after the first dispatch the family is ARMED
        # and any NEW program key (a mid-run p_key change — nothing may
        # cause one) fires dryad_recompile_unexpected_total + /healthz
        _tw = default_tripwire()
        _tw.begin_program("train.chunk")
        _shards_lbl = mesh.devices.size if mesh is not None else 1

        setup.close()
        it = start_iter
        while it < total_iters:
            n = min(CH, total_iters - it)
            # the supervisor's live cap applies HERE — after path selection
            # and independent of calibration — so degradation mid-run only
            # shortens chunks (traced scalar), never changes the program
            ch_eff = _ch_max
            if chunk_policy is not None:
                cap_dyn = int(chunk_policy.cap())
                if cap_dyn > 0:
                    n = min(n, cap_dyn)
                    ch_eff = min(ch_eff, cap_dyn) if ch_eff > 0 else cap_dyn
            if checkpointer is not None:
                # land chunk ends exactly on checkpoint boundaries
                n = min(n, checkpointer.every - (it % checkpointer.every))
            if valids and p.early_stopping_rounds:
                # early stopping reads each eval before growing past it:
                # every chunk must END on an eval boundary
                n = min(n, next_eval_end(it) - it)
            if chunk_policy is not None:
                # report the length BEFORE anything can fault: a death at
                # this chunk's first fetch must still leave the policy
                # knowing what length was fatal (resilience/policy.py)
                chunk_policy.note_dispatch(n)
            if chunk_hook is not None:
                chunk_hook("dispatch", it)
            # async site: this is host dispatch wall (masks + enqueue), not
            # device execution — the fetch spans carry that.  A real
            # interval with its true start, so a profile lays it on the gap
            # before the chunk's program
            _obs_on = _obs.enabled
            with span("train.chunk_dispatch"):
                bag_bits = fmask_chunk = None
                if bagging:
                    bb = (np.zeros((CH0, nbytes), np.uint8) if row_sampled
                          else None)
                    fm = (np.ones((CH0, F), bool) if col_sampled else None)
                    for j in range(n):
                        rm, fmk = sample_masks(p, it + j, N, F)
                        if bb is not None:
                            row = np.ones(N, bool) if rm is None else rm
                            bb[j] = np.packbits(np.pad(row, (0, pad)),
                                                bitorder="little")
                        if fm is not None and fmk is not None:
                            fm[j] = fmk
                    if mesh is not None:
                        # replicate the packed masks over the mesh explicitly: a
                        # plain asarray commits to one device and the chunk jit
                        # would reject mixed placements.  The devices unpack the
                        # replicated bytes and slice their own row range — bit
                        # packs need no shard alignment (VERDICT r3 #6).
                        from jax.sharding import NamedSharding
                        from jax.sharding import PartitionSpec as PS

                        rep = NamedSharding(mesh, PS())
                        bag_bits = (jax.device_put(bb, rep)
                                    if bb is not None else None)
                        fmask_chunk = (jax.device_put(fm, rep)
                                       if fm is not None else None)
                    else:
                        bag_bits = jnp.asarray(bb) if bb is not None else None
                        fmask_chunk = jnp.asarray(fm) if fm is not None else None

                _chunk_args = (
                    p_key, B, has_cat, mesh, plat, learn_missing, N, K, pad,
                    rank_Q, rank_S, out, score, Xb, y, weight, ones_rows,
                    ones_feat, is_cat_feat, qoff_j, rank_row, rank_col,
                    jnp.int32(it), jnp.int32(n), bmask, bag_bits, fmask_chunk,
                    metric_names, p.ndcg_at, p.eval_period, total_iters,
                    vXbs_t, vys_t, vqids_t, vscores_t, eval_buf, eval_its,
                    eval_cnt)
                if _obs.enabled:
                    # compile-boundary introspection: the first chunk of a new
                    # program key lowers (NO compile) for dryad_prog_* cost
                    # series and notes the key on the tripwire; warm chunks
                    # cost one memo lookup.  The key is the chunk jit's static
                    # signature, so a changed program mid-run is caught here.
                    introspect.capture(
                        "train.chunk",
                        ("chunk", p_key, B, has_cat, plat, N, K, pad,
                         metric_names, p.eval_period, total_iters, renew_a),
                        _chunk_jit, *_chunk_args, init_arr=init_dev,
                        renew_alpha=renew_a,
                        labels={"growth": p.growth, "shards": _shards_lbl})
                (out, score, vscores_t, eval_buf, eval_its,
                 eval_cnt) = _chunk_jit(*_chunk_args, init_arr=init_dev,
                                        renew_alpha=renew_a)
                # expected-compile budget spent: arm every chunk (idempotent;
                # a key-less family stays inert, so a mid-run enable() arms
                # cleanly at the first ENABLED chunk instead of false-firing)
                _tw.arm("train.chunk")
            if _obs_on:
                if _obs_chunks is None:
                    _obs_chunks = _obs.counter(
                        "dryad_train_chunks_total",
                        "Chunk programs dispatched")
                    _obs_iter = _obs.gauge(
                        "dryad_train_iteration",
                        "Last host-side boosting iteration")
                _obs_chunks.inc()
                _obs_iter.set(it)

            if not calibrated:
                # drain the pipeline: chunk 0 absorbs compile, chunk 1 is
                # the measurement
                with watch_fetch("calibrate", it):
                    if chunk_hook is not None:
                        chunk_hook("fetch", it)
                    # no fetch span: this wait moves no bytes to the host.
                    # It is the job's start on the device: chunk 0's run
                    # (its compile was the dispatch's) and chunk 1's, the
                    # one that is timed.  (The watchdog wrap is different:
                    # it times only the in-flight AGE, and an injected
                    # stall in the hook must be visible.)
                    with span("train.calibrate"):
                        jax.block_until_ready(out["max_depth"])
                now = _time.perf_counter()
                if chunk_idx == 1 and t_mark is not None:
                    per_iter = max((now - t_mark) / n, 1e-4)
                    cap = CH0 if bagging else 64
                    CH = max(1, min(cap, int(20.0 / per_iter)))
                    if _ch_max > 0:
                        CH = min(CH, _ch_max)
                    calibrated = True
                t_mark = now
            else:
                # Cap the async run-ahead to ~2 chunks.  Without this, a
                # deferred-eval 500-tree run enqueues its entire chunk
                # stream in seconds and the FIRST fetch (a checkpoint
                # flush or the end-of-run flush) then waits minutes behind
                # the queue (two round-5 headline runs died at exactly
                # such a fetch; sync-eval runs were immune because their
                # per-chunk fetch keeps the host in lockstep).  Blocking
                # on the chunk TWO dispatches back keeps one chunk of
                # pipeline overlap (chunks are calibrated to ~20 s, so any
                # later fetch waits <= ~2 chunks ~= 40 s).  The wait is a
                # one-element fetch so the runahead span and the fetch
                # watchdog see it like every other fetch site.
                inflight.append((it, out["max_depth"]))
                if len(inflight) > 2:
                    # the fetch blocks on the OLDEST inflight chunk — label
                    # the hook with ITS head iteration, not the current
                    # chunk's, so a fault here journals against the work
                    # that actually stalled
                    fetch_it, fetch_arr = inflight.pop(0)
                    with watch_fetch("runahead", fetch_it):
                        if chunk_hook is not None:
                            chunk_hook("fetch", fetch_it)
                        with span("train.fetch.runahead"):
                            jax.device_get(fetch_arr[:1])
            chunk_idx += 1

            evs = eval_iters_in(it, it + n)
            host_cnt += len(evs)
            stop = False
            if valids and sync_eval and evs:
                # one small fetch per chunk: the values feed early stopping
                # and live callbacks (the chunk ended ON the eval boundary,
                # so stopping here is iteration-exact)
                with watch_fetch("eval", it):
                    if chunk_hook is not None:
                        chunk_hook("fetch", it)
                    with span("train.fetch.eval"):
                        # the grower's statistics ride this fetch
                        vals, stats = jax.device_get((
                            eval_buf[host_cnt - len(evs):host_cnt],
                            _grow_stats_of(out, stats_done, (it + n) * K)))
                        vals = np.asarray(vals)
                _count_grow_stats(stats)
                stats_done = (it + n) * K
                _, higher0, _ = evaluators[0]
                val_rows = dict(zip(evs, vals))
                # user code runs here (a logger, the benchmark's clock): its
                # wall is the job's, under a name of its own
                with span("train.callbacks"):
                    for j in range(it, it + n):
                        info = {"iteration": j, "ch_max_effective": ch_eff}
                        if comm is not None:
                            info.update(comm)
                        if j in val_rows:
                            for vi, ((vname, _), (mname, higher, _)) in \
                                    enumerate(zip(valids, evaluators)):
                                info[f"{vname}_{mname}"] = float(
                                    val_rows[j][vi])
                            best_iteration, best_value, stale = update_best(
                                p, best_iteration, best_value, stale, j,
                                float(val_rows[j][0]), higher0)
                            if (p.early_stopping_rounds
                                    and stale >= p.early_stopping_rounds):
                                stop = True
                        if callback is not None:
                            callback(j, info)
                flushed_cnt = host_cnt  # consumed: keep deferred flush exact
            elif callback is not None:
                with span("train.callbacks"):
                    for j in range(it, it + n):
                        info = {"iteration": j, "ch_max_effective": ch_eff}
                        if comm is not None:
                            info.update(comm)
                        callback(j, info)
            it += n
            if checkpointer is not None and checkpointer.due(it):
                # _materialize is a real bulk fetch — the site the recorded
                # fetch deaths surfaced at (STATUS r5)
                with watch_fetch("checkpoint", it):
                    if chunk_hook is not None:
                        chunk_hook("fetch", it)
                    with span("train.fetch.checkpoint"):
                        # two children: the fetch (with what it compiles)
                        # and the write to disk
                        with span("materialize"):
                            if valids and not sync_eval:
                                flush_chunk_evals(host_cnt)
                            ckpt = _materialize(p, data.mapper, out, it * K,
                                                init, max_depth_prev,
                                                best_iteration, best_value,
                                                stale)
                        if eval_history is not None:  # carried from resume
                            ckpt.train_state["eval_history"] = eval_history
                        with span("save"):
                            checkpointer.save(ckpt, it)
            if chunk_policy is not None:
                # "clean" = dispatched + all due host work done; the async
                # run-ahead means device completion trails <= 2 chunks, so
                # a re-widen decision is at most two chunks optimistic
                # (documented in resilience/policy.py).  The length feeds
                # the policy's degrade target: the first step must actually
                # SHORTEN chunks relative to what has been running.
                chunk_policy.note_clean_chunk(n)
            if stop:
                total_iters = it
                break

        # hook BEFORE the deferred-eval flush: that flush is itself a bulk
        # fetch, and a fault inside it must attribute to a fetch site
        with watch_fetch("final", total_iters):
            if chunk_hook is not None:
                chunk_hook("fetch", total_iters)
            with span("train.fetch.final"):
                if valids and not sync_eval:
                    flush_chunk_evals(host_cnt)
                _count_grow_stats(jax.device_get(
                    _grow_stats_of(out, stats_done, total_iters * K)))
                booster = _materialize(p, data.mapper, out, total_iters * K,
                                       init, max_depth_prev, best_iteration,
                                       best_value, stale)
        if eval_history is not None:
            booster.train_state["eval_history"] = eval_history
        if comm is not None:
            booster.train_state["comm_stats"] = comm
        # journals/benches read the cap that governed this run (0 = uncapped;
        # the supervisor's per-chunk cap additionally rides the info dicts)
        booster.train_state["ch_max_effective"] = _ch_max
        booster.train_state.update(run_device)
        return booster

    # ---- boosting loop: async dispatch, zero per-iteration syncs -------------
    import time as _time

    _obs = default_registry()
    _obs_iter = None    # bound on first enabled use (see chunked path)
    # recompile tripwire, per-iteration arm: the step program is fixed
    # after the first iteration — except under DART, whose drop iterations
    # legitimately alternate the value_scale variant, so DART never arms
    _tw = default_tripwire()
    _tw.begin_program("train.step")
    _shards_lbl = mesh.devices.size if mesh is not None else 1
    setup.close()
    for it in range(start_iter, T // K):
        # a checkpoint taken AT the early-stop boundary restores stale >=
        # rounds; growing anything past it would diverge from the stopped run
        if (valids and p.early_stopping_rounds
                and stale >= p.early_stopping_rounds):
            T = it * K
            break
        if chunk_hook is not None:
            chunk_hook("dispatch", it)
        # the iteration's host interval, opened and closed by hand (a
        # ``with`` would put its name in front of every fetch span's path)
        _t_it = _time.perf_counter() if _obs.enabled else None
        _ann_it = annotation("train.iteration")
        row_mask_np, feat_mask_np = sample_masks(p, it, N, F)
        if row_mask_np is None:
            bag = ones_rows
        else:
            bag_np = np.pad(row_mask_np, (0, pad))
            bag = jnp.asarray(bag_np)
            if mesh is not None:
                bag = shard_rows(mesh, bag)[0]
        fmask = ones_feat if feat_mask_np is None else jnp.asarray(feat_mask_np)

        # ---- DART drop (mirrors cpu/trainer.py arithmetic exactly) --------
        value_scale = None
        if p.boosting == "dart":
            drop_np = dart_drop_set(p, it, it)
            if drop_np.size:
                kd = int(drop_np.size)
                inv = jnp.float32(1.0 / (kd + 1))
                fdrop = jnp.float32(np.float32(kd / (kd + 1.0)))
                Dmax = p.max_drop * K
                tids_np = np.full((Dmax,), -1, np.int32)
                tcls_np = np.zeros((Dmax,), np.int32)
                flat = (drop_np[:, None] * K
                        + np.arange(K)[None, :]).reshape(-1)
                tids_np[: flat.size] = flat
                tcls_np[: flat.size] = np.tile(np.arange(K), kd)
                tids = jnp.asarray(tids_np)
                tcls = jnp.asarray(tcls_np)
                db = (p.max_depth if p.max_depth > 0
                      else max(p.effective_num_leaves - 1, 1))
                score_eff, newval = _dart_drop_jit(
                    out, score, tids, tcls, Xb, fdrop, db, B, has_cat)
                out = dict(out)
                out["value"] = newval
                value_scale = inv
                g_all, h_all = grads(score_eff)
                # score/vscores are REBUILT after the grow below by the
                # exact replay-sum a resumed run computes (_accumulate) —
                # incremental drop deltas round differently and would
                # break the resume bit-identity invariant
            else:
                g_all, h_all = grads(score)
        else:
            g_all, h_all = rf_gh if rf_gh is not None else grads(score)
        if p.boosting == "goss":
            u_np = np.pad(goss_uniform(p, it, N), (0, pad), constant_values=2.0)
            u = jnp.asarray(u_np)
            if mesh is not None:
                u = shard_rows(mesh, u)[0]
            g_all, h_all, goss_mask = _goss_jit(p_key, N, g_all, h_all, u, bag)
            bag = goss_mask
        roots = None
        if K > 1 and _shared_roots_ok(p, plat):
            # shared-plan multiclass roots (one pass for all K classes);
            # the histogram is feat_mask-independent — masked features'
            # columns simply never win the split scan
            roots = _roots_jit(B, p.rows_per_chunk, p.hist_precision, mesh,
                               Xb, g_all, h_all, bag)
        if _obs.enabled:
            # compile boundary of the per-iteration step program (one memo
            # lookup on warm iterations); the tripwire key carries the
            # value_scale variant so DART's two legitimate step programs
            # stay distinct keys instead of false-firing
            introspect.capture(
                "train.step",
                ("step", p_key, B, has_cat, plat, N, K, renew_a,
                 value_scale is not None),
                _step_jit, p_key, B, has_cat, mesh, plat, learn_missing,
                out, score, Xb, g_all, h_all, bag, fmask, is_cat_feat,
                it * K, 0, None if roots is None else roots[0], bmask,
                n_rows=N, value_scale=value_scale, y=y, renew_alpha=renew_a,
                labels={"growth": p.growth, "shards": _shards_lbl,
                        "arm": "per_iteration"})
        for k in range(K):
            t = it * K + k
            out, score = step(out, score, g_all, h_all, bag, fmask, t, k,
                              None if roots is None else roots[k],
                              value_scale=value_scale)
            if value_scale is None:
                for vi, vXb in enumerate(vXbs):
                    vscores[vi] = vscores[vi].at[:, k].set(
                        _apply_valid_jit(out, t, vXb, vscores[vi][:, k],
                                         out["max_depth"][t], B, has_cat)
                    )
        if p.boosting != "dart":
            # idempotent per-iteration arm (key-less families stay inert —
            # see the chunked path); DART never arms: drop iterations
            # legitimately alternate the value_scale program variant
            _tw.arm("train.step")
        if value_scale is not None:
            # DART drop iteration: rebuild carried scores as the replay-sum
            # over the CURRENT (rescaled) value table — the construction a
            # resumed run performs, so checkpoint boundaries are bitwise
            trees_live = {key: out[key].reshape((T // K, K)
                                                + out[key].shape[1:])
                          for key in _TREE_KEYS}
            db = (p.max_depth if p.max_depth > 0
                  else max(p.effective_num_leaves - 1, 1))
            score = _dart_replay_jit(trees_live, Xb, jnp.asarray(init), db)
            vscores = [_dart_replay_jit(trees_live, vXb, jnp.asarray(init),
                                        db)
                       for vXb in vXbs]

        # ch_max_effective = 0 here: per-iteration dispatch has no chunking,
        # so no cap is in force — but the key is the documented contract
        # journals/benches read on every path
        info: dict = {"iteration": it, "ch_max_effective": 0}
        if comm is not None:
            info.update(comm)
        stop = False
        # eval every eval_period-th iteration, always including the last so
        # the training tail is never silently unscored
        eval_now = (it + 1) % p.eval_period == 0 or it + 1 == T // K
        if valids and eval_now:
            if p.boosting == "rf":
                # rf scores the AVERAGED model — same transform as predict
                inv_it = jnp.float32(np.float32(1.0) / np.float32(it + 1))
                vs_eval = [_rf_avg_jit(vs, init_dev, inv_it)
                           for vs in vscores]
            else:
                vs_eval = vscores
            vals_dev = [fn(vs_eval[vi])
                        for vi, (_, _, fn) in enumerate(evaluators)]
            if not sync_eval:
                deferred.append((it, vals_dev))
            else:
                with watch_fetch("eval", it):
                    if chunk_hook is not None:
                        chunk_hook("fetch", it)
                    with span("train.fetch.eval"):
                        vals = jax.device_get(vals_dev)  # ONE fetch, all sets
                for vi, ((vname, _), (mname, higher, _)) in enumerate(
                        zip(valids, evaluators)):
                    value = float(vals[vi])
                    info[f"{vname}_{mname}"] = value
                    if vi > 0:
                        continue  # early stopping watches the first set only
                    best_iteration, best_value, stale = update_best(
                        p, best_iteration, best_value, stale, it, value,
                        higher)
                    if (p.early_stopping_rounds
                            and stale >= p.early_stopping_rounds):
                        stop = True
        if callback is not None:
            with span("train.callbacks"):
                callback(it, info)
        if checkpointer is not None and checkpointer.due(it + 1):
            with watch_fetch("checkpoint", it + 1):
                if chunk_hook is not None:
                    chunk_hook("fetch", it + 1)
                with span("train.fetch.checkpoint"):
                    with span("materialize"):
                        flush_deferred()
                        ckpt = _materialize(p, data.mapper, out,
                                            (it + 1) * K, init,
                                            max_depth_prev, best_iteration,
                                            best_value, stale)
                    if eval_history is not None:
                        ckpt.train_state["eval_history"] = eval_history
                    with span("save"):
                        checkpointer.save(ckpt, it + 1)
        if _ann_it is not None:
            _ann_it.close()
        if _t_it is not None:
            # async dispatch: this is the iteration's HOST wall, fetches
            # and callbacks included, not device execution
            record_at("train.iteration", _t_it,
                      _time.perf_counter() - _t_it)
            if _obs_iter is None:
                _obs_iter = _obs.gauge(
                    "dryad_train_iteration",
                    "Last host-side boosting iteration")
            _obs_iter.set(it)
        if stop:
            T = (it + 1) * K
            break

    # deferred evals: one final bulk fetch + replay; the full per-set
    # history lands on the booster (train_state["eval_history"]) since no
    # callback saw the values live
    with watch_fetch("final", T // K):
        if chunk_hook is not None:
            chunk_hook("fetch", T // K)
        with span("train.fetch.final"):
            flush_deferred()
            _count_grow_stats(jax.device_get(_grow_stats_of(out, stats_done, T)))

            # ---- the single end-of-training fetch ----------------------------
            booster = _materialize(p, data.mapper, out, T, init,
                                   max_depth_prev, best_iteration,
                                   best_value, stale)
    if eval_history is not None:
        booster.train_state["eval_history"] = eval_history
    if comm is not None:
        booster.train_state["comm_stats"] = comm
    booster.train_state["ch_max_effective"] = 0   # per-iteration: no chunks
    booster.train_state.update(run_device)
    return booster
