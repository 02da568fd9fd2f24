"""Distributed data-parallel training: rows sharded over a device mesh.

The reference distributes GBDT training the NCCL way: shard rows across
workers, build local per-node grad/hess histograms, allreduce them, take
identical split decisions everywhere (BASELINE.json:5; SURVEY.md §2 #13-14).
The TPU-native translation keeps that exact dataflow but rides XLA
collectives:

* mesh axis ``"data"`` spans all chips (ICI within a slice, DCN across
  hosts on v5p-64 — the mesh abstracts both).
* the full per-class train step (grad/hess -> grow -> partition -> score
  update) runs under ``shard_map``: every device executes the same grower
  program on its row shard.
* the cross-device exchange is per-arm (``Params.hist_reduce``).  The
  "fused" arm keeps the classic contract: ONE fused grad/hess/count
  histogram ``jax.lax.psum`` per builder call — payload the full
  (P, 3, F, B) fp32 stack, exactly where the reference put NCCL; split
  decisions derive from the replicated histogram, so every device grows
  bit-identical trees with no further communication.  The "feature" arm
  (r16 — LightGBM's reduce-scatter data-parallel mode) replaces that
  all-reduce with ``reduce_scatter_hist``: each shard receives its OWN
  contiguous F/n feature slice fully reduced (per-device reduced payload
  shrinks n-fold), runs the split scan on the owned slice only
  (``split.find_best_split_sliced``), and one tiny per-level
  ``all_gather`` of packed best-split records (``combine_best_splits``)
  makes every shard pick the SAME winner — the packed tie key reproduces
  the fused scan's feature-major first-max order exactly, and the
  reduce-scattered slices are bitwise-equal to the psum's slices
  (measured; pinned by tests/test_hist_reduce.py).

Row counts must divide the mesh; ``pad_rows`` pads with bagged-out rows
(mask False) that cannot influence any histogram.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dryad_tpu.config import Params, hist_reduce_resolved

AXIS = "data"


def make_mesh(devices=None) -> Mesh:
    devices = jax.devices() if devices is None else devices
    return Mesh(np.asarray(devices), (AXIS,))


def padded_rows(n: int, n_shards: int) -> int:
    return -(-n // n_shards) * n_shards


def replicate(mesh: Mesh, tree):
    """Place a pytree fully replicated over the mesh — one copy per device.

    The serving registry uses this for predict's tree tables: replicating
    once at stage time means every sharded predict dispatch finds its
    operands already resident instead of re-transferring them per call."""
    return jax.device_put(tree, NamedSharding(mesh, P()))


def shard_rows(mesh: Mesh, *arrays):
    """Place row-indexed arrays with rows split over the mesh's data axis."""
    out = []
    for a in arrays:
        spec = P(AXIS) if a.ndim == 1 else P(AXIS, *(None,) * (a.ndim - 1))
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return tuple(out)


# ---------------------------------------------------------------------------
# feature-parallel histogram reduction (hist_reduce="feature", r16)
# ---------------------------------------------------------------------------

def axis_shards(axis_name) -> int:
    """Static shard count inside shard_map (psum of a constant folds to
    the axis size at trace time — the pallas_hist.maybe_natural_tiles
    precedent); 1 outside any mesh."""
    return int(jax.lax.psum(1, axis_name)) if axis_name is not None else 1


def feature_slice_width(num_features: int, n_shards: int) -> int:
    """Owned features per shard: ceil(F / n).  Non-divisible F pads the
    reduced histogram (and the sliced masks) with dead features — all-pad
    shards contribute -inf records the combine can never pick."""
    return -(-num_features // max(n_shards, 1))


def reduce_scatter_hist(hist: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """(..., F, B) per-shard partial histograms -> (..., Fs, B) fully
    reduced OWNED slice (shard i owns features [i*Fs, (i+1)*Fs) of the
    zero-padded feature axis).  The reduce-scattered slice is bitwise
    equal to the corresponding slice of ``jax.lax.psum`` on this backend
    (measured; the fused-vs-feature bitwise parity tests ride on it)."""
    n = axis_shards(axis_name)
    F = hist.shape[-2]
    pad = feature_slice_width(F, n) * n - F
    if pad:
        width = [(0, 0)] * (hist.ndim - 2) + [(0, pad), (0, 0)]
        hist = jnp.pad(hist, width)
    return jax.lax.psum_scatter(hist, axis_name,
                                scatter_dimension=hist.ndim - 2, tiled=True)


def reduce_hist(hist: jnp.ndarray, axis_name, hist_reduce: str = "fused"):
    """The one histogram cross-shard reduction every builder tail calls:
    the fused psum (default — the classic single collective) or the
    feature-arm reduce-scatter.  No-op outside a mesh (the degenerate
    single-device "feature" program keeps the full slice).  The exchange
    (this reduction on either arm, and the feature arm's all-gather of
    best-split records) has a scope of its own, ``dryad.reduce``: innermost,
    so a trace reads it apart from the ``dryad.hist`` glue around it."""
    if axis_name is None:
        return hist
    with jax.named_scope("dryad.reduce"):
        if hist_reduce == "feature":
            return reduce_scatter_hist(hist, axis_name)
        return jax.lax.psum(hist, axis_name)


def feature_shard_slice(arr: jnp.ndarray, axis_name, axis: int = 0):
    """Slice a replicated feature-indexed array to this shard's owned
    features (zero/False padding on the tail shard — dead entries).  The
    identity outside a mesh: the degenerate 1-shard feature program scans
    the full slice."""
    if axis_name is None:
        return arr
    n = axis_shards(axis_name)
    F = arr.shape[axis]
    Fs = feature_slice_width(F, n)
    pad = Fs * n - F
    if pad:
        width = [(0, 0)] * arr.ndim
        width[axis] = (0, pad)
        arr = jnp.pad(arr, width)
    off = jax.lax.axis_index(axis_name).astype(jnp.int32) * Fs
    return jax.lax.dynamic_slice_in_dim(arr, off, Fs, axis=axis)


def feature_shard_offset(axis_name, num_features: int) -> jnp.ndarray:
    """This shard's first owned GLOBAL feature id (0 outside a mesh) —
    the sliced scan's ``feat_offset``, a traced scalar so every shard
    runs ONE program."""
    if axis_name is None:
        return jnp.int32(0)
    Fs = feature_slice_width(num_features, axis_shards(axis_name))
    return jax.lax.axis_index(axis_name).astype(jnp.int32) * Fs


@jax.named_scope("dryad.split_scan")
def combine_best_splits(rec, axis_name, *, allow, min_split_gain: float,
                        has_cat: bool):
    """All-gather per-shard LocalSplit records and run the replicated
    combine — every shard computes the identical SplitResult batch.  The
    scalar fields ride ONE packed (…, 8)-word all-gather per level (plus
    one for the raw categorical rows when the config has them); outside a
    mesh the gather degenerates to a leading singleton axis (same combine
    program)."""
    from dryad_tpu.engine.split import combine_local_splits, pack_local_split

    words = pack_local_split(rec)
    cat = rec.cat_mask if has_cat else None
    if axis_name is not None:
        with jax.named_scope("dryad.reduce"):
            words = jax.lax.all_gather(words, axis_name, axis=0)
            if cat is not None:
                cat = jax.lax.all_gather(cat, axis_name, axis=0)
    else:
        words = words[None]
        cat = cat[None] if cat is not None else None
    return combine_local_splits(words, cat, allow=allow,
                                min_split_gain=min_split_gain,
                                has_cat=has_cat)


def grow_sharded(params: Params, total_bins: int, has_cat: bool,
                 mesh: Mesh, Xb, g, h, bag_mask, feat_mask, is_cat_feat,
                 platform=None, learn_missing=False, root_hist=None,
                 bundled_mask=None, global_rows=None):
    """One sharded tree grow; returns the grower's tree dict.

    Called inside the device train step's jit: the tree arrays and the
    key -> leaf table come back replicated, each row's partition key
    (``row_key``) keeps the row sharding so the caller's score update stays
    shard-local.  ``root_hist`` (replicated) carries the class's slice of
    the shared-plan multiclass root pass.
    """
    from dryad_tpu.engine.grower import grow_any  # lazy: builders import us

    def run(Xb_l, g_l, h_l, bag_l, fmask, iscat, *extras):
        extras = list(extras)
        bmask_l = extras.pop(0) if bundled_mask is not None else None
        return grow_any(
            params, total_bins, Xb_l, g_l, h_l, bag_l, fmask, iscat,
            has_cat=has_cat, axis_name=AXIS, platform=platform,
            learn_missing=learn_missing,
            root_hist=extras[0] if extras else None,
            bundled_mask=bmask_l, global_rows=global_rows,
        )

    row = P(AXIS)
    row2 = P(AXIS, None)
    rep = P()
    tree_specs = {
        "feature": rep, "threshold": rep, "left": rep, "right": rep,
        "value": rep, "gain": rep, "is_cat": rep, "cat_bitset": rep,
        "default_left": rep, "cover": rep, "max_depth": rep,
        "row_key": row, "key_leaf": rep,
    }
    extra = () if bundled_mask is None else (bundled_mask,)
    extra += () if root_hist is None else (root_hist,)
    # the feature arm's combine all_gather is typed device-VARYING by the
    # vma checker although its outputs ARE device-identical (the combine
    # runs on gathered records), so the replicated out_specs the tree
    # arrays need are rejected; the check comes off for that arm only,
    # with the N-shard ≡ 1-shard ≡ fused parity tests standing in.
    # Only the LEVEL-SYNCHRONOUS growers run the feature program — the
    # sequential grower ignores hist_reduce — so the checker stays ON for
    # every fused program (mirrors _comm_stats' level_synchronous rule).
    level_sync = params.growth == "depthwise" and params.max_depth > 0
    if not level_sync and params.growth == "leafwise":
        from dryad_tpu.engine import leafwise_fast

        level_sync = leafwise_fast.supports(
            params, Xb.shape[1], int(total_bins),
            global_rows if global_rows is not None else Xb.shape[0],
            mesh.devices.size)
    mode = (hist_reduce_resolved(params, Xb.shape[1], int(total_bins),
                                 mesh.devices.size)
            if level_sync else "fused")
    if level_sync and params.growth == "leafwise":
        # the batched grower's two statistics (train._GROW_STATS)
        tree_specs.update(expanded_splits=rep, selected_splits=rep)
    # jax 0.9's Pallas HLO interpreter evaluates a kernel's index maps
    # without the casts the vma checker wants between a shard-varying
    # scalar-prefetch operand and the unvarying grid index (dynamic_slice:
    # "varying manual axes to match"), so the interpreted kernels of a CPU
    # mesh cannot be traced with the checker on; the chip's program, whose
    # kernels are Mosaic calls, keeps it.
    from dryad_tpu.engine.histogram import resolve_backend

    plat = platform or mesh.devices.flat[0].platform
    interpreted = (plat == "cpu" and resolve_backend(
        params.hist_backend, platform=plat) == "pallas")
    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(row2, row, row, row, rep, rep) + (rep,) * len(extra),
        out_specs=tree_specs,
        check_vma=mode != "feature" and not interpreted,
    )(Xb, g, h, bag_mask, feat_mask, is_cat_feat, *extra)


def roots_sharded(mesh: Mesh, Xb, g_all, h_all, bag, total_bins,
                  rows_per_chunk, precision):
    """Shared-plan multiclass root histograms over the mesh -> replicated
    (K, 3, F, B); one fused psum carries all K classes' stats.  Runs the
    SAME builder program as the single-device path so near-tie root
    argmaxes cannot differ between 1-shard and N-shard runs (the MXU's
    lowering of the (2K+1)-row pass is fusion-sensitive — measured NOT
    bitwise vs the 3-row per-class pass on real hardware)."""
    from dryad_tpu.engine.histogram import build_hist_classes

    def run(X, gs, hs, bg):
        return build_hist_classes(
            X, gs, hs, bg, total_bins, rows_per_chunk=rows_per_chunk,
            precision=precision, axis_name=AXIS)

    row = P(AXIS)
    row2 = P(AXIS, None)
    return jax.shard_map(
        run, mesh=mesh, in_specs=(row2, row2, row2, row), out_specs=P(),
    )(Xb, g_all, h_all, bag)
