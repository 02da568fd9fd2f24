"""Device predict: level-synchronous tree traversal under jit.

Bit-identity contract (BASELINE.json:5): traversal decisions compare integer
bin ids — exact on any backend — and leaf-value accumulation runs in fp32 in
the same per-class tree order as ``cpu/predict.py`` (a ``lax.scan`` over
boosting iterations), so CPU and TPU raw scores are bit-identical given the
same model, not merely close.

r21: two traversal table layouts share that contract.  The default packed
arm ("auto" resolves to it whenever the fields fit) stages each node's
traversal fields in one (M, 2)-uint32 limb table so every level pays ONE
small-table gather; ``predict_layout="legacy"`` keeps the
structure-of-arrays arm as the comparison baseline.  Packed ≡ legacy is
bitwise on the single-device and sharded arms (tests/test_predict_packed.py
pins it across numeric/cat/missing/multiclass/rf at 1/2/8 shards).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.engine.levelwise import select_bins

# ---- packed node-word layout (r21) ----------------------------------------
# Gather cost on TPU is per-ACCESS, not per-byte (CLAUDE.md measured
# lowering facts), so the traversal fields of one node are packed into a
# single table row and the per-level body pays ONE small-table gather
# instead of the legacy structure-of-arrays ~7.  The repo never enables
# jax_enable_x64 — a device uint64 would silently truncate to uint32 — so
# the "word" is two uint32 limbs in a (..., M, 2) table; ``table[node]``
# still lowers to one gather instruction fetching 8 bytes per row.
#
#   limb0: left (bits 0..15) | right (bits 16..31)
#   limb1: threshold (0..15) | feature (16..27) | default_left (28)
#          | is_cat (29) | internal (30)
#
# Leaf nodes pack as all-zero fields with the internal bit clear; the
# traversal keeps the legacy leaf-self-loop via where(internal, nxt, node).
PACKED_CHILD_BITS = 16      # node ids: max_nodes = 2*num_leaves - 1
PACKED_THRESHOLD_BITS = 16  # bin ids: max_bins <= 65536
PACKED_FEATURE_BITS = 12    # column ids in the binned matrix


def packed_fallback_reason(feature, threshold, left, right):
    """The first traversal field that overflows its packed-word width,
    named (``"threshold max 70000 exceeds 16-bit packed width"``), or
    None when everything fits (checked against the ACTUAL staged values,
    not declared dims — a sliced model can fit even when the full one
    would not).  The reason rides the policy decision record into serve
    ``/stats`` so an operator can see WHY a model serves legacy (r23)."""
    feature = np.asarray(feature)
    internal = feature >= 0
    if not internal.any():
        return None
    named = (("feature", feature, PACKED_FEATURE_BITS),
             ("threshold", np.asarray(threshold), PACKED_THRESHOLD_BITS),
             ("left", np.asarray(left), PACKED_CHILD_BITS),
             ("right", np.asarray(right), PACKED_CHILD_BITS))
    for name, arr, bits in named:
        lo, hi = int(arr[internal].min()), int(arr[internal].max())
        if lo < 0 or hi >= (1 << bits):
            return (f"{name} range {lo}..{hi} exceeds its "
                    f"{bits}-bit packed width")
    return None


def packed_fields_fit(feature, threshold, left, right) -> bool:
    """True when every traversal field fits its packed-word width."""
    return packed_fallback_reason(feature, threshold, left, right) is None


def pack_node_words(feature, threshold, left, right, default_left,
                    is_cat) -> np.ndarray:
    """Pack per-node traversal fields (..., M) into the (..., M, 2) uint32
    limb table.  Width-asserted against the actual values; leaf fields are
    canonicalised to zero so the packing is a pure function of the
    traversal-relevant content."""
    feature = np.asarray(feature, np.int64)
    internal = feature >= 0
    fields = {
        "feature": np.where(internal, feature, 0),
        "threshold": np.where(internal, np.asarray(threshold, np.int64), 0),
        "left": np.where(internal, np.asarray(left, np.int64), 0),
        "right": np.where(internal, np.asarray(right, np.int64), 0),
    }
    widths = {"feature": PACKED_FEATURE_BITS,
              "threshold": PACKED_THRESHOLD_BITS,
              "left": PACKED_CHILD_BITS, "right": PACKED_CHILD_BITS}
    for name, arr in fields.items():
        if arr.size and (int(arr.min()) < 0
                         or int(arr.max()) >= (1 << widths[name])):
            raise ValueError(
                f"packed predict layout: field {name!r} does not fit "
                f"{widths[name]} bits (max value {int(arr.max())}); use "
                f"predict_layout='legacy' for this model")
    dl = np.where(internal & np.asarray(default_left, bool), 1, 0)
    ic = np.where(internal & np.asarray(is_cat, bool), 1, 0)
    limb0 = (fields["left"] | (fields["right"] << PACKED_CHILD_BITS))
    limb1 = (fields["threshold"]
             | (fields["feature"] << 16)
             | (dl << 28) | (ic << 29)
             | (np.where(internal, 1, 0) << 30))
    return np.stack([limb0.astype(np.uint32), limb1.astype(np.uint32)],
                    axis=-1)


def packed_shapes_fit(num_features: int, num_bins: int,
                      max_nodes: int) -> bool:
    """True when every tree of these STATIC shapes fits the packed widths:
    column ids below ``num_features``, thresholds below ``num_bins``, node
    ids below ``max_nodes``.  The trainer's test for a tree it has only as
    traced arrays (``packed_fields_fit`` reads actual values on the host)."""
    return (num_features <= 1 << PACKED_FEATURE_BITS
            and num_bins <= 1 << PACKED_THRESHOLD_BITS
            and max_nodes <= 1 << PACKED_CHILD_BITS)


def pack_node_words_device(feature, threshold, left, right, default_left,
                           is_cat) -> jnp.ndarray:
    """``pack_node_words`` on device arrays (..., M), traced: the same two
    limbs bit for bit, for a tree that has not left the device.  Widths are
    the caller's to check (``packed_shapes_fit``): traced values cannot be
    asserted, and a field past its width would spill into its neighbour."""
    internal = feature >= 0

    def field(a):
        return jnp.where(internal, a, 0).astype(jnp.uint32)

    limb0 = field(left) | (field(right) << PACKED_CHILD_BITS)
    limb1 = (field(threshold)
             | (field(feature) << 16)
             | (field(default_left) << 28) | (field(is_cat) << 29)
             | (internal.astype(jnp.uint32) << 30))
    return jnp.stack([limb0, limb1], axis=-1)


def unpack_node_words(words: np.ndarray) -> dict:
    """Inverse of ``pack_node_words`` back to the canonical (leaf-zeroed)
    field dict — the round-trip anchor for the pack/unpack property test."""
    words = np.asarray(words, np.uint32)
    limb0 = words[..., 0].astype(np.int64)
    limb1 = words[..., 1].astype(np.int64)
    internal = ((limb1 >> 30) & 1) > 0
    return {
        "left": (limb0 & 0xFFFF).astype(np.int32),
        "right": (limb0 >> PACKED_CHILD_BITS).astype(np.int32),
        "threshold": (limb1 & 0xFFFF).astype(np.int32),
        "feature": np.where(
            internal, (limb1 >> 16) & 0xFFF, -1).astype(np.int32),
        "default_left": ((limb1 >> 28) & 1) > 0,
        "is_cat": ((limb1 >> 29) & 1) > 0,
    }


def staged_layout(trees: dict) -> str:
    """Layout of a staged trees dict — dict-key presence IS the dispatch
    (pytree structure is static under jit, so this costs nothing traced)."""
    return "packed" if "node_word" in trees else "legacy"


def tree_leaves(tree: dict, Xb: jnp.ndarray, depth_bound) -> jnp.ndarray:
    """Leaf node id reached by every row in one tree (arrays shaped (M, ...)).

    ``depth_bound`` may be a Python int (static unroll bound) or a traced
    scalar (the grower's measured depth) — ``fori_loop`` accepts both.

    Two table layouts (r21), dispatched on dict-key presence (static):
    ``node_word`` selects the packed arm — one (M, 2)-uint32 table gather
    per level plus the row's bin through ``levelwise.select_bins`` (a masked
    reduce over the contiguous row where the ``partition`` gate admits, so
    no per-row gather into ``Xb``: the walk route does); otherwise
    the legacy structure-of-arrays arm runs, itself issuing the
    ``cat_bitset`` gather only when the staged dict carries one (numeric
    models no longer pay the bitset gather).  Both arms compare the SAME
    int32 bin/threshold/child values, so packed ≡ legacy is bitwise.
    """
    N = Xb.shape[0]
    if isinstance(depth_bound, int):
        depth_bound = max(depth_bound, 1)
    else:
        depth_bound = jnp.maximum(depth_bound, 1)

    def body_packed(_, node):
        w = tree["node_word"][node]                    # (N, 2) — ONE gather
        w0, w1 = w[..., 0], w[..., 1]
        internal = (w1 >> jnp.uint32(30)) > 0          # bit 31 never set
        fc = ((w1 >> jnp.uint32(16)) & jnp.uint32(0xFFF)).astype(jnp.int32)
        bins = select_bins(Xb, fc)
        num_left = bins <= (w1 & jnp.uint32(0xFFFF)).astype(jnp.int32)
        num_left &= (((w1 >> jnp.uint32(28)) & 1) > 0) | (bins != 0)
        if "cat_bitset" in tree:                       # static: model has cats
            with jax.named_scope("dryad.cat"):
                bs = tree["cat_bitset"]
                word = bs[node, jnp.minimum(bins >> 5, bs.shape[1] - 1)]
                cat_left = ((word >> (bins & 31).astype(jnp.uint32)) & 1) > 0
                go_left = jnp.where(((w1 >> jnp.uint32(29)) & 1) > 0,
                                    cat_left, num_left)
        else:
            go_left = num_left
        nxt = jnp.where(go_left,
                        (w0 & jnp.uint32(0xFFFF)).astype(jnp.int32),
                        (w0 >> jnp.uint32(16)).astype(jnp.int32))
        return jnp.where(internal, nxt, node)

    def body(_, node):
        f = tree["feature"][node]                      # (N,)
        internal = f >= 0
        fc = jnp.where(internal, f, 0).astype(jnp.int32)
        bins = jnp.take_along_axis(Xb, fc[:, None], axis=1)[:, 0].astype(jnp.int32)
        num_left = bins <= tree["threshold"][node]
        num_left &= tree["default_left"][node] | (bins != 0)
        if "cat_bitset" in tree:                       # static: model has cats
            with jax.named_scope("dryad.cat"):
                bs = tree["cat_bitset"]
                word = bs[node, jnp.minimum(bins >> 5, bs.shape[1] - 1)]
                cat_left = ((word >> (bins & 31).astype(jnp.uint32)) & 1) > 0
                go_left = jnp.where(tree["is_cat"][node], cat_left, num_left)
        else:
            # satellite r21: a False is_cat mask selected num_left exactly,
            # so dropping the dead bitset/is_cat gathers is bitwise free
            go_left = num_left
        nxt = jnp.where(go_left, tree["left"][node], tree["right"][node])
        return jnp.where(internal, nxt, node)

    # derive the init from Xb so it inherits Xb's varying axes under shard_map
    node0 = (Xb[:, 0] * 0).astype(jnp.int32)
    step = body_packed if "node_word" in tree else body
    return jax.lax.fori_loop(0, depth_bound, step, node0)


def _accumulate_body(trees: dict, Xb: jnp.ndarray, init: jnp.ndarray,
                     depth_bound: int, axis_name: str | None = None):
    """Raw scores (N, K): scan boosting iterations, vmap the K class trees.

    ``trees`` arrays are shaped (n_iter, K, M, ...); per class the additions
    happen in iteration order — the exact fp32 summation order of the CPU
    reference path.  Shared verbatim by the jitted single-device program
    and by each shard's block under ``shard_map`` (sharded_accumulate_fn):
    every op here is strictly per-row, which is what makes row sharding a
    bitwise no-op rather than an approximation.  Under ``shard_map`` pass
    ``axis_name``: the scan carry starts from the replicated ``init`` and
    must be marked device-varying like the per-row sums that replace it.
    """
    N = Xb.shape[0]
    K = trees["value"].shape[1]    # present in both layouts
    score0 = jnp.broadcast_to(init.astype(jnp.float32), (N, K))
    if axis_name is not None:
        score0 = jax.lax.pcast(score0, axis_name, to="varying")

    def step(score, tree_k):
        leaves = jax.vmap(lambda tr: tree_leaves(tr, Xb, depth_bound))(tree_k)  # (K, N)
        delta = jnp.take_along_axis(tree_k["value"], leaves, axis=1)            # (K, N)
        return score + delta.T, None

    score, _ = jax.lax.scan(step, score0, trees)
    return score


_accumulate = partial(jax.jit, static_argnames=("depth_bound",))(_accumulate_body)


@lru_cache(maxsize=None)
def sharded_accumulate_fn(mesh, depth_bound: int):
    """jit(shard_map(accumulate)): rows sharded over the mesh's data axis,
    tree tables replicated.  There are NO collectives inside — raw scores
    are per-row, so each device traverses its row block independently and
    the only cross-device motion is the implicit gather at the result edge
    when the host fetches the sharded output.  Cached per (mesh, depth) so
    warm serving traffic reuses one jitted program per bucket shape."""
    from jax.sharding import PartitionSpec as P

    from dryad_tpu.engine.distributed import AXIS

    def run(trees, Xb, init):
        return _accumulate_body(trees, Xb, init, depth_bound, axis_name=AXIS)

    return jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(AXIS, None), P()),
        out_specs=P(AXIS, None),
    ))


def predict_binned_sharded(booster, Xb, num_iteration: Optional[int] = None,
                           mesh=None):
    """``predict_binned_device`` with the padded row batch sharded across
    the mesh (trees replicated).  Rows are padded with zero bins up to a
    multiple of the shard count; padding rows are sliced away before any
    host arithmetic, and every predict stage is per-row, so the result is
    BITWISE equal to the single-device path (tests pin it on the 8 fake
    CPU devices)."""
    import jax as _jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dryad_tpu.engine import distributed as dist

    mesh = dist.make_mesh() if mesh is None else mesh
    n_shards = int(np.prod(mesh.devices.shape))
    trees_np, init, n_iter = stage_trees(booster, num_iteration)
    trees = {k: jnp.asarray(v) for k, v in trees_np.items()}
    Xb = np.asarray(Xb)
    n = int(Xb.shape[0])
    m = dist.padded_rows(max(n, 1), n_shards)
    if m != n:
        # np.concatenate already produces a fresh contiguous array, so the
        # old ascontiguousarray pre-copy paid a second full copy for nothing
        pad = np.zeros((m - n,) + Xb.shape[1:], Xb.dtype)
        Xp = np.concatenate([Xb, pad])
    else:
        Xp = Xb    # no padding needed -> zero-copy straight into device_put
    Xp = _jax.device_put(Xp, NamedSharding(mesh, P(dist.AXIS, None)))
    depth = max(booster.max_depth_seen, 1)
    fn = sharded_accumulate_fn(mesh, depth)
    init_j = jnp.asarray(init)
    # compile-boundary introspection (r12): dryad_prog_* for the sharded
    # predict program; memoized per shape, observation-only
    from dryad_tpu.engine import introspect

    layout = staged_layout(trees_np)
    introspect.capture(
        "predict", ("sharded", n_shards, n_iter, booster.num_outputs,
                    Xp.shape, depth, layout),
        fn, trees, Xp, init_j,
        labels={"arm": "sharded", "shards": n_shards, "layout": layout})
    # np.asarray is the result-edge gather AND the one real host fetch
    raw = np.asarray(fn(trees, Xp, init_j))[:n]
    if booster.params.boosting == "rf" and n_iter > 0:
        from dryad_tpu.cpu.predict import rf_average

        return rf_average(raw, booster.init_score, n_iter)
    return raw


def stage_trees(booster, num_iteration: Optional[int] = None,
                layout: Optional[str] = None):
    """Slice + reshape the tree tables for the device scan: (n_iter, K, M, ...)
    numpy arrays, the ``num_iteration``/``best_iteration`` semantics of
    ``predict_binned_cpu``.  Traversal-irrelevant tables (gain, cover) are
    dropped — they never feed an op, so removing them from the scan carry
    cannot change a bit of the result.  Shared by the one-shot device
    predict below and by the serving layer's model registry, which keeps
    the staged arrays device-resident across requests.

    ``layout`` (default: ``booster.params.predict_layout``) selects the
    staged table layout:

    * ``"packed"`` — the r21 node-word arm: traversal fields packed into a
      (n_iter, K, M, 2) uint32 limb table (``pack_node_words``, width-
      asserted), ``cat_bitset`` kept ONLY when the sliced model actually
      contains a categorical split, so numeric programs are statically
      bitset-free.  Raises when a field exceeds its packed width.
    * ``"legacy"`` — the structure-of-arrays comparison arm; numeric
      models drop ``is_cat``/``cat_bitset`` (they fed a dead select).
    * ``"auto"`` — packed when every field fits, legacy otherwise.

    Packing only rewrites TRAVERSAL inputs; ``value`` and the accumulation
    scan are untouched, so packed ≡ legacy predict is bitwise.
    """
    K = booster.num_outputs
    if num_iteration is None:
        n_iter = booster.best_iteration if booster.best_iteration > 0 else booster.num_iterations
    else:
        n_iter = min(num_iteration, booster.num_iterations)
    ta = booster.tree_arrays()
    T = n_iter * K
    trees = {
        k: v[:T].reshape((n_iter, K) + v.shape[1:])
        for k, v in ta.items() if k not in ("gain", "cover")
    }
    if layout is None:
        layout = getattr(booster.params, "predict_layout", "auto")
    if layout == "auto":
        from dryad_tpu.policy.gates import resolve

        reason = packed_fallback_reason(
            trees["feature"], trees["threshold"], trees["left"],
            trees["right"])
        layout = resolve("predict_layout", {"fits": reason is None},
                         detail=reason)
    has_cat = bool(np.asarray(trees["is_cat"]).any())
    if layout == "packed":
        words = pack_node_words(
            trees["feature"], trees["threshold"], trees["left"],
            trees["right"], trees["default_left"], trees["is_cat"])
        staged = {"node_word": words, "value": trees["value"]}
        if has_cat:
            staged["cat_bitset"] = trees["cat_bitset"]
        trees = staged
    elif not has_cat:
        trees = {k: v for k, v in trees.items()
                 if k not in ("is_cat", "cat_bitset")}
    return trees, np.asarray(booster.init_score, np.float32), n_iter


def predict_binned_device(
    booster, Xb, num_iteration: Optional[int] = None
):
    """``dryad.predict`` device backend on pre-binned rows → raw scores
    (N, K).  Returns a device array — except under ``boosting='rf'``,
    where the final averaging transform runs on host (see below) and a
    numpy array comes back; the sole caller (Booster.predict_binned) ends
    in ``np.asarray`` either way."""
    trees_np, init, n_iter = stage_trees(booster, num_iteration)
    trees = {k: jnp.asarray(v) for k, v in trees_np.items()}
    Xb = jnp.asarray(Xb)
    depth = max(booster.max_depth_seen, 1)
    init_j = jnp.asarray(init)
    # compile-boundary introspection (r12) — memoized per shape
    from dryad_tpu.engine import introspect

    layout = staged_layout(trees_np)
    introspect.capture(
        "predict", ("single", n_iter, booster.num_outputs, Xb.shape, depth,
                    layout),
        _accumulate, trees, Xb, init_j, depth,
        labels={"arm": "single", "shards": 1, "layout": layout})
    raw = _accumulate(trees, Xb, init_j, depth)
    if booster.params.boosting == "rf" and n_iter > 0:
        # rf averaging runs ON HOST via the ONE shared transform (device
        # FMA fusion is 1 ulp off — see cpu/predict.rf_average); the
        # accumulation stays on device, only the final elementwise
        # transform moves (predict ends in one host fetch anyway)
        from dryad_tpu.cpu.predict import rf_average

        return rf_average(np.asarray(raw), booster.init_score, n_iter)
    return raw
