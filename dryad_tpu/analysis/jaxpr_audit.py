"""Layer 2: the jaxpr auditor — IR invariants checked mechanically.

Traces the growers, histogram builders and sharded predict with ABSTRACT
inputs (``jax.make_jaxpr`` over ``ShapeDtypeStruct``s — nothing compiles,
nothing runs, so the Pallas/TPU programs trace on a CPU-only box) and
walks the closed jaxprs for the invariants the repo documents:

* **Collective census** — the growers' collective plan is per-arm (r16):
  on the fused arm the ONLY collective is the fused grad/hess/count psum
  in the histogram builders; on the feature arm (hist_reduce="feature")
  each level's builder issues one reduce-scatter and each level ONE
  combine all-gather, with the root still on the fused psum — counts of
  all three are cross-checked against ``_comm_stats``.  GOSS adds one
  global sort per iteration, the L1-family leaf renewal one global
  (leaf, residual) sort per tree; sharded predict has ZERO collectives.
  Counts are TRIP-WEIGHTED: ``fori_loop`` with static bounds lowers to
  ``scan`` whose ``length`` param is in the jaxpr, so "one psum per level
  body x 7 levels" counts as 7.  The census is cross-checked against
  ``engine.train._comm_stats`` on every arm — the accounting and the
  traced program must agree or one of them drifted.
* **Row-sort / row-gather census** — sorts and gathers touching row-scale
  operands, distinguished from (L,)-slot bookkeeping by a per-arm row
  threshold.  The wired layout arms must show ZERO row sorts ("nothing on
  the wired path sorts rows", r10); the legacy arm's tile-plan sorts are
  recorded in the goldens so their count is pinned too.
* **Kernel-boundary dtype discipline** — for every ``pallas_call``, the
  dominant integer operand must be u8/u16 (tiles stay u8/u16 end to end;
  the kernel casts in VMEM — 4x tile HBM traffic otherwise, CLAUDE.md
  lowering facts), and each kernel's full input signature is recorded.
* **Program digests** — a canonical structural digest per arm, compared
  against committed goldens (``--update-goldens`` refreshes after an
  INTENTIONAL program change).  This is the fusion-shape tripwire: any
  pass that replaces another must run the SAME program on every path, or
  near-tie argmaxes flip between arms.

Arm configs are intentionally small (trace cost only — shapes never
execute) but chosen so every audited regime is LIVE: the wired layout
gates admit, the legacy deep phase really runs its tile-plan sort, GOSS
and renewal really emit their one global sort.
"""

from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass, field
from typing import Callable, Optional

from dryad_tpu.analysis.digests import (
    GOLDENS_PATH,
    canonical_digest,
    iter_sub_jaxprs,
    load_goldens,
    save_goldens,
)

_COLLECTIVES = frozenset({
    "psum", "psum2", "psum_invariant", "all_reduce", "all_gather",
    "all_gather_invariant", "all_to_all", "ppermute", "pbroadcast",
    "reduce_scatter", "pmin", "pmax", "pgather", "axis_index",
})

# a vma-checked shard_map spells the all-reduce whose result is device-
# invariant ``psum_invariant``; the unchecked feature arm spells the same
# collective ``psum`` — one census key for both
_CANONICAL = {"psum_invariant": "psum"}

# mesh width every arm traces against (matches tests/conftest.py's 8 fake
# CPU devices; the CLI exports the same XLA_FLAGS before importing jax)
N_SHARDS = 8


# ---------------------------------------------------------------------------
# census walk

@dataclass
class Census:
    collectives: Counter = field(default_factory=Counter)
    # row-scale sorts OUTSIDE any shard_map body run on the GLOBAL array
    # (a GSPMD collective sort under a mesh — the GOSS quantile / renewal
    # class); sorts INSIDE a shard_map body are shard-LOCAL implementation
    # details of a builder (the XLA segmented pass sorts its shard per
    # level) and are pinned by goldens, not by the collective contract
    global_row_sorts: int = 0
    local_row_sorts: int = 0
    row_gathers: int = 0
    # gathers whose every operand sits BELOW the row threshold — the
    # small-table per-node lookups of the predict traversal (r21).  Gather
    # cost is per-ACCESS on TPU, so the packed node-word arm's whole point
    # is this count: 1 per level vs the legacy structure-of-arrays 7.
    # Trip-weighted like everything else.
    table_gathers: int = 0
    # the score update's per-row look-ups (PR 35): gathers inside scope
    # ``dryad.score`` whose INDEX is row-sized — one a tree, from the
    # composed (keys, 2) record table (train._row_records); two where leaf
    # renewal needs the leaf before the value.  ``score_flat_gathers`` are
    # those among them whose table has no minor dimension of at least two
    # words: the 1-D form, which costs four times as much an index on the
    # chip (PERF.md) and which no arm may hold.
    score_row_gathers: int = 0
    score_flat_gathers: int = 0
    pallas_kernels: dict = field(default_factory=dict)  # name -> set of sigs
    dynamic_loop: bool = False
    branch_mismatch: bool = False

    def scaled(self, k: int) -> "Census":
        out = Census(Counter({p: n * k for p, n in self.collectives.items()}),
                     self.global_row_sorts * k, self.local_row_sorts * k,
                     self.row_gathers * k, self.table_gathers * k,
                     self.score_row_gathers * k, self.score_flat_gathers * k,
                     {n: set(s) for n, s in self.pallas_kernels.items()},
                     self.dynamic_loop, self.branch_mismatch)
        return out

    def add(self, other: "Census") -> None:
        self.collectives.update(other.collectives)
        self.global_row_sorts += other.global_row_sorts
        self.local_row_sorts += other.local_row_sorts
        self.row_gathers += other.row_gathers
        self.table_gathers += other.table_gathers
        self.score_row_gathers += other.score_row_gathers
        self.score_flat_gathers += other.score_flat_gathers
        for name, sigs in other.pallas_kernels.items():
            self.pallas_kernels.setdefault(name, set()).update(sigs)
        self.dynamic_loop |= other.dynamic_loop
        self.branch_mismatch |= other.branch_mismatch

    @property
    def interesting(self) -> bool:
        return (bool(self.collectives) or self.global_row_sorts
                or self.local_row_sorts or self.row_gathers)


def _aval_sig(v) -> str:
    aval = getattr(v, "aval", None)
    if aval is None:
        return "lit"
    return f"{getattr(aval, 'dtype', '?')}{tuple(getattr(aval, 'shape', ()))}"


def _shape(v) -> tuple:
    return tuple(getattr(getattr(v, "aval", None), "shape", ()) or ())


def _max_rows(eqn) -> int:
    return max((int(_shape(v)[0])
                for v in list(eqn.invars) + list(eqn.outvars) if _shape(v)),
               default=0)


def census_jaxpr(jaxpr, row_threshold: int, in_shard_map: bool = False,
                 stack: str = "") -> Census:
    """Trip-weighted census of one (possibly closed) jaxpr.  ``stack`` is
    the name stack of the equations that enclose it (a sub-jaxpr's own
    stacks start afresh)."""
    j = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    out = Census()
    for eqn in j.eqns:
        name = eqn.primitive.name
        here = stack + "/" + str(eqn.source_info.name_stack)
        if name in _COLLECTIVES:
            out.collectives[_CANONICAL.get(name, name)] += 1
        elif name == "sort" and _max_rows(eqn) >= row_threshold:
            if in_shard_map:
                out.local_row_sorts += 1
            else:
                out.global_row_sorts += 1
        elif name == "gather":
            if _max_rows(eqn) >= row_threshold:
                out.row_gathers += 1
            else:
                out.table_gathers += 1
            table, index = _shape(eqn.invars[0]), _shape(eqn.invars[1])
            if ("dryad.score" in here and index
                    and index[0] >= row_threshold):
                out.score_row_gathers += 1
                out.score_flat_gathers += len(table) < 2 or table[-1] < 2
        elif name == "pallas_call":
            # the call's explicit name, else the kernel body's function
            kname = (eqn.params.get("name")
                     or eqn.params["jaxpr"].debug_info.func_name)
            sig = "(" + ",".join(_aval_sig(v) for v in eqn.invars) + ")"
            out.pallas_kernels.setdefault(kname, set()).add(sig)
            continue  # do not descend into kernel bodies
        subs = [(key, sub, consts)
                for key, sub, consts in iter_sub_jaxprs(eqn)]
        sub_in_sm = in_shard_map or name == "shard_map"
        if name == "scan":
            length = int(eqn.params.get("length", 1))
            for _, sub, _ in subs:
                out.add(census_jaxpr(sub, row_threshold, sub_in_sm,
                                     here).scaled(length))
        elif name == "while":
            inner = Census()
            for _, sub, _ in subs:
                inner.add(census_jaxpr(sub, row_threshold, sub_in_sm, here))
            inner.dynamic_loop |= inner.interesting
            out.add(inner)
        elif name == "cond":
            branches = [census_jaxpr(sub, row_threshold, sub_in_sm, here)
                        for _, sub, _ in subs]
            if branches:
                merged = branches[0]
                for b in branches[1:]:
                    if (b.collectives != merged.collectives
                            or b.global_row_sorts != merged.global_row_sorts):
                        merged.branch_mismatch = True
                    merged.collectives = Counter({
                        p: max(merged.collectives.get(p, 0),
                               b.collectives.get(p, 0))
                        for p in set(merged.collectives) | set(b.collectives)})
                    merged.global_row_sorts = max(merged.global_row_sorts,
                                                  b.global_row_sorts)
                    merged.local_row_sorts = max(merged.local_row_sorts,
                                                 b.local_row_sorts)
                    merged.row_gathers = max(merged.row_gathers, b.row_gathers)
                    merged.table_gathers = max(merged.table_gathers,
                                               b.table_gathers)
                    merged.score_row_gathers = max(merged.score_row_gathers,
                                                   b.score_row_gathers)
                    merged.score_flat_gathers = max(merged.score_flat_gathers,
                                                    b.score_flat_gathers)
                    for n, s in b.pallas_kernels.items():
                        merged.pallas_kernels.setdefault(n, set()).update(s)
                    merged.dynamic_loop |= b.dynamic_loop
                    merged.branch_mismatch |= b.branch_mismatch
                out.add(merged)
        else:
            for _, sub, _ in subs:
                out.add(census_jaxpr(sub, row_threshold, sub_in_sm, here))
    return out


def kernel_dtype_violations(census: Census) -> list[str]:
    """Tiles stay u8/u16 end to end: for every pallas kernel input
    signature, the LARGEST integer operand must be u8/u16 (f32/bf16
    weights and small i32 seg/pos metadata are expected; an i32 operand
    dominating the integer bytes means someone widened the tiles)."""
    bad = []
    for kname, sigs in sorted(census.pallas_kernels.items()):
        for sig in sorted(sigs):
            best_bytes, best_dtype = 0, None
            for m in re.finditer(r"(u?int\d+)\((\d+(?:,\s*\d+)*)?,?\)", sig):
                dtype = m.group(1)
                dims = [int(x) for x in (m.group(2) or "1").split(",")]
                size = {"int8": 1, "uint8": 1, "int16": 2, "uint16": 2,
                        "int32": 4, "uint32": 4, "int64": 8, "uint64": 8}[dtype]
                nbytes = size
                for d in dims:
                    nbytes *= d
                if nbytes > best_bytes:
                    best_bytes, best_dtype = nbytes, dtype
            if best_dtype is not None and best_dtype not in ("uint8",
                                                             "uint16"):
                bad.append(
                    f"kernel {kname}: dominant integer operand is "
                    f"{best_dtype} in {sig} — tiles must stay u8/u16 into "
                    "the kernel (cast in VMEM; CLAUDE.md lowering facts)")
    return bad


# ---------------------------------------------------------------------------
# arms

@dataclass
class Arm:
    name: str
    doc: str
    build: Callable[[], tuple]       # -> (fn, args, meta dict)


def _mesh():
    import jax

    from dryad_tpu.engine.distributed import make_mesh

    if len(jax.devices()) < N_SHARDS:
        raise RuntimeError(
            f"jaxpr audit needs {N_SHARDS} devices "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=8, "
            "JAX_PLATFORMS=cpu — the CLI does this automatically)")
    return make_mesh(jax.devices()[:N_SHARDS])


def _train_arm(params: dict, *, N=2048, F=8, platform="tpu", K=1,
               renewal=False):
    from dryad_tpu.config import make_params
    from dryad_tpu.engine.train import (_comm_stats, _shared_roots_ok,
                                        audit_iteration_args,
                                        audit_iteration_fn)

    p = make_params(params).validate()
    mesh = _mesh()
    renew_a = None
    if renewal:
        from dryad_tpu.objectives import renew_alpha

        renew_a = renew_alpha(p, weighted=False)
        assert renew_a is not None, "renewal arm config does not renew"
    B = int(params["max_bins"])
    fn = audit_iteration_fn(p, B, False, mesh, platform, N, K=K,
                            renew_alpha=renew_a)
    comm = _comm_stats(p, F, B, K, N_SHARDS,
                       shared_roots=K > 1 and _shared_roots_ok(p, platform),
                       num_rows=N, padded_rows=N, platform=platform)
    meta = {
        "rows_threshold": N // N_SHARDS,
        "expected_psums": comm["psum_calls_per_iter"],
        "comm": comm,
        # one record gather a tree; renewal reads the leaf from one and
        # the renewed value from a second
        "expected_score_gathers": K * (2 if renewal else 1),
    }
    return fn, audit_iteration_args(p, N, F, K), meta


def _arm_levelwise_wired():
    return _train_arm(dict(objective="binary", num_trees=1, num_leaves=127,
                           max_depth=7, growth="depthwise", max_bins=32,
                           hist_backend="pallas"),
                      platform="tpu") + ({"expected_row_sorts": 0,
                                          "wired": True},)


def _arm_levelwise_legacy():
    return _train_arm(dict(objective="binary", num_trees=1, num_leaves=127,
                           max_depth=7, growth="depthwise", max_bins=32,
                           hist_backend="pallas", deep_layout="legacy"),
                      platform="tpu") + ({"expected_row_sorts": 0},)


def _arm_leafwise_wired():
    return _train_arm(dict(objective="binary", num_trees=1, num_leaves=31,
                           max_depth=5, growth="leafwise", max_bins=32,
                           hist_backend="pallas"),
                      platform="tpu") + ({"expected_row_sorts": 0,
                                          "wired": True},)


def _arm_levelwise_feature():
    # the SAME wired config as levelwise_wired with the reduce-scatter
    # arm forced on (F=8 is far below the auto gate — explicit "feature"
    # keeps the trace cheap while the collective plan is fully live:
    # root psum + per-level reduce_scatter + per-level combine all_gather)
    return _train_arm(dict(objective="binary", num_trees=1, num_leaves=127,
                           max_depth=7, growth="depthwise", max_bins=32,
                           hist_backend="pallas", hist_reduce="feature"),
                      platform="tpu") + ({"expected_row_sorts": 0,
                                          "wired": True},)


def _arm_leafwise_feature():
    return _train_arm(dict(objective="binary", num_trees=1, num_leaves=31,
                           max_depth=5, growth="leafwise", max_bins=32,
                           hist_backend="pallas", hist_reduce="feature"),
                      platform="tpu") + ({"expected_row_sorts": 0,
                                          "wired": True},)


def _arm_goss():
    return _train_arm(dict(objective="binary", num_trees=1, num_leaves=127,
                           max_depth=7, growth="depthwise", max_bins=32,
                           hist_backend="pallas", boosting="goss",
                           goss_top_rate=0.3, goss_other_rate=0.2),
                      platform="tpu") + ({"expected_row_sorts": 1,
                                          "wired": True},)


def _arm_renewal():
    return _train_arm(dict(objective="l1", num_trees=1, num_leaves=15,
                           max_depth=4, growth="leafwise", max_bins=32),
                      platform="cpu", renewal=True) \
        + ({"expected_row_sorts": 1},)


def _arm_multiclass_shared_roots():
    return _train_arm(dict(objective="multiclass", num_class=3, num_trees=1,
                           num_leaves=15, max_depth=4, growth="depthwise",
                           max_bins=32),
                      platform="cpu", K=3) + ({"expected_row_sorts": 0},)


def _arm_sharded_predict():
    import jax
    import jax.numpy as jnp

    from dryad_tpu.booster import CAT_WORDS
    from dryad_tpu.engine.predict import sharded_accumulate_fn

    mesh = _mesh()
    N, F, M, n_iter, K, depth = 2048, 8, 63, 3, 1, 6
    fn = sharded_accumulate_fn(mesh, depth)
    sds = jax.ShapeDtypeStruct
    trees = {
        "feature": sds((n_iter, K, M), jnp.int32),
        "threshold": sds((n_iter, K, M), jnp.int32),
        "left": sds((n_iter, K, M), jnp.int32),
        "right": sds((n_iter, K, M), jnp.int32),
        "value": sds((n_iter, K, M), jnp.float32),
        "is_cat": sds((n_iter, K, M), jnp.bool_),
        "cat_bitset": sds((n_iter, K, M, CAT_WORDS), jnp.uint32),
        "default_left": sds((n_iter, K, M), jnp.bool_),
    }
    args = (trees, sds((N, F), jnp.uint8), sds((1,), jnp.float32))
    meta = {"rows_threshold": N // N_SHARDS, "expected_psums": 0,
            "comm": {"psum_calls_per_iter": 0}}
    # legacy structure-of-arrays traversal, CAT program: per level the
    # feature/threshold/default_left/is_cat/left/right lookups + the
    # cat_bitset word = 7 small-table gathers — the baseline the packed
    # arm collapses to 1/level.  (The per-iteration value lookup's index
    # operand is N-long after take_along_axis's reshape, so it lands in
    # row_gathers, not here.)
    return fn, args, meta, {"expected_row_sorts": 0,
                            "collective_free": True,
                            "expected_table_gathers": 3 * 6 * 7}


def _arm_packed_predict():
    import jax
    import jax.numpy as jnp

    from dryad_tpu.engine.predict import sharded_accumulate_fn

    mesh = _mesh()
    N, F, M, n_iter, K, depth = 2048, 8, 63, 3, 1, 6
    fn = sharded_accumulate_fn(mesh, depth)
    sds = jax.ShapeDtypeStruct
    # the r21 packed numeric program: node traversal fields live in ONE
    # (M, 2)-uint32 limb table, no cat_bitset key -> statically bitset-free
    trees = {
        "node_word": sds((n_iter, K, M, 2), jnp.uint32),
        "value": sds((n_iter, K, M), jnp.float32),
    }
    args = (trees, sds((N, F), jnp.uint8), sds((1,), jnp.float32))
    meta = {"rows_threshold": N // N_SHARDS, "expected_psums": 0,
            "comm": {"psum_calls_per_iter": 0}}
    # exactly ONE node-word gather per level — the acceptance pin (<= 2
    # small-table gathers/level; the value lookup rides row_gathers)
    return fn, args, meta, {"expected_row_sorts": 0,
                            "collective_free": True,
                            "expected_table_gathers": 3 * 6 * 1}


def _arm_train_eval():
    import jax
    import jax.numpy as jnp

    from dryad_tpu.booster import CAT_WORDS
    from dryad_tpu.engine.train import _apply_valid_jit, _empty_out_device

    N, F, B, M, depth = 2048, 8, 32, 63, 6
    sds = jax.ShapeDtypeStruct
    out = jax.eval_shape(lambda: _empty_out_device(1, M, CAT_WORDS))

    # the body per-iteration dispatch runs after every tree (the chunk
    # program's eval stage is the same two calls): the fresh tree's fields
    # packed on the device, then the packed walk.  The jitted entry takes
    # the depth traced (a while loop, counted once); the body under it
    # with a static depth makes the walk a scan the census weights by trips
    def fn(out, t, vXb, vs_col):
        return _apply_valid_jit.__wrapped__(out, t, vXb, vs_col, depth, B,
                                            False)

    args = (out, sds((), jnp.int32), sds((N, F), jnp.uint8),
            sds((N,), jnp.float32))
    # rows_threshold above N: the valid rows are the walk's INDEX, so every
    # per-node lookup lands in table_gathers, as in the predict arms (where
    # vmap's leading K axis does it)
    meta = {"rows_threshold": N + 1, "expected_psums": 0,
            "comm": {"psum_calls_per_iter": 0}}
    # ONE node-word gather a level + the value lookup; the structure of
    # arrays it replaced read 8 a level (6 fields, the bitset, the bin)
    return fn, args, meta, {"expected_row_sorts": 0,
                            "collective_free": True,
                            "expected_table_gathers": depth * 1 + 1}


ARMS: dict[str, Arm] = {
    "levelwise_wired": Arm(
        "levelwise_wired",
        "root-anchored layout levelwise grower (r10 wired path), sharded",
        _arm_levelwise_wired),
    "levelwise_legacy": Arm(
        "levelwise_legacy",
        "plan-based levelwise comparison arm (deep_layout='legacy')",
        _arm_levelwise_legacy),
    "leafwise_wired": Arm(
        "leafwise_wired",
        "layout-wired batched leaf-wise expansion + selection, sharded",
        _arm_leafwise_wired),
    "levelwise_feature": Arm(
        "levelwise_feature",
        "feature-parallel reduction arm: reduce-scatter + combine "
        "all-gather per level, root psum (hist_reduce='feature')",
        _arm_levelwise_feature),
    "leafwise_feature": Arm(
        "leafwise_feature",
        "feature-parallel batched leaf-wise expansion (reduce-scatter + "
        "combine all-gather per expansion level)",
        _arm_leafwise_feature),
    "goss_iteration": Arm(
        "goss_iteration",
        "GOSS boosting iteration: +1 global row sort over the psums",
        _arm_goss),
    "renewal_iteration": Arm(
        "renewal_iteration",
        "L1 leaf renewal: +1 global (leaf, residual) row sort per tree",
        _arm_renewal),
    "multiclass_shared_roots": Arm(
        "multiclass_shared_roots",
        "K=3 shared-plan roots (XLA backend): one fused root psum for all K",
        _arm_multiclass_shared_roots),
    "sharded_predict": Arm(
        "sharded_predict",
        "shard_map predict: zero collectives (per-row traversal)",
        _arm_sharded_predict),
    "packed_predict": Arm(
        "packed_predict",
        "shard_map packed node-word predict: one table gather per level",
        _arm_packed_predict),
    "train_eval": Arm(
        "train_eval",
        "training eval of a fresh tree: device-side node-word packing + "
        "the packed walk, one table gather per level",
        _arm_train_eval),
}


# ---------------------------------------------------------------------------
# runner

@dataclass
class ArmReport:
    name: str
    digest: str
    census: Census
    expected_psums: int
    failures: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.failures

    def payload(self) -> dict:
        return {
            "digest": self.digest,
            "collectives": dict(sorted(self.census.collectives.items())),
            "global_row_sorts": self.census.global_row_sorts,
            "local_row_sorts": self.census.local_row_sorts,
            "row_gathers": self.census.row_gathers,
            "table_gathers": self.census.table_gathers,
            "score_row_gathers": self.census.score_row_gathers,
            "pallas_kernels": {k: sorted(v) for k, v in
                               sorted(self.census.pallas_kernels.items())},
        }


@dataclass
class AuditReport:
    arms: list = field(default_factory=list)
    drift: list = field(default_factory=list)   # digest/golden mismatches

    @property
    def ok(self) -> bool:
        return all(a.ok for a in self.arms)

    @property
    def drift_ok(self) -> bool:
        return not self.drift

    def summary(self) -> str:
        bad = [a.name for a in self.arms if not a.ok]
        s = (f"jaxpr audit: {len(self.arms)} arm(s), "
             f"{sum(len(a.failures) for a in self.arms)} invariant "
             f"failure(s), {len(self.drift)} digest drift(s)")
        if bad:
            s += f" [failed: {', '.join(bad)}]"
        return s


def trace_arm(name: str) -> ArmReport:
    import jax

    built = ARMS[name].build()
    fn, args, meta, expect = built
    closed = jax.make_jaxpr(fn)(*args)
    census = census_jaxpr(closed, meta["rows_threshold"])
    digest = canonical_digest(closed)
    rep = ArmReport(name, digest, census, meta["expected_psums"])

    psums = census.collectives.get("psum", 0)
    comm = meta.get("comm") or {}
    rs = census.collectives.get("reduce_scatter", 0)
    ag = census.collectives.get("all_gather", 0)
    exp_rs = comm.get("reduce_scatter_calls_per_iter", 0)
    exp_ag = comm.get("all_gather_calls_per_iter", 0)
    allowed = {"psum", "reduce_scatter", "all_gather"}
    if comm.get("hist_reduce") == "feature":
        # the feature arm derives each shard's owned slice/offset from
        # axis_index — communication-free, not a payload
        allowed.add("axis_index")
    others = {k: v for k, v in census.collectives.items()
              if k not in allowed}
    if census.dynamic_loop:
        rep.failures.append(
            "collective/sort inside a dynamic-trip while loop — census "
            "cannot weight it; use static fori bounds")
    if census.branch_mismatch:
        rep.failures.append(
            "cond branches disagree on collective counts — the same-program "
            "rule requires every branch to run the same collective plan")
    if psums != meta["expected_psums"]:
        rep.failures.append(
            f"psum census {psums} != _comm_stats accounting "
            f"{meta['expected_psums']} (comm={meta.get('comm')}) — the "
            "traced program and the observability accounting drifted")
    if (rs, ag) != (exp_rs, exp_ag):
        rep.failures.append(
            f"reduce_scatter/all_gather census ({rs}, {ag}) != _comm_stats "
            f"accounting ({exp_rs}, {exp_ag}) (comm={comm}) — only the "
            "feature arm's per-level reduce-scatter + combine all-gather "
            "may appear, and in exactly the accounted counts")
    if expect.get("collective_free") and census.collectives:
        rep.failures.append(
            f"collectives {dict(census.collectives)} in a collective-free "
            "arm — sharded predict must stay per-row")
    if not expect.get("collective_free") and others:
        rep.failures.append(
            f"unexpected collectives {others} — the per-arm histogram "
            "reduction (fused psum, or feature-arm reduce-scatter + "
            "combine all-gather) + documented global sorts are the "
            "growers' ONLY collectives")
    if "expected_row_sorts" in expect \
            and census.global_row_sorts != expect["expected_row_sorts"]:
        rep.failures.append(
            f"global row-scale sorts {census.global_row_sorts} != expected "
            f"{expect['expected_row_sorts']} (threshold "
            f"{meta['rows_threshold']} rows) — only GOSS (+1/iter) and L1 "
            "renewal (+1/tree) may sort the global rows")
    if "expected_table_gathers" in expect \
            and census.table_gathers != expect["expected_table_gathers"]:
        rep.failures.append(
            f"small-table gathers {census.table_gathers} != expected "
            f"{expect['expected_table_gathers']} — the predict traversal's "
            "per-level lookup budget drifted (packed arm: exactly 1 "
            "node-word gather/level; gather cost is per-ACCESS, so every "
            "extra lookup is a real per-level cost)")
    exp_score = meta.get("expected_score_gathers", 0)
    if (census.score_row_gathers, census.score_flat_gathers) != (exp_score, 0):
        rep.failures.append(
            f"scope dryad.score holds {census.score_row_gathers} row-sized "
            f"gather(s), {census.score_flat_gathers} of them from a table "
            f"with no two-word minor dimension; expected {exp_score} and 0 "
            "— the score update looks each row up once, in the composed "
            "(keys, 2) record table (train._row_records); a 1-D table "
            "gather costs four times as much an index on the chip")
    if expect.get("wired") and census.local_row_sorts:
        rep.failures.append(
            f"{census.local_row_sorts} row-scale sort(s) inside the wired "
            "grower program — nothing on the wired path sorts rows (r10)")
    rep.failures.extend(kernel_dtype_violations(census))
    return rep


def run_audit(arm_names=None, goldens_path: Optional[str] = None,
              update_goldens: bool = False) -> AuditReport:
    report = AuditReport()
    names = list(arm_names or ARMS)
    payloads = {}
    for name in names:
        rep = trace_arm(name)
        report.arms.append(rep)
        payloads[name] = rep.payload()

    goldens_path = goldens_path or GOLDENS_PATH
    if update_goldens:
        import jax

        if not report.ok:
            # never pin a program that fails its own invariants: the next
            # (fixed) trace would "drift" against a known-bad baseline
            report.drift.append(
                "refusing to write goldens: arm invariant failures above "
                "must be fixed first (a golden must pin a sound program)")
            return report
        # merge into the existing store: refreshing a SUBSET of arms
        # (--arm X --update-goldens) must not delete the other arms'
        # committed pins — that would force a full re-baseline and wash
        # out exactly the unreviewed-drift signal the goldens exist for
        merged = load_goldens(goldens_path).get("arms", {})
        merged.update(payloads)
        save_goldens({"jax_version": jax.__version__,
                      "n_shards": N_SHARDS, "arms": merged}, goldens_path)
        return report

    goldens = load_goldens(goldens_path)
    stored = goldens.get("arms", {})
    import jax

    env = {"jax_version": jax.__version__, "n_shards": N_SHARDS}
    pinned = {k: goldens.get(k) for k in env}
    if goldens and pinned != env:
        # an environment change legitimately re-lowers every program —
        # say so instead of blaming 7 arms of phantom fusion drift
        report.drift.append(
            f"goldens were pinned under {pinned}, this environment is "
            f"{env} — re-baseline with --update-goldens (not a code "
            "regression)")
        return report
    for name in names:
        if name not in stored:
            report.drift.append(
                f"{name}: no committed golden — run --update-goldens and "
                "commit the diff")
            continue
        for key in ("digest", "collectives", "global_row_sorts",
                    "local_row_sorts", "row_gathers", "table_gathers",
                    "score_row_gathers", "pallas_kernels"):
            if stored[name].get(key) != payloads[name][key]:
                report.drift.append(
                    f"{name}: {key} drifted from golden "
                    f"({stored[name].get(key)!r} -> {payloads[name][key]!r})"
                    " — if intentional, re-run with --update-goldens and "
                    "commit; if not, the program changed under you "
                    "(fusion-shape / argmax-flip class)")
    return report
