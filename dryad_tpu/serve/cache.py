"""Shape-bucketed compiled-predict cache, single-device AND sharded.

jit specializes on array shapes, so every distinct request size would
compile.  Instead,
batches are padded up to the next power-of-two row bucket and predicted
at the bucket shape; warm traffic then touches a small fixed set of
programs — at most log2(max_bucket / min_bucket) + 1 per model version
and shard arm — and never recompiles.  Batches larger than
``max_bucket`` are predicted in ``max_bucket``-row chunks.

Entries come in two families keyed by (version, bucket, n_shards):

* ``n_shards == 1`` — the single-device jitted accumulate (fast path for
  small interactive batches).
* ``n_shards == mesh size`` — ``engine.predict.sharded_accumulate_fn``:
  the padded row bucket sharded over the mesh, trees replicated, no
  collectives; one implicit gather at the result edge when the host
  fetches.  Routing is deterministic per bucket (``bucket × num_outputs
  >= sharded_threshold``), so warming every bucket warms exactly the arm
  that bucket will use forever — warm traffic stays recompile-free
  across BOTH families.

The dispatch pipeline (batcher.py) needs host work separated from device
work, so prediction is split: ``prepare_raw`` does the host-side
chunk/bucket/pad and entry resolution, ``execute_raw`` runs the compiled
programs and performs the ONE real host fetch per chunk (np.asarray on
the raw result, which the caller needs on the host anyway).
``predict_raw`` composes the two for serial callers.

Bitwise contract: padding rows (bin 0 everywhere), chunking, and row
sharding cannot change the real rows' scores.  Tree traversal and fp32
leaf accumulation are strictly per-row (one scan carry element per row,
no cross-row reduction anywhere in predict), so a padded or sharded
program computes exactly the same per-row arithmetic as an unpadded
single-device one — the parity is structural, not approximate, and
tests/test_serve.py + tests/test_serve_sharded.py pin it.

Compiled callables never close over device arrays: they re-resolve
``entry.device_state()`` per call, so a registry eviction actually frees
the buffers and a re-staged model is picked up transparently with no
recompile (jit caches on shape, not array identity).

The cache also serves the no-device fallback: with ``backend='cpu'`` the
per-bucket entry wraps the canonical numpy predict instead of a jitted
program.  Bucketing is kept there too so batching behavior, metrics, and
the warmup discipline are identical on both backends.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np


def bucket_rows(n: int, min_bucket: int = 8,
                max_bucket: Optional[int] = None) -> int:
    """Smallest power of two >= n, floored at min_bucket, capped at
    max_bucket (itself rounded up to a power of two by the server)."""
    if n < 1:
        raise ValueError("bucket_rows needs n >= 1")
    b = max(int(min_bucket), 1 << (int(n) - 1).bit_length())
    if max_bucket is not None:
        b = min(b, int(max_bucket))
    return b


class PreparedPredict:
    """Host-side-ready predict work: padded chunks + their resolved
    compiled callables.  Built by ``prepare_raw`` (pipeline stage A),
    consumed by ``execute_raw`` (stage B)."""

    __slots__ = ("entry", "n", "chunks")

    def __init__(self, entry, n: int, chunks: list):
        self.entry = entry
        self.n = n
        self.chunks = chunks    # [(fn, padded_chunk, start, m), ...]


class CompiledPredictCache:
    """(version, bucket, n_shards) → prepared predict callable, with
    hit/compile accounting.  ``backend`` is 'jax' (device-resident jitted
    accumulate, optionally sharded over ``mesh``) or 'cpu' (canonical
    numpy predict)."""

    GUARDED_BY = {"_fns": "_lock", "_warm": "_lock"}

    def __init__(self, backend: str = "cpu", metrics=None, *,
                 min_bucket: int = 8, max_bucket: int = 4096,
                 mesh=None, sharded_threshold: Optional[int] = None):
        if backend not in ("jax", "cpu"):
            raise ValueError(f"unknown cache backend {backend!r}")
        self.backend = backend
        self.metrics = metrics
        self.min_bucket = int(min_bucket)
        # cap must be a power of two so chunk remainders re-bucket cleanly
        self.max_bucket = 1 << (int(max_bucket) - 1).bit_length()
        # sharding: None threshold disables the sharded family entirely
        self.mesh = mesh if backend == "jax" else None
        self.n_shards = (int(np.prod(mesh.devices.shape))
                         if self.mesh is not None else 1)
        self.sharded_threshold = (None if sharded_threshold is None
                                  else int(sharded_threshold))
        # one prepared callable per (version, n_shards) — the callable is
        # shape-agnostic; on the jax path the per-shape specialization
        # lives in jit's own cache — plus per-(version, bucket, n_shards)
        # warmth accounting: the first call at a bucket shape is what
        # triggers an XLA compile.  The lock covers _fns/_warm: the
        # collector thread inserts via _get while an admin thread may
        # purge via evict_version
        self._lock = threading.Lock()
        self._fns: dict[tuple, object] = {}
        self._warm: set[tuple] = set()
        # recompile tripwire (r12, obs/tripwire.py): a fresh cache
        # legitimately compiles during warmup; once ``warmup_complete()``
        # arms the family, any NEW (version, bucket, shards) key raises
        # ``dryad_recompile_unexpected_total`` and degrades /healthz —
        # the "zero recompiles after warmup" test assertion as a live
        # production alarm.  begin_program here resets the family for
        # this cache's generation (the jax-free obs side; host keys only).
        from dryad_tpu.obs.tripwire import default_tripwire

        self._tripwire = default_tripwire()
        self._tripwire.begin_program("serve.predict")

    @property
    def num_entries(self) -> int:
        """Warm (version, bucket, shards) keys — compiled shapes, not
        closures."""
        with self._lock:
            return len(self._warm)

    def warmup_complete(self) -> None:
        """Declare the expected-compile budget spent: every bucket this
        cache can produce has been touched (``buckets()`` is the warmup
        set and shard routing is deterministic per bucket), so any later
        cold key is an UNEXPECTED recompile — counter + degraded
        /healthz, not just a slow request.  Re-arming after a deploy (or
        a fired alarm) clears the standing degradation — re-warm +
        re-arm IS the recovery path."""
        self._tripwire.arm("serve.predict")

    def deploy_started(self) -> None:
        """Open a deploy window (a model load legitimately compiles new
        programs): disarm without forgetting warm keys; the caller warms
        the new version's buckets and calls ``warmup_complete()`` again."""
        self._tripwire.disarm("serve.predict")

    def buckets(self) -> list[int]:
        """Every bucket size this cache can ever produce — the warmup set.
        Routing to the shard arm is a pure function of the bucket, so
        touching each bucket once warms both families completely."""
        out, b = [], self.min_bucket
        while b <= self.max_bucket:
            out.append(b)
            b <<= 1
        return out

    def shards_for(self, bucket: int, num_outputs: int) -> int:
        """Deterministic shard-arm routing: the sharded family only when a
        mesh is attached, the bucket divides it, and the bucket carries
        enough row-outputs of work to beat the single-device dispatch."""
        if (self.mesh is None or self.sharded_threshold is None
                or self.n_shards <= 1):
            return 1
        if bucket % self.n_shards != 0:
            return 1
        return (self.n_shards
                if bucket * int(num_outputs) >= self.sharded_threshold else 1)

    # ---- prediction --------------------------------------------------------
    def prepare_raw(self, entry, Xb: np.ndarray) -> PreparedPredict:
        """HOST stage: chunk at max_bucket, bucket, zero-pad, and resolve
        each chunk's compiled callable (warmth accounting happens here).
        No device work — safe to overlap with an in-flight execute."""
        n = int(Xb.shape[0])
        chunks = []
        for start in range(0, n, self.max_bucket):
            chunk = Xb[start:start + self.max_bucket]
            m = int(chunk.shape[0])
            b = bucket_rows(m, self.min_bucket, self.max_bucket)
            fn = self._get(entry, b, self.shards_for(b, entry.num_outputs))
            if m < b:
                # concatenate already yields a fresh contiguous array; the
                # old ascontiguousarray pre-copy doubled the pad-path copy
                pad = np.zeros((b - m,) + chunk.shape[1:], chunk.dtype)
                chunk = np.concatenate([chunk, pad])
            chunks.append((fn, chunk, start, m))
        return PreparedPredict(entry, n, chunks)

    def execute_raw(self, prepared: PreparedPredict) -> np.ndarray:
        """DEVICE stage: run the compiled programs; the np.asarray inside
        each ``fn`` is the single real host fetch per chunk."""
        out = np.empty((prepared.n, prepared.entry.num_outputs), np.float32)
        for fn, chunk, start, m in prepared.chunks:
            out[start:start + m] = fn(chunk)[:m]
        return out

    def predict_raw(self, entry, Xb: np.ndarray) -> np.ndarray:
        """Raw scores (n, K) fp32 for pre-binned rows, through the bucketed
        compiled program; bitwise equal to the direct unpadded predict."""
        if int(Xb.shape[0]) == 0:
            return np.zeros((0, entry.num_outputs), np.float32)
        return self.execute_raw(self.prepare_raw(entry, Xb))

    # ---- entry construction ------------------------------------------------
    def _get(self, entry, bucket: int, n_shards: int):
        key = (entry.version, bucket, n_shards)
        with self._lock:
            hit = key in self._warm
            if not hit:
                self._warm.add(key)
            fkey = (entry.version, n_shards)
            fn = self._fns.get(fkey)
            if fn is None:
                # closure construction is cheap and pure (the compile
                # happens at first call, outside the lock)
                fn = (self._build_jax(entry, n_shards)
                      if self.backend == "jax" else self._build_cpu(entry))
                self._fns[fkey] = fn
        if not hit:
            # cold key = a compile boundary; after warmup_complete() a new
            # key here fires the recompile tripwire (exactly once per key)
            self._tripwire.note_compile(
                "serve.predict", key,
                detail=f"version={key[0]} bucket={key[1]} shards={key[2]}")
        if self.metrics is not None:
            self.metrics.record_cache(hit, entry.version)
        return fn

    def evict_version(self, version: int) -> None:
        """Drop a version's prepared callables + warmth keys (model
        unloaded): the closures hold the ModelEntry (and through it the
        booster) alive, so an unload without this purge would leak every
        co-served model ever retired.  (An in-flight _get racing this can
        re-insert one tiny closure for the dead version, but the entry is
        closed by then — its staged() raises, so nothing big gets pinned
        and the in-flight group fails like any unloaded-mid-queue group.)"""
        version = int(version)
        with self._lock:
            for key in [k for k in self._fns if k[0] == version]:
                del self._fns[key]
            self._warm -= {k for k in self._warm if k[0] == version}

    def _build_cpu(self, entry):
        from dryad_tpu.cpu.predict import predict_binned_cpu

        booster, num_iteration = entry.booster, entry.num_iteration

        def fn(Xp):
            return predict_binned_cpu(booster, Xp, num_iteration=num_iteration)

        return fn

    def _build_jax(self, entry, n_shards: int):
        import jax
        import jax.numpy as jnp

        from dryad_tpu.cpu.predict import rf_average
        from dryad_tpu.engine import introspect
        from dryad_tpu.engine.predict import _accumulate, sharded_accumulate_fn

        booster = entry.booster
        depth = max(booster.max_depth_seen, 1)
        is_rf = booster.params.boosting == "rf"
        mesh = self.mesh if n_shards > 1 else None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from dryad_tpu.engine.distributed import AXIS

            acc = sharded_accumulate_fn(mesh, depth)
            row_sharding = NamedSharding(mesh, P(AXIS, None))

        def fn(Xp):
            # device_state is re-resolved EVERY call so a registry
            # eviction's re-stage is picked up transparently — jit caches
            # on shape/dtype, not array identity, so this never recompiles
            trees_dev, init_dev = entry.device_state(mesh)
            # r21: the staged dict's keys carry the traversal layout —
            # packed node-word tables dispatch the packed program per
            # bucket with no cache-side branching, and a re-stage under a
            # different predict_layout retraces via the pytree structure
            # (the version in the key keeps introspection honest too)
            layout = "packed" if "node_word" in trees_dev else "legacy"
            # compile-boundary introspection (memoized per shape; the
            # cache-level _get already notes the tripwire key, so the
            # capture only records dryad_prog_* cost series)
            if mesh is not None:
                Xd = jax.device_put(Xp, row_sharding)
                introspect.capture(
                    "serve.predict",
                    (entry.version, Xp.shape, n_shards, depth,
                     trees_dev["value"].shape, layout),
                    acc, trees_dev, Xd, init_dev, note_tripwire=False,
                    labels={"bucket": Xp.shape[0], "shards": n_shards,
                            "layout": layout})
                raw = np.asarray(acc(trees_dev, Xd, init_dev))
            else:
                Xj = jnp.asarray(Xp)
                introspect.capture(
                    "serve.predict",
                    (entry.version, Xp.shape, 1, depth,
                     trees_dev["value"].shape, layout),
                    _accumulate, trees_dev, Xj, init_dev, depth,
                    note_tripwire=False,
                    labels={"bucket": Xp.shape[0], "shards": 1,
                            "layout": layout})
                raw = np.asarray(_accumulate(trees_dev, Xj, init_dev,
                                             depth))
            if is_rf:
                _, _, n_iter = entry.staged()
                if n_iter > 0:
                    raw = rf_average(raw, booster.init_score, n_iter)
            return raw

        return fn
