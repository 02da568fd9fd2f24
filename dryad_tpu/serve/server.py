"""PredictServer: the long-lived online-inference front object.

Composes the registry (versioned, named, hot-swappable, device-resident
models under an LRU memory budget), the shape-bucketed compiled-predict
cache (single-device + sharded entry families), and the micro-batching
queue's overlapped dispatch pipeline behind one thread-safe ``predict``
call, with a ``stats()`` snapshot for observability.  ``python -m
dryad_tpu serve`` wraps this in an HTTP front end (serve/http.py).

Backend resolution ('auto') takes the device path when jax initialised
with an accelerator and the canonical numpy predict when it initialised
with CPU devices only; a device initialisation that raises is never
turned into a CPU server.  The serving semantics (bucketing, batching,
metrics, bitwise parity with ``Booster.predict``) are identical on both
paths, and ``stats()["devices"]`` names the devices the jit path uses.

Sharded predict: on the device path with a multi-device mesh, buckets
whose rows × outputs clear ``sharded_threshold`` run under ``shard_map``
with rows split over the mesh (``sharded='auto'``; ``True`` forces every
bucket onto the mesh, ``False`` disables it).  Small interactive batches
stay on the single-device fast path either way.

The dispatch pipeline splits each coalesced batch into ``_prepare``
(host: group by version, concatenate, bucket-pad, resolve compiled
entries) and ``_execute`` (device: run programs + the one real host
fetch, then per-request slice/transform) so batch i+1's host work
overlaps batch i's device work (batcher.py; ``pipeline_depth=1`` forces
the old strictly serial loop, kept as the bench comparison arm).
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

from dryad_tpu.serve.batcher import MicroBatcher, Request, RequestTrace
from dryad_tpu.serve.cache import CompiledPredictCache
from dryad_tpu.serve.metrics import ServeMetrics
from dryad_tpu.serve.registry import ModelRegistry


_DRIFT_UNSET = object()      # "not probed yet" marker in the monitor table


def _resolve_backend(backend: str) -> str:
    """'auto'|'tpu'|'cpu' → 'jax' (device predict) or 'cpu' (numpy).

    'tpu' runs the jit path on whatever platform jax initializes (the
    test mesh is 8 virtual CPU devices); 'auto' takes the jit path only
    when a real accelerator is attached.  A device initialisation that
    RAISES (a chip held by another process, a broken libtpu) propagates
    for both: a replica that cannot reach its device must fail its
    start-up, not serve from the host under a device backend's name.
    """
    if backend == "cpu":
        return "cpu"
    if backend not in ("auto", "tpu"):
        raise ValueError(f"unknown backend {backend!r}")
    import jax

    devices = jax.devices()
    if backend == "tpu":
        return "jax"
    return "jax" if any(d.platform != "cpu" for d in devices) else "cpu"


class _PreparedGroup:
    """One model-version group of a prepared batch (see _prepare).
    ``drift`` is the version's DriftMonitor (or None): _prepare observed
    the binned features into it and _execute observes the raw scores —
    the handoff queue's happens-before makes the plain field safe."""

    __slots__ = ("idxs", "entry", "prepared", "row_counts", "raw_flags",
                 "error", "drift")

    def __init__(self, idxs, entry=None, prepared=None, row_counts=None,
                 raw_flags=None, error=None, drift=None):
        self.idxs = idxs
        self.entry = entry
        self.prepared = prepared
        self.row_counts = row_counts
        self.raw_flags = raw_flags
        self.error = error
        self.drift = drift


class PredictServer:
    def __init__(self, registry: Optional[ModelRegistry] = None, *,
                 backend: str = "auto", max_batch_rows: int = 4096,
                 max_wait_ms: float = 2.0, queue_size: int = 256,
                 min_bucket: int = 8, latency_window: int = 4096,
                 pipeline_depth: int = 2, sharded="auto",
                 sharded_threshold: Optional[int] = None,
                 device_budget_bytes: Optional[int] = None,
                 drift="auto", drift_window: int = 8192):
        self.backend = _resolve_backend(backend)
        self.metrics = ServeMetrics(latency_window=latency_window)
        # drift monitors (obs/drift.py) are per model version, created
        # lazily at first dispatch for versions whose artifact carries a
        # reference profile.  The zero-cost contract: with the obs
        # registry disabled at construction (DRYAD_OBS=0) — or with
        # drift off — the table stays None and the request path never
        # allocates drift state (one attr check per batch, pinned by
        # tracemalloc in tests/test_drift.py).
        self.drift_window = int(drift_window)
        drift_on = (drift not in (False, 0, "off", "none")
                    and self.drift_window > 0 and self.metrics.obs_enabled)
        self._drift_monitors: Optional[dict] = {} if drift_on else None
        if registry is not None:
            self.registry = registry
            # a caller-supplied registry still honors this server's budget
            # unless it already carries its own
            if (device_budget_bytes is not None
                    and self.registry.budget_bytes is None):
                self.registry.budget_bytes = int(device_budget_bytes)
        else:
            self.registry = ModelRegistry(budget_bytes=device_budget_bytes)
        if self.registry.metrics is None:
            self.registry.metrics = self.metrics
        self.mesh = self._make_mesh(sharded)
        if sharded_threshold is None:
            # the default is the predict_sharded gate's work floor
            # (policy/gates.py says why 32k row-outputs)
            from dryad_tpu.policy.gates import gate_value

            sharded_threshold = int(gate_value("predict_sharded",
                                               "min_work"))
        # threshold in rows × outputs; sharded=True forces the mesh arm for
        # every bucket, False (or a 1-device mesh) disables it entirely.
        # NOTE the interplay with max_batch_rows: buckets cap there, so at
        # the default 4096-row cap 'auto' (32k row-outputs) shards only
        # wide-output models (K >= 8) — by design: sharding a 4096-row
        # binary dispatch is dispatch-bound and loses to the single-device
        # program.  Giant-batch bulk scoring should raise max_batch_rows
        # (or force sharded=True), which is what unlocks the mesh for K=1.
        threshold = (None if self.mesh is None
                     else 0 if sharded is True else int(sharded_threshold))
        self.cache = CompiledPredictCache(
            self.backend, self.metrics,
            min_bucket=min_bucket, max_bucket=max_batch_rows,
            mesh=self.mesh, sharded_threshold=threshold)
        self.batcher = MicroBatcher(
            self._dispatch, prepare=self._prepare, execute=self._execute,
            pipeline_depth=pipeline_depth, max_batch_rows=max_batch_rows,
            max_wait_ms=max_wait_ms, queue_size=queue_size,
            metrics=self.metrics)

    def _make_mesh(self, sharded):
        if self.backend != "jax" or sharded is False:
            return None
        import jax

        devices = jax.devices()
        if len(devices) < 2:
            return None
        from dryad_tpu.engine.distributed import make_mesh

        return make_mesh(devices)

    def _devices(self) -> Optional[dict]:
        """Where the jit path runs: platform, device_kind, the ids of the
        mesh's devices (or the one default device) and the chips this
        process was shown — None on the numpy backend, which touches no
        jax device.  A process pinned to one chip numbers it 0 whichever
        chip it is, so ``visible_chips`` (libtpu's ``TPU_VISIBLE_CHIPS``
        as the fleet set it, None when unpinned) is what tells a fleet's
        replicas apart."""
        if self.backend != "jax":
            return None
        import jax

        devs = (list(self.mesh.devices.flat) if self.mesh is not None
                else jax.devices()[:1])
        return {"platform": devs[0].platform,
                "device_kind": devs[0].device_kind,
                "ids": [int(d.id) for d in devs],
                "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS")}

    # ---- lifecycle ---------------------------------------------------------
    def start(self) -> "PredictServer":
        self.batcher.start()
        return self

    def stop(self) -> None:
        self.batcher.stop()

    def __enter__(self) -> "PredictServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---- model lifecycle (thin registry passthroughs) ----------------------
    def load_model(self, path: str, *, activate: bool = True,
                   num_iteration: Optional[int] = None,
                   name: Optional[str] = None) -> int:
        # a deploy legitimately compiles the new version's buckets: open
        # the tripwire's deploy window (no-op when never armed) so a
        # routine model load can't latch /healthz at 503 — warm the new
        # version (``warmup``) and re-arm (``warmup_complete``) to close it
        self.cache.deploy_started()
        return self.registry.load(path, activate=activate,
                                  num_iteration=num_iteration, name=name)

    def activate(self, version: int) -> None:
        self.registry.activate(version)

    def rollback(self) -> int:
        return self.registry.rollback()

    def unload(self, version: int) -> None:
        """Unload a version AND purge its compiled-cache closures — the
        registry alone cannot free those (they hold the entry alive)."""
        self.registry.unload(version)
        self.cache.evict_version(version)

    def warmup(self, versions=None) -> int:
        """Structural warmup through the real compiled-predict path: one
        zero-binned batch per (version, bucket) — ``cache.buckets()`` is
        the complete reachable set and shard routing is deterministic per
        bucket, so this compiles every program warm traffic can ever hit
        — then arm the recompile tripwire (``warmup_complete``).  This is
        the PRODUCTION arming path: the serve CLI runs it with
        ``--warmup``; serve/bench.py does the equivalent with real
        feature batches.  Returns the number of (version, bucket) pairs
        touched."""
        if versions is None:
            versions = self.registry.versions()
        touched = 0
        for version in versions:
            entry = self.registry.get(version)
            mapper = entry.booster.mapper
            for b in self.cache.buckets():
                Xb = np.zeros((b, mapper.num_features), mapper.bin_dtype)
                self.cache.predict_raw(entry, Xb)
                touched += 1
        self.warmup_complete()
        return touched

    def warmup_complete(self) -> None:
        """Arm the recompile tripwire (obs/tripwire.py): the caller has
        touched every bucket it intends to serve warm, so any later cold
        compiled-entry key increments
        ``dryad_recompile_unexpected_total{program="serve.predict"}`` and
        degrades ``/healthz`` — the live form of the "zero recompiles
        after warmup" invariant.  ``warmup()`` / serve/bench.py call this
        after their structural warmups; re-arming after a deploy clears
        the standing degradation (the recovery path)."""
        self.cache.warmup_complete()

    # ---- request path ------------------------------------------------------
    def predict(self, X: np.ndarray, *, version: Optional[int] = None,
                model: Optional[str] = None, raw_score: bool = False,
                binned: bool = False,
                timeout: Optional[float] = None,
                trace: Optional[str] = None,
                priority: Optional[str] = None) -> np.ndarray:
        """Predict through the full serving stack (bin → bucket → batch →
        compiled predict → link transform); bitwise equal to the direct
        ``Booster.predict`` / ``predict_binned`` on the same rows.
        Routing: ``version`` pins an exact version, ``model`` routes by
        registry name; default is the active version.  ``trace`` is the
        propagated request trace id (``X-Dryad-Trace`` — the HTTP front
        end passes it through) and ``priority`` the admission class; both
        feed the per-(priority, stage) latency series and the span ring,
        and cost nothing when obs is disabled (no context is allocated)."""
        self.start()
        # pin the version at submit time (a name is resolved here too, so
        # a mid-queue re-deploy under the same name can't switch models)
        entry = self.registry.get(version, name=model)
        X = np.asarray(X)
        if X.ndim == 1:
            X = X[None, :]
        if binned:
            Xb = np.ascontiguousarray(X)
        else:
            # binning is DEFERRED to _prepare: it rides the dispatch
            # pipeline's host stage, overlapped with the in-flight device
            # predict (dtype is coerced here so _prepare can concatenate
            # requests without widening surprises)
            Xb = np.ascontiguousarray(np.asarray(X, np.float32))
        # validate the feature width HERE, in the caller's thread: binning
        # is deferred into the coalesced _prepare, and without this check
        # one malformed request would poison every co-batched request of
        # the same version (raw width is the BASE mapper's for bundled
        # mappers — transform folds it down to num_features)
        mapper = entry.booster.mapper
        nf = (mapper.num_features if binned
              else getattr(mapper, "base", mapper).num_features)
        if Xb.ndim != 2 or Xb.shape[1] != nf:
            raise ValueError(
                f"request shape {Xb.shape} does not match model version "
                f"{entry.version}: expected (n, {nf}) "
                f"{'binned' if binned else 'raw'} features")
        if Xb.shape[0] == 0:
            # empty request: no dispatch, same output shape/dtype contract
            t0 = time.perf_counter()
            raw = np.zeros((0, entry.num_outputs), np.float32)
            out = entry.booster.transform_raw(raw, raw_score=raw_score)
            self.metrics.record_request(0, time.perf_counter() - t0,
                                        entry.version)
            return out
        # trace context only when obs records — the zero-cost contract:
        # with the registry disabled the request path allocates nothing
        # beyond the Request it always built
        tctx = (RequestTrace(trace, priority or "interactive")
                if self.metrics.obs_enabled else None)
        req = Request(Xb, version=entry.version, raw_score=raw_score,
                      binned=binned, priority=priority or "interactive",
                      tctx=tctx)
        return self.batcher.submit(req, timeout=timeout)

    # ---- dispatch (serial) / prepare + execute (pipeline) ------------------
    def _prepare(self, batch: list[Request]) -> list[_PreparedGroup]:
        """HOST stage: group the coalesced batch by (model version, binned)
        — a hot-swap mid-queue may interleave versions — concatenate each
        group's rows, BIN the raw-feature groups through the model's
        frozen mapper, and run the cache's host-side bucket/pad + entry
        resolution.  Binning is per-row, so batching it here is bitwise
        equal to per-request binning.  A dead group (e.g. its version was
        unloaded mid-queue) carries its error instead of poisoning the
        batch."""
        groups: dict[tuple, list[int]] = {}
        for i, req in enumerate(batch):
            groups.setdefault((req.version, req.binned), []).append(i)
        out = []
        for (version, binned), idxs in groups.items():
            try:
                entry = self.registry.get(version)
                if len(idxs) == 1:
                    X = batch[idxs[0]].rows
                else:
                    X = np.concatenate([batch[i].rows for i in idxs], axis=0)
                if not binned:
                    X = entry.booster.mapper.transform(X)
                # drift accounting on the already-binned batch: the
                # monitor counts the SAME bin ids the compiled predict is
                # about to consume, so covariate drift is measured in the
                # model's own split space (zero extra binning work)
                mon = None
                if self._drift_monitors is not None:
                    mon = self._drift_monitor(entry)
                    if mon is not None:
                        mon.observe_features(X)
                out.append(_PreparedGroup(
                    idxs, entry, self.cache.prepare_raw(entry, X),
                    [batch[i].rows.shape[0] for i in idxs],
                    [batch[i].raw_score for i in idxs], drift=mon))
            except Exception as e:  # noqa: BLE001 — fail only this group
                out.append(_PreparedGroup(idxs, error=e))
        return out

    def _execute(self, prepared: list[_PreparedGroup]) -> list:
        """DEVICE stage: run each group's compiled programs (one real host
        fetch per chunk inside the cache), then slice + link-transform per
        request.  Per-row arithmetic makes the slicing bitwise-exact."""
        n = 1 + max(i for g in prepared for i in g.idxs)
        results: list = [None] * n
        for g in prepared:
            if g.error is not None:
                for i in g.idxs:
                    results[i] = g.error
                continue
            try:
                raw = self.cache.execute_raw(g.prepared)
                if g.drift is not None:
                    # score-shift accounting on the raw margins the one
                    # real host fetch just delivered (pre-link: the raw
                    # score space is objective-invariant and matches the
                    # profile's train/valid histograms)
                    g.drift.observe_scores(raw)
                offset = 0
                for i, rows, raw_flag in zip(g.idxs, g.row_counts,
                                             g.raw_flags):
                    results[i] = g.entry.booster.transform_raw(
                        raw[offset:offset + rows], raw_score=raw_flag)
                    offset += rows
            except Exception as e:  # noqa: BLE001 — fail only this group
                for i in g.idxs:
                    results[i] = e
        return results

    def _dispatch(self, batch: list[Request]) -> list:
        """Serial-mode dispatch: the pipeline stages composed in-line."""
        return self._execute(self._prepare(batch))

    # ---- drift monitors (obs/drift.py) -------------------------------------
    def _drift_monitor(self, entry):
        """The version's monitor, created on first dispatch when the
        model carries a reference profile (None cached otherwise, so a
        profile-less model costs one dict probe per batch).  Runs on the
        collector thread only; _execute reads the group's stashed handle
        after the handoff (happens-before via the pipeline queue)."""
        table = self._drift_monitors
        mon = table.get(entry.version, _DRIFT_UNSET)
        if mon is _DRIFT_UNSET:
            profile = getattr(entry.booster, "profile", None)
            if profile is None:
                mon = None
            else:
                from dryad_tpu.obs.drift import DriftMonitor

                # the model label prefers the registry alias (operators
                # name models, not versions); the version pins it apart
                # from a re-push under the same name
                names = [n for n, v in self.registry.aliases().items()
                         if v == entry.version]
                label = names[0] if names else f"v{entry.version}"
                mon = DriftMonitor(
                    profile.feature_counts,
                    ref_score_state=profile.score_hist.get("train"),
                    model=label, window_rows=self.drift_window,
                    registry=self.metrics.obs_registry)
            table[entry.version] = mon
        return mon

    def drift_state(self) -> dict:
        """Raw drift blocks by model label — the replica's ``/obs``
        section the fleet router count-merges exactly."""
        if not self._drift_monitors:
            return {}
        out = {}
        # snapshot the table in one C-level copy: the collector thread
        # inserts new versions' monitors concurrently, and iterating the
        # live view would raise "dict changed size during iteration"
        # under a mid-deploy scrape
        for mon in list(self._drift_monitors.values()):
            if mon is not None:
                block = mon.export_state()
                out[block["model"]] = block
        return out

    def drift_report(self, budget_psi: Optional[float] = None) -> dict:
        """Local PSI verdicts by model label (also refreshes the
        ``dryad_drift_*`` gauges)."""
        if not self._drift_monitors:
            return {}
        return {mon.model: mon.snapshot(budget_psi)
                for mon in list(self._drift_monitors.values())
                if mon is not None}

    # ---- observability -----------------------------------------------------
    def stats(self) -> dict:
        snap = self.metrics.snapshot()
        snap["backend"] = self.backend
        snap["devices"] = self._devices()
        snap["active_version"] = self.registry.active_version
        snap["versions"] = self.registry.versions()
        snap["aliases"] = self.registry.aliases()
        snap["compiled_buckets"] = self.cache.num_entries
        snap["pipeline_depth"] = (self.batcher.pipeline_depth
                                  if self.batcher.pipelined else 1)
        snap["mesh_shards"] = self.cache.n_shards
        snap["sharded_threshold"] = self.cache.sharded_threshold
        snap["memory"] = self.registry.memory()
        from dryad_tpu.policy.gates import stats_block

        # device kind + newest decision per gate (incl. the
        # predict_layout fallback reason when a model serves legacy)
        snap["policy"] = stats_block()
        drift = self.drift_report()
        if drift:
            snap["drift"] = {
                model: {"rows": r["rows"], "psi_max": r["psi_max"],
                        "score_psi": r["score_psi"]}
                for model, r in drift.items()}
        return snap
