"""The quickest proof that dryad-tpu still starts on the chip.

One process drives the system's main path once, through the entry points a
user calls, at the full width of the headline model (``BASELINE.json:2``:
Higgs-shaped, 28 features, 256 bins, binary, depth-wise, depth 8, 255
leaves); rows are the batch, depth in trees is cut to a few iterations and
the data is synthetic, made from a seed:

1. ``parity`` — device vs CPU-reference tree structures and same-booster
   predict bits on the tie-free fixture ``higgs_like(20_000, seed=31)``,
   6 trees, 64 bins.
2. ``train`` — ``dryad.train(..., backend="tpu", valid_sets=[...],
   checkpoint_dir=...)`` at full width: the chunked device loop crosses
   at least two chunks and a checkpoint boundary, the valid metric of the
   last iteration beats the first, the run's ``train_state`` names the
   device, and one lowered iteration of the same shape carries compiled
   Mosaic kernels (Pallas neither interpreted nor replaced by XLA).
3. ``predict`` — ``dryad.predict(backend="tpu")`` bitwise equal to
   ``backend="cpu"`` on the same booster.
4. ``serve`` — a ``PredictServer(backend="tpu")`` behind
   ``serve.http.make_http_server`` in this process answers ``/predict``
   requests of mixed sizes bitwise equal to ``Booster.predict``, and
   ``/stats`` names the device it runs on.
5. ``multichip`` — with >= 4 devices: the same config through
   ``dryad.train(..., mesh=make_mesh(devices))``, rows really split,
   4-device structures equal to 1-device on the fixture, sharded predict
   bitwise.  Otherwise reported as ``"1 device"``.

Contract: exits non-zero and prints no result unless
``jax.devices()[0].platform == "tpu"`` (and wherever the ``dryad_tpu``
package is not beside it); any failed phase makes the exit code non-zero;
the last line of stdout is one JSON object with exactly these keys,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
(the line before it, ``summary: {...}``, carries everything else).  It
starts no process that needs the chip (a chip has one owner) and needs no
network.
Numbers printed here are a smoke's facts, not benchmark results.

``--rehearse-cpu`` runs the same phases tiny on a CPU-only jax, to debug
the script before spending a chip call; it is labelled as a rehearsal and
never reports ``"ok": true``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import traceback

import numpy as np

# the headline model (BASELINE.json:2, bench.py, scripts/headline_10m.py)
PARAMS = dict(objective="binary", num_leaves=255, max_depth=8, max_bins=256,
              learning_rate=0.1, growth="depthwise", seed=11)
ITERATIONS = 8
CHECKPOINT_EVERY = 4        # chunks end on checkpoint boundaries: 2 chunks
ROWS, VALID_ROWS = 10_000_000, 1_000_000
REHEARSAL_ROWS, REHEARSAL_VALID_ROWS = 20_000, 4_000
REQUEST_SIZES = (1, 7, 100, 1000, 5000)   # 5000 > the 4096-row bucket cap


class CompileClock:
    """Set-up seconds as jax itself reports them (``jax.monitoring``):
    trace + lowering + backend compile + persistent-cache retrieval, and
    the cache's hit/miss counts.  ``since(mark)`` gives the share of a
    phase, so its run seconds are its wall minus this."""

    _DURATIONS = ("/jax/core/compile/",
                  "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        from jax import monitoring

        self.setup_s = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name.startswith(self._DURATIONS):
            self.setup_s += secs
        if name.endswith("backend_compile_duration"):
            self.backend_compiles += 1

    def _event(self, name, **kw):
        if name.endswith("/cache_hits"):
            self.cache_hits += 1
        elif name.endswith("/cache_misses"):
            self.cache_misses += 1

    def since(self, mark: float) -> float:
        return self.setup_s - mark


def result_line(ok: bool, devices) -> str:
    """The contract's last stdout line: exactly ``ok`` and ``device``, the
    device as jax reports it.  Everything else goes on the lines before."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}})


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except OSError:
        return 0


def _equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and np.array_equal(a, b)


def phase_parity(ctx):
    """Device vs CPU reference on the tie-free fixture (the verify skill's
    parity rule; what scripts/smoke_tpu.py's train-parity smoke does)."""
    import dryad_tpu as dryad
    from dryad_tpu.datasets import higgs_like

    X, y = higgs_like(20_000, seed=31)
    ds = dryad.Dataset(X, y, max_bins=64)
    p = dict(objective="binary", num_trees=6, num_leaves=31, max_bins=64)
    b_cpu = dryad.train(p, ds, backend="cpu")
    b_dev = dryad.train(p, ds, backend="tpu")
    for key in ("feature", "threshold", "left", "right", "is_cat"):
        if not _equal(b_cpu.tree_arrays()[key], b_dev.tree_arrays()[key]):
            raise AssertionError(f"fixture: device {key!r} differs from the "
                                 "CPU reference")
    raw_cpu = b_cpu.predict_binned(ds.X_binned, raw_score=True, backend="cpu")
    raw_dev = b_cpu.predict_binned(ds.X_binned, raw_score=True, backend="tpu")
    if not _equal(raw_cpu, raw_dev):
        raise AssertionError("fixture: same-booster predict bits differ")
    ctx["fixture"] = (ds, p, b_dev)
    return {"trees": b_dev.num_total_trees, "rows": 20_000, "bins": 64,
            "structures": "equal to CPU reference",
            "predict": "bitwise"}


def phase_train(ctx):
    import jax

    import dryad_tpu as dryad
    from dryad_tpu.checkpoint import Checkpointer
    from dryad_tpu.config import make_params
    from dryad_tpu.datasets import higgs_like
    from dryad_tpu.engine import pallas_hist
    from dryad_tpu.engine.histogram import resolve_backend
    from dryad_tpu.engine.levelwise import deep_layout_supported
    from dryad_tpu.engine.train import (audit_iteration_args,
                                        audit_iteration_fn)
    from dryad_tpu.metrics.device import HIGHER_BETTER

    rows, valid_rows = ctx["rows"], ctx["valid_rows"]
    clock, dev = ctx["clock"], ctx["device"]
    t0 = time.perf_counter()
    X, y = higgs_like(rows + valid_rows, seed=7)
    ds = dryad.Dataset(X[:rows], y[:rows], max_bins=PARAMS["max_bins"])
    Xv, yv = X[rows:], y[rows:]
    vds = ds.bind(Xv, yv)
    del X
    data_s = time.perf_counter() - t0
    F, B = ds.num_features, int(ds.mapper.total_bins)
    print(f"  data: {rows} + {valid_rows} valid rows x {F} features, {B} bins "
          f"({ds.X_binned.dtype}) in {data_s:.1f}s", flush=True)

    dispatches = []

    def chunk_hook(site, iteration):
        if site == "dispatch":
            dispatches.append(int(iteration))

    params = dict(PARAMS, num_trees=ITERATIONS)
    ckdir = os.path.join(ctx["tmp"], "checkpoints")
    mark, t0 = clock.setup_s, time.perf_counter()
    booster = dryad.train(params, ds, valid_sets=[vds], backend="tpu",
                          checkpoint_dir=ckdir,
                          checkpoint_every=CHECKPOINT_EVERY,
                          chunk_hook=chunk_hook)
    wall_s = time.perf_counter() - t0
    setup_s = clock.since(mark)

    chunks = np.diff(dispatches + [ITERATIONS]).tolist()
    if booster.num_iterations != ITERATIONS:
        raise AssertionError(f"trained {booster.num_iterations} iterations, "
                             f"wanted {ITERATIONS}")
    if len(chunks) < 2 or max(chunks) < 2:
        raise AssertionError(f"chunk lengths {chunks}: the chunked device "
                             "loop did not run (per-iteration dispatch)")
    saved = Checkpointer(ckdir, every=CHECKPOINT_EVERY).iterations()
    if CHECKPOINT_EVERY not in saved:
        raise AssertionError(f"no checkpoint at iteration "
                             f"{CHECKPOINT_EVERY}: found {saved}")
    state = booster.train_state
    if state.get("platform") != dev.platform:
        raise AssertionError(f"train_state platform {state.get('platform')!r}"
                             f" != {dev.platform!r}")
    (key, history), = state["eval_history"].items()
    metric = next(m for m in HIGHER_BETTER if key.endswith("_" + m))
    first, last = history[0][1], history[-1][1]
    if not (last > first if HIGHER_BETTER[metric] else last < first):
        raise AssertionError(f"{key} did not improve: {first} -> {last}")
    for name, arr in booster.tree_arrays().items():
        if arr.dtype.kind == "f" and not np.isfinite(arr).all():
            raise AssertionError(f"non-finite values in trained {name!r}")

    # which arms ran, from what the trainer itself consults
    p = make_params(params).validate()
    plat = dev.platform
    backend = resolve_backend(p.hist_backend, segmented=True, platform=plat)
    interpreted = pallas_hist._interpret(plat)
    layout = ("wired" if deep_layout_supported(
        p, F, B, ds.X_binned.dtype.itemsize, plat) else "legacy")
    lowered = jax.jit(audit_iteration_fn(
        p, B, False, None, plat, rows, learn_missing=ds.has_missing)).lower(
            *audit_iteration_args(p, rows, F, bin_dtype=ds.X_binned.dtype))
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    if plat == "tpu" and (backend != "pallas" or interpreted
                          or mosaic_calls == 0):
        raise AssertionError(
            f"hist backend {backend!r}, interpreted={interpreted}, "
            f"{mosaic_calls} Mosaic custom calls in one lowered iteration: "
            "the Pallas kernels are not compiled into the device program")

    ctx["full"] = (ds, vds, Xv, params, booster)
    return {
        "rows": rows, "valid_rows": valid_rows, "features": F, "bins": B,
        "max_depth": p.max_depth, "num_leaves": p.num_leaves,
        "iterations": ITERATIONS, "chunks": len(chunks),
        "chunk_lengths": chunks,
        "ch_max_effective": state.get("ch_max_effective"),
        "checkpoints": saved,
        "hist_backend": backend, "pallas_interpreted": interpreted,
        "mosaic_custom_calls_per_iteration": mosaic_calls,
        "deep_layout": layout,
        key: [round(first, 6), round(last, 6)],
        "train_state_device": [state["platform"], state["device_kind"]],
        "data_s": round(data_s, 1), "wall_s": round(wall_s, 1),
        "setup_s": round(setup_s, 1), "run_s": round(wall_s - setup_s, 1),
    }


def phase_predict(ctx):
    import dryad_tpu as dryad
    from dryad_tpu.engine.predict import stage_trees, staged_layout

    _, _, Xv, _, booster = ctx["full"]
    p_cpu = dryad.predict(booster, Xv, backend="cpu")
    p_dev = dryad.predict(booster, Xv, backend="tpu")
    if p_dev.shape != (Xv.shape[0],) or not np.isfinite(p_dev).all():
        raise AssertionError(f"device predict: shape {p_dev.shape} or "
                             "non-finite values")
    if not _equal(p_cpu, p_dev):
        raise AssertionError("predict(backend='tpu') differs from "
                             "backend='cpu' on the same booster")
    return {"rows": int(Xv.shape[0]), "bitwise_vs_cpu": True,
            "predict_layout": staged_layout(stage_trees(booster)[0])}


def phase_serve(ctx):
    import http.client

    from dryad_tpu.serve import PredictServer
    from dryad_tpu.serve.http import make_http_server

    _, _, Xv, _, booster = ctx["full"]
    path = os.path.join(ctx["tmp"], "model.dryad")
    booster.save(path)
    server = PredictServer(backend="tpu")
    server.load_model(path)
    httpd = make_http_server(server, "127.0.0.1", 0)
    host, port = httpd.server_address[:2]
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()

    def call(method, url, body=None):
        conn = http.client.HTTPConnection(host, port, timeout=600)
        try:
            conn.request(method, url, body=body)
            resp = conn.getresponse()
            payload = json.loads(resp.read())
            if resp.status != 200:
                raise AssertionError(f"{method} {url}: {resp.status} "
                                     f"{payload}")
            return payload
        finally:
            conn.close()

    try:
        offset = 0
        for n in REQUEST_SIZES:
            rows = Xv[offset:offset + n]
            offset += n
            got = call("POST", "/predict",
                       json.dumps({"rows": rows.tolist()}))
            want = booster.predict(rows)
            if not _equal(np.asarray(got["predictions"], np.float32), want):
                raise AssertionError(f"/predict with {n} rows differs from "
                                     "Booster.predict")
        stats = call("GET", "/stats")
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.stop()
        thread.join(timeout=10)
    devices = stats["devices"]
    if stats["backend"] != "jax" or devices["platform"] != ctx[
            "device"].platform:
        raise AssertionError(f"/stats says backend {stats['backend']!r} on "
                             f"{devices}")
    return {"requests": list(REQUEST_SIZES), "bitwise_vs_booster": True,
            "stats_devices": devices,
            "compiled_buckets": stats["compiled_buckets"]}


def phase_multichip(ctx):
    import jax

    import dryad_tpu as dryad
    from dryad_tpu.engine.distributed import make_mesh

    devices = jax.devices()[:4]
    mesh = make_mesh(devices)
    clock = ctx["clock"]

    ds_f, p_f, b_one = ctx["fixture"]
    b_four = dryad.train(p_f, ds_f, mesh=mesh)
    for key in ("feature", "threshold", "left", "right", "is_cat"):
        if not _equal(b_one.tree_arrays()[key], b_four.tree_arrays()[key]):
            raise AssertionError(f"fixture: 4-device {key!r} differs from "
                                 "1-device")

    ds, vds, Xv, params, _ = ctx["full"]
    rows = -(-ds.num_rows // 4) * 4             # the trainer pads to the mesh
    placements = {}

    def record_placements(iteration, info):
        """Between chunks the run's own device arrays are alive: note where
        every row-sized one lives (the 1-device leg's cached copies show
        up too, whole on device 0)."""
        if placements:
            return
        for a in jax.live_arrays():
            if a.ndim and a.shape[0] == rows:
                placements.setdefault(f"{a.dtype}{list(a.shape)}", []).append(
                    sorted((s.device.id, s.data.shape[0])
                           for s in a.addressable_shards))

    def peaks():
        return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in devices]

    one_device_peak = peaks()[0]
    mark, t0 = clock.setup_s, time.perf_counter()
    b_mesh = dryad.train(params, ds, valid_sets=[vds], mesh=mesh,
                         callbacks=[record_placements])
    wall_s = time.perf_counter() - t0
    setup_s = clock.since(mark)
    after = peaks()
    if b_mesh.num_iterations != ITERATIONS:
        raise AssertionError("mesh run stopped early")
    comm = b_mesh.train_state.get("comm_stats") or {}
    if comm.get("n_shards") != 4:
        raise AssertionError(f"comm_stats {comm}: not a 4-shard run")
    quarters = [(d.id, rows // 4) for d in devices]
    for what, key in (("Xb", f"{ds.X_binned.dtype}[{rows}, "
                             f"{ds.num_features}]"),
                      ("score", f"float32[{rows}, 1]")):
        if quarters not in placements.get(key, []):
            raise AssertionError(
                f"no {what} array {key} split as {quarters} during the mesh "
                f"run; row-sized live arrays: {placements}")
    # device 0 ran the whole 1-device leg before; devices 1-3 have only ever
    # held their share of the sharded run (the counter follows live buffers,
    # part of which does not scale with rows)
    if one_device_peak and not 0 < max(after[1:]) < 0.75 * one_device_peak:
        raise AssertionError(
            f"per-device peak bytes {after} against the 1-device run's "
            f"{one_device_peak}: devices 1-3 hold as much as one device did")
    p_cpu = dryad.predict(b_mesh, Xv, backend="cpu")
    p_sh = b_mesh.predict(Xv, backend="tpu", sharded=True)
    if not _equal(p_cpu, p_sh):
        raise AssertionError("sharded predict differs from backend='cpu'")
    return {
        "devices": [d.id for d in devices],
        "fixture_structures": "4-device equal to 1-device",
        "row_arrays_split": {k: quarters for k, v in placements.items()
                             if quarters in v},
        "one_device_peak_bytes": one_device_peak,
        "peak_bytes_per_device": after,
        "collective_bytes_per_iter": comm.get("collective_bytes_per_iter"),
        "hist_reduce": comm.get("hist_reduce"),
        "sharded_predict": "bitwise",
        "wall_s": round(wall_s, 1), "setup_s": round(setup_s, 1),
        "run_s": round(wall_s - setup_s, 1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny labelled rehearsal on a CPU-only jax; never "
                         "reports ok")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not (args.rehearse_cpu
                                      and dev.platform == "cpu"):
        print(f"chip_smoke: jax initialised with platform {dev.platform!r} "
              f"({dev.device_kind}), not 'tpu' — refusing to start",
              file=sys.stderr)
        return 2
    rehearsal = dev.platform != "tpu"

    # the chunked loop is what a long run uses; a run this short would be
    # routed to per-iteration dispatch by the compile-vs-work heuristic
    # (engine/train.py), so pin it as bench.py does for the same reason
    os.environ.setdefault("DRYAD_CHUNK", "1")

    # the package comes after the device check: without it beside this
    # script nothing below can run, and the import error is the exit
    import dryad_tpu.engine as engine
    from dryad_tpu import native
    from dryad_tpu.policy import gates
    from dryad_tpu.policy.device import current_device_kind

    cache_dir = engine.place_compile_cache()
    entries_before = _cache_entries(cache_dir)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"chip_smoke{' [CPU REHEARSAL — not a device result]' * rehearsal}"
          f": {json.dumps(device)}")
    placed_by = ("JAX_COMPILATION_CACHE_DIR"
                 if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 else "dryad_tpu.engine")
    print(f"  jax {jax.__version__}; compile cache {cache_dir} (placed by "
          f"{placed_by}; {'warm' if entries_before else 'cold'}: "
          f"{entries_before} entries)")
    print(f"  native host library: {native.status()}; policy device_kind: "
          f"{current_device_kind()!r}", flush=True)

    failed = []
    results = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        ctx = {
            "tmp": tmp, "device": dev, "clock": CompileClock(),
            "rows": REHEARSAL_ROWS if rehearsal else ROWS,
            "valid_rows": REHEARSAL_VALID_ROWS if rehearsal else VALID_ROWS,
        }
        if current_device_kind() is None:
            failed.append("device_kind")
            print("FAIL device_kind: policy.device.current_device_kind() "
                  "is None — /stats and every artifact stamp would not "
                  "name the device the gates dispatched for")
        phases = [("parity", phase_parity, ()),
                  ("train", phase_train, ()),
                  ("predict", phase_predict, ("full",)),
                  ("serve", phase_serve, ("full",))]
        if len(devices) >= 4:
            phases.append(("multichip", phase_multichip, ("fixture", "full")))
        for name, fn, needs in phases:
            t0, mark = time.perf_counter(), ctx["clock"].setup_s
            try:
                missing = [k for k in needs if k not in ctx]
                if missing:
                    raise AssertionError(f"needs the result of an earlier "
                                         f"phase that failed ({missing})")
                results[name] = fn(ctx)
            except Exception:  # noqa: BLE001 — report every phase, then fail
                failed.append(name)
                print(f"FAIL {name}:\n{traceback.format_exc()}", flush=True)
                continue
            wall = time.perf_counter() - t0
            print(f"ok   {name}: {json.dumps(results[name])} "
                  f"[{wall:.1f}s, of which set-up "
                  f"{ctx['clock'].since(mark):.1f}s]", flush=True)
        clock = ctx["clock"]

    mem = dev.memory_stats() or {}
    summary = {
        "ok": not failed and not rehearsal,
        "device": device,
        "rows": ctx["rows"],
        "failed": failed,
        "multichip": (results.get("multichip", "failed")
                      if len(devices) >= 4 else "1 device"),
        "arms": {"decisions": {g: d["arm"]
                               for g, d in gates.decisions().items()},
                 **{k: results.get("train", {}).get(k)
                    for k in ("hist_backend", "pallas_interpreted",
                              "mosaic_custom_calls_per_iteration",
                              "deep_layout")},
                 "predict_layout": results.get("predict", {}).get(
                     "predict_layout")},
        "compile_cache": {
            "dir": cache_dir, "warm": bool(entries_before),
            "entries_before": entries_before,
            "entries_after": _cache_entries(cache_dir),
            "hits": clock.cache_hits, "misses": clock.cache_misses,
            "backend_compiles": clock.backend_compiles},
        "setup_s": round(clock.setup_s, 1),
        "total_s": round(time.perf_counter() - t_start, 1),
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "bytes_limit": mem.get("bytes_limit"),
        "native": native.status(),
    }
    if rehearsal:
        summary["rehearsal"] = "cpu"
    print(f"arms: {json.dumps(summary['arms'])}")
    print(f"set-up {summary['setup_s']}s of {summary['total_s']}s total; "
          f"cache {json.dumps(summary['compile_cache'])}; peak HBM "
          f"{summary['peak_bytes_in_use']} of {summary['bytes_limit']} bytes")
    print(f"summary: {json.dumps(summary)}")
    print(result_line(summary["ok"], devices), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
