"""Bring-up contracts (PR 21): nothing on the main path hides the device.

* ``chip_smoke.py`` and ``scripts/smoke_tpu.py --gate`` refuse a CPU-only
  jax instead of skipping their way to an OK;
* a device initialisation that RAISES is never turned into a CPU run
  (``dryad.train(backend="auto")``, ``PredictServer``), while a jax that
  initialised with CPU devices only still picks the CPU paths;
* the persistent compile cache is placed from outside when
  ``JAX_COMPILATION_CACHE_DIR`` is set and under the checkout otherwise;
* a fleet replica with a device backend is shown exactly one chip;
* the run's device is on the record (``train_state``, ``/stats``).
"""

import importlib.util
import os

import jax
import numpy as np
import pytest

import dryad_tpu as dryad
from dryad_tpu.datasets import higgs_like

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(relpath: str):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(relpath)[:-3], os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _broken_devices(*a, **kw):
    raise RuntimeError("ABORTED: Internal error when accessing libtpu "
                       "multi-process lockfile")


# ---- smokes that need the chip say so ------------------------------------

def test_chip_smoke_refuses_cpu_and_names_the_platform(capsys):
    rc = _load("chip_smoke.py").main([])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""                    # no result of any kind
    assert "'cpu'" in out.err and "tpu" in out.err


def test_chip_smoke_last_line_is_exactly_ok_and_device():
    import json

    line = _load("chip_smoke.py").result_line(False, jax.devices())
    assert "\n" not in line
    got = json.loads(line)
    assert got == {"ok": False, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    assert type(got["device"]["count"]) is int


def test_smoke_tpu_gate_fails_without_accelerator(capsys):
    rc = _load("scripts/smoke_tpu.py").main(["--gate"])
    out = capsys.readouterr().out
    assert rc != 0
    assert "no accelerator" in out and "GATE OK" not in out


# ---- no fallback that hides the device ------------------------------------

def test_accelerator_probe_lets_device_errors_through(monkeypatch):
    assert dryad._accelerator_present() is False    # CPU-only jax: CPU path
    monkeypatch.setattr(jax, "devices", _broken_devices)
    with pytest.raises(RuntimeError, match="lockfile"):
        dryad._accelerator_present()
    X, y = higgs_like(200, seed=1)
    with pytest.raises(RuntimeError, match="lockfile"):
        dryad.train(dict(objective="binary", num_trees=1),
                    dryad.Dataset(X, y, max_bins=16), backend="auto")


def test_serve_backend_lets_device_errors_through(monkeypatch):
    from dryad_tpu.serve import PredictServer
    from dryad_tpu.serve.server import _resolve_backend

    assert _resolve_backend("auto") == "cpu"        # CPU-only jax: numpy path
    assert _resolve_backend("tpu") == "jax"
    assert _resolve_backend("cpu") == "cpu"
    monkeypatch.setattr(jax, "devices", _broken_devices)
    assert _resolve_backend("cpu") == "cpu"         # never touches jax
    for backend in ("auto", "tpu"):
        with pytest.raises(RuntimeError, match="lockfile"):
            _resolve_backend(backend)
        with pytest.raises(RuntimeError, match="lockfile"):
            PredictServer(backend=backend)


# ---- where a run executed is on the record ---------------------------------

def test_train_state_and_stats_name_the_device():
    from dryad_tpu.serve import PredictServer

    X, y = higgs_like(400, seed=2)
    ds = dryad.Dataset(X, y, max_bins=16)
    b = dryad.train(dict(objective="binary", num_trees=2, num_leaves=4,
                         max_bins=16), ds, backend="tpu")
    dev = jax.devices()[0]
    assert b.train_state["platform"] == dev.platform
    assert b.train_state["device_kind"] == dev.device_kind
    assert PredictServer(backend="cpu").stats()["devices"] is None
    block = PredictServer(backend="tpu", sharded=False).stats()["devices"]
    assert block == {"platform": dev.platform,
                     "device_kind": dev.device_kind, "ids": [dev.id],
                     "visible_chips": None}
    mesh_ids = PredictServer(backend="tpu",
                             sharded=True).stats()["devices"]["ids"]
    assert mesh_ids == [d.id for d in jax.devices()]


# ---- the compile cache is placed from outside ------------------------------

def test_compile_cache_placement(monkeypatch):
    import dryad_tpu.engine as engine

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert engine.place_compile_cache() == "/some/dir"
    assert calls == []                      # jax reads the variable itself

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = engine.place_compile_cache()
    second = engine.place_compile_cache()
    assert first == second == os.path.join(ROOT, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2


# ---- one process per chip --------------------------------------------------

def test_fleet_slots_get_one_chip_each():
    from dryad_tpu.fleet import FleetSupervisor, serve_env
    from dryad_tpu.resilience.faults import REPLICA_FAULTS_ENV

    assert serve_env(3, "cpu") == {}
    envs = [serve_env(i, "tpu") for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert serve_env(1, "auto") == envs[1]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)

    sup = FleetSupervisor(lambda i, pf: ["true"], 2,
                          make_env=lambda i: serve_env(i, "tpu"),
                          fault_env={1: "request:2:replica_crash"})
    env0, env1 = (sup._spawn_env(s) for s in sup.slots)
    assert env0 == dict(envs[0], **{REPLICA_FAULTS_ENV: ""})
    assert env1 == dict(envs[1],
                        **{REPLICA_FAULTS_ENV: "request:2:replica_crash"})
    sup.slots[1].generation = 1             # a respawn keeps its chip,
    assert sup._spawn_env(sup.slots[1]) == dict(    # drops the drill
        envs[1], **{REPLICA_FAULTS_ENV: ""})


# ---- a ragged layout buffer is an error, not a silent drop -----------------

def test_layout_kernels_reject_a_ragged_tail():
    import jax.numpy as jnp

    from dryad_tpu.engine import leafperm

    T = leafperm._TILE_ROWS
    rec = jnp.zeros((T + 1, leafperm._REC_WB), jnp.uint8)
    with pytest.raises(ValueError, match="multiple"):
        leafperm.move_level(rec, jnp.zeros((1,), jnp.int32),
                            jnp.zeros((1, 2), jnp.uint32),
                            bin_dtype=np.uint8)
    with pytest.raises(ValueError, match="multiple"):
        leafperm.hist_from_layout(rec, jnp.zeros((1,), jnp.int32),
                                  jnp.ones((1,), jnp.int32), 1, 16, 4,
                                  np.uint8, 1)
