"""dryad_tpu — a TPU-native gradient-boosted-decision-tree framework.

Public API mirrors the reference's ``dryad.train`` / ``dryad.predict``
surface (BASELINE.json:5).  The ``dryad`` package is an alias of this one.

    import dryad_tpu as dryad
    ds = dryad.Dataset(X, y)
    booster = dryad.train({"objective": "binary", "num_trees": 100}, ds)
    p = dryad.predict(booster, X_test)
"""

from __future__ import annotations

import contextlib
from typing import Any, Mapping, Optional

import numpy as np

from dryad_tpu.booster import Booster
from dryad_tpu.config import Params, make_params
from dryad_tpu.cv import cv
from dryad_tpu.dataset import Dataset

__version__ = "0.1.0"
__all__ = ["train", "predict", "cv", "Dataset", "Booster", "Params",
           "__version__"]


def train(
    params: "Params | Mapping[str, Any] | None" = None,
    train_set: Optional[Dataset] = None,
    valid_sets: Optional[list[Dataset]] = None,
    *,
    valid_names: Optional[list[str]] = None,
    backend: str = "auto",
    init_booster: Optional[Booster] = None,
    init_model: Optional[Booster] = None,
    callback=None,
    callbacks=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 10,
    resume: bool = False,
    profile_dir: Optional[str] = None,
    chunk_hook=None,
    chunk_policy=None,
    mesh=None,
    **kw: Any,
) -> Booster:
    """Train a booster.  backend: 'auto' (TPU if available), 'tpu', 'cpu'.

    ``checkpoint_dir`` enables periodic atomic checkpoints every
    ``checkpoint_every`` iterations; with ``resume=True`` training continues
    from the newest checkpoint in that directory (reproducing the
    uninterrupted run bit for bit — see dryad_tpu/checkpoint.py).
    ``callbacks`` is a list of ``fn(iteration, info)`` (see
    dryad_tpu/callbacks.py); ``callback`` remains as a single-function alias.
    ``profile_dir`` captures a jax.profiler trace of the whole training run
    (open with XProf/Perfetto — SURVEY.md §5 tracing).
    ``chunk_hook``/``chunk_policy`` are the resilience subsystem's loop
    observation + adaptive chunk-cap surfaces (see engine/train.py and
    dryad_tpu/resilience/ — most callers want ``supervise_train`` instead of
    passing these directly).  ``mesh`` forwards an explicit device mesh to
    the device trainer (rows sharded, histograms psum'd; see
    ``distributed.train_distributed`` for the usual front door).

    ``init_model`` (r19, continual boosting) is the warm-start APPEND
    surface: resume boosting from a LOADED served model's carried scores
    on fresh rows — ``num_trees`` counts the NEW trees to append (0 is a
    valid no-op that returns a predict-identical copy), and the fresh
    rows must be binned in the model's frozen bin space
    (``Dataset(X, y, mapper=model.mapper)``).  It rides the checkpoint-
    resume machinery (carried scores rebuilt bitwise by tree replay), so
    a same-shape append reuses the already-compiled programs — the
    num_trees total is erased from the jit key.  ``init_booster`` remains
    the low-level TOTAL-count resume surface the checkpoint path uses;
    pass one or the other.  Apply ``Booster.refit``/leaf renewal BEFORE
    the append when the old trees' leaf values should be re-weighted
    toward the fresh rows.
    """
    p = make_params(params, **kw)
    if train_set is None:
        raise ValueError("train_set is required")
    if init_model is not None:
        if init_booster is not None:
            raise ValueError("pass init_model (append semantics) or "
                             "init_booster (total-count resume), not both")
        if resume:
            raise ValueError(
                "init_model with resume=True is ambiguous (the checkpoint "
                "would be shadowed by the warm start) — warm-started runs "
                "that need crash recovery go through "
                "resilience.supervise_train, which owns that hand-off")
        _check_append_compatible(p, train_set, init_model)
        p = p.replace(num_trees=p.num_trees + init_model.num_iterations)
        init_booster = init_model
    elif p.num_trees == 0:
        raise ValueError("num_trees=0 is only meaningful with init_model "
                         "(a 0-tree warm-start append)")
    if (any(p.monotone_constraints)
            and getattr(train_set.mapper, "bundled_mask", None) is not None):
        # EFB reorders/stacks columns, so positional per-feature constraints
        # would land on the wrong (and non-ordinal) columns
        raise ValueError(
            "monotone_constraints are positional over the original features "
            "and are incompatible with feature bundling — rebuild the "
            "Dataset with bundle=False")
    # every valid set is evaluated and logged per iteration; early stopping
    # watches the FIRST one (LightGBM semantics)
    valid = list(valid_sets) if valid_sets else None
    if valid_names is not None:
        if valid is None or len(valid_names) != len(valid):
            raise ValueError("valid_names must match valid_sets in length")
        valid = list(zip(valid_names, valid))
    if mesh is not None:
        if backend == "cpu":
            raise ValueError(
                "mesh requires the device trainer — backend='cpu' with an "
                "explicit mesh is contradictory (drop the mesh to run the "
                "CPU reference path)")
        backend = "tpu"           # an explicit mesh means the device path
    elif backend == "auto":
        backend = "tpu" if (_accelerator_present() and _engine_present()) else "cpu"

    checkpointer = None
    if checkpoint_dir is not None:
        from dryad_tpu.checkpoint import Checkpointer

        checkpointer = Checkpointer(checkpoint_dir, every=checkpoint_every)
        if resume and init_booster is None:
            latest = checkpointer.latest()
            if latest is not None:
                init_booster = latest[0]
    elif resume:
        raise ValueError("resume=True requires checkpoint_dir")

    from dryad_tpu.callbacks import combine

    cb = combine(([callback] if callback else []) + list(callbacks or []))

    if backend not in ("cpu", "tpu"):
        raise ValueError(f"unknown backend {backend!r}")

    if profile_dir is not None:
        import jax

        trace_ctx = jax.profiler.trace(profile_dir)
    else:
        trace_ctx = contextlib.nullcontext()

    with trace_ctx:
        if backend == "cpu":
            from dryad_tpu.cpu.trainer import train_cpu

            booster = train_cpu(p, train_set, valid,
                                init_booster=init_booster, callback=cb,
                                checkpointer=checkpointer,
                                chunk_hook=chunk_hook)
        else:
            from dryad_tpu.engine.train import train_device

            booster = train_device(p, train_set, valid,
                                   init_booster=init_booster, callback=cb,
                                   checkpointer=checkpointer, mesh=mesh,
                                   chunk_hook=chunk_hook,
                                   chunk_policy=chunk_policy)
    _attach_profile(booster, train_set, valid)
    return booster


def _check_append_compatible(p: Params, train_set: Dataset,
                             model: Booster) -> None:
    """A warm-start append is only well-defined when the fresh rows live
    in the model's frozen bin space and the tree geometry matches — the
    carried-score replay walks the OLD trees over the NEW binned matrix,
    so a re-sketched mapper would silently misroute every row."""
    m_new, m_old = train_set.mapper, model.mapper
    same = m_new is m_old
    if not same:
        try:
            same = m_new.to_json_dict() == m_old.to_json_dict()
        except AttributeError:
            same = False
    if not same:
        raise ValueError(
            "init_model append: the training set was binned with a "
            "different mapper than the model's frozen bin space — build "
            "it as Dataset(X, y, mapper=model.mapper) so the carried-"
            "score replay and the new trees share one bin vocabulary")
    if p.max_nodes != model.params.max_nodes:
        raise ValueError(
            f"init_model append: params imply max_nodes={p.max_nodes} but "
            f"the model was grown with {model.params.max_nodes} — derive "
            "the append params from model.params (e.g. "
            "model.params.replace(num_trees=K)) so tree arrays stack")


def _attach_profile(booster, train_set, valid_sets) -> None:
    """Train-completion hook: embed the drift baseline (data/profile.py)
    in the returned model.  Host-side and bounded (stride subsample +
    one CPU predict); ``DRYAD_PROFILE=0`` skips it (the tier-1 suite
    pins it off in conftest — hundreds of tiny trains need no baseline).
    Best-effort: a profile failure warns, it never fails a finished
    training run at the finish line."""
    import os

    if os.environ.get("DRYAD_PROFILE", "1") == "0":
        return
    try:
        from dryad_tpu.data.profile import build_reference_profile

        booster.profile = build_reference_profile(booster, train_set,
                                                  valid_sets)
    except Exception as e:  # noqa: BLE001 — telemetry must not kill a train
        import warnings

        warnings.warn(f"reference-profile capture failed ({e!r}); "
                      "the model ships without a drift baseline")


def predict(
    booster: Booster,
    X: np.ndarray,
    *,
    raw_score: bool = False,
    backend: str = "cpu",
    num_iteration: Optional[int] = None,
    pred_leaf: bool = False,
    pred_contrib: bool = False,
) -> np.ndarray:
    """Predict on raw features through the booster's frozen bin mapper."""
    return booster.predict(
        X, raw_score=raw_score, backend=backend, num_iteration=num_iteration,
        pred_leaf=pred_leaf, pred_contrib=pred_contrib
    )


def _accelerator_present() -> bool:
    """True when jax initialised with a non-CPU device.  No jax installed,
    or a jax that initialised with CPU devices only, picks the CPU
    trainer; a device initialisation that RAISES (a chip held by another
    process, a broken libtpu) propagates — it must never turn into a
    silent run on the numpy reference."""
    import importlib.util

    if importlib.util.find_spec("jax") is None:
        return False
    import jax

    return any(d.platform != "cpu" for d in jax.devices())


def _engine_present() -> bool:
    import importlib.util

    return importlib.util.find_spec("dryad_tpu.engine") is not None
