"""ctypes bridge to the native host layer (``src/dryad_native.cpp``).

The reference keeps sketching/binning/predict hot loops in native code
(BASELINE.json:5); here they live in a zero-dependency shared object built
with ``make -C dryad_tpu/native`` and loaded through ctypes (the image has
no pybind11).  The pure-numpy implementations in ``data/sketch.py`` /
``cpu/predict.py`` remain the bit-exact *spec*; this module is the fast
path and must match them bit for bit (tests/test_native.py diffs them).

Loading is lazy and failure-tolerant.  ``libdryad_native.so`` is not
committed (``.gitignore``), so the rule is stated on the binary itself,
never on file times (which mean nothing in a fresh copy of the tree): a
.so that is absent, cannot be opened or reports another ABI version is
rebuilt from ``src/dryad_native.cpp`` into the checkout; if the
toolchain is missing too, ``available()`` is False and every caller
falls back to numpy.  ``status()`` says which of these happened.
``DRYAD_NATIVE=0`` disables the native path outright.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

from dryad_tpu.obs.spans import span

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libdryad_native.so")

_lib: Optional[ctypes.CDLL] = None
_tried = False
# "loaded" (an existing .so passed the ABI check), "built" (compiled by
# this process), "unavailable" (no usable .so and no toolchain) or
# "disabled" (DRYAD_NATIVE=0) — set by the first _load()
_status = "unavailable"
# must equal dryad_abi_version() in the .so; a binary from another
# revision would otherwise be called through the wrong signature
_ABI_VERSION = 2

_i64 = ctypes.c_int64
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_u16p = np.ctypeslib.ndpointer(np.uint16, flags="C_CONTIGUOUS")
_u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")


def _build() -> bool:
    # on the obs clock where it happens: a fresh checkout's first sketch
    # waits for the compiler (the span nests under ``data.sketch`` there)
    with span("data.native_build"):
        try:
            # -B: the rule is the ABI check, not make's file times
            res = subprocess.run(
                ["make", "-B", "-C", _HERE],
                capture_output=True,
                timeout=120,
            )
            return res.returncode == 0 and os.path.exists(_SO)
        except (OSError, subprocess.TimeoutExpired):
            return False


def _open() -> Optional[ctypes.CDLL]:
    """The .so on disk, or None when it is absent, cannot be opened or
    was built for another ABI version."""
    if not os.path.exists(_SO):
        return None
    try:
        lib = ctypes.CDLL(_SO)
        lib.dryad_abi_version.restype = _i64
        lib.dryad_abi_version.argtypes = []
        return lib if lib.dryad_abi_version() == _ABI_VERSION else None
    except (OSError, AttributeError):
        return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _status
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("DRYAD_NATIVE", "1") == "0":
        _status = "disabled"
        return None
    lib, status = _open(), "loaded"
    if lib is None:
        lib, status = (_open() if _build() else None), "built"
    if lib is None:
        return None
    try:
        lib.sketch_numerical.restype = _i64
        lib.sketch_numerical.argtypes = [_f32p, _i64, _i64, _f32p]
        lib.bin_matrix.restype = None
        lib.bin_matrix.argtypes = [
            _f32p, _i64, _i64, _f32p, _i64p, _f32p, _i32p, _i64p, _u8p, _i32p,
            _u16p,
        ]
        lib.predict_accumulate.restype = None
        lib.predict_accumulate.argtypes = [
            _u16p, _i64, _i64, _i32p, _i32p, _i32p, _i32p, _u8p, _u32p, _u8p,
            _f32p, _i64, _i64, _i64, _i64, _i64, _f32p,
        ]
    except AttributeError:
        # a symbol is missing although the ABI number matched: fall back
        # to numpy rather than crash
        return None
    _lib, _status = lib, status
    return _lib


def status() -> str:
    """How the native library came to be (or not) in this process:
    ``"loaded"``, ``"built"``, ``"unavailable"`` or ``"disabled"``."""
    _load()
    return _status


def available() -> bool:
    return _load() is not None


def sketch_numerical(col: np.ndarray, max_bins: int) -> Optional[np.ndarray]:
    """Native numerical quantile sketch -> ascending float32 edges, or None."""
    lib = _load()
    if lib is None:
        return None
    col = np.ascontiguousarray(col, np.float32)
    out = np.empty(max(int(max_bins), 2), np.float32)
    k = lib.sketch_numerical(col, col.size, int(max_bins), out)
    return out[:k].copy()


def bin_matrix(X: np.ndarray, mapper) -> Optional[np.ndarray]:
    """Native dense binning through a frozen BinMapper, or None."""
    lib = _load()
    if lib is None:
        return None
    X = np.ascontiguousarray(X, np.float32)
    n, F = X.shape
    feats = mapper.features

    edge_offsets = np.zeros(F + 1, np.int64)
    cat_offsets = np.zeros(F + 1, np.int64)
    for f, fb in enumerate(feats):
        edge_offsets[f + 1] = edge_offsets[f] + fb.edges.size
        cat_offsets[f + 1] = cat_offsets[f] + fb.cat_values.size
    edges_flat = np.empty(max(int(edge_offsets[-1]), 1), np.float32)
    catv_flat = np.empty(max(int(cat_offsets[-1]), 1), np.float32)
    catb_flat = np.empty(max(int(cat_offsets[-1]), 1), np.int32)
    for f, fb in enumerate(feats):
        edges_flat[edge_offsets[f] : edge_offsets[f + 1]] = fb.edges
        catv_flat[cat_offsets[f] : cat_offsets[f + 1]] = fb.cat_values
        catb_flat[cat_offsets[f] : cat_offsets[f + 1]] = fb.cat_bins
    is_cat = mapper.is_categorical.astype(np.uint8)
    overflow = np.array([fb.overflow_bin for fb in feats], np.int32)

    out = np.empty((n, F), np.uint16)
    lib.bin_matrix(
        X, n, F, edges_flat, edge_offsets, catv_flat, catb_flat, cat_offsets,
        is_cat, overflow, out,
    )
    return out.astype(mapper.bin_dtype, copy=False)


def predict_accumulate(
    Xb: np.ndarray,
    trees: dict[str, np.ndarray],
    init_score: np.ndarray,
    num_trees: int,
    K: int,
    depth_bound: int,
) -> Optional[np.ndarray]:
    """Native booster predict: (N, K) raw scores, or None."""
    lib = _load()
    if lib is None:
        return None
    Xb = np.ascontiguousarray(Xb, np.uint16)
    n, F = Xb.shape
    feature = np.ascontiguousarray(trees["feature"], np.int32)
    max_nodes = feature.shape[1]
    cat_bitset = np.ascontiguousarray(trees["cat_bitset"], np.uint32)
    cat_words = cat_bitset.shape[2]
    score = np.broadcast_to(
        np.asarray(init_score, np.float32), (n, K)
    ).astype(np.float32, order="C")
    lib.predict_accumulate(
        Xb, n, F,
        feature,
        np.ascontiguousarray(trees["threshold"], np.int32),
        np.ascontiguousarray(trees["left"], np.int32),
        np.ascontiguousarray(trees["right"], np.int32),
        np.ascontiguousarray(trees["is_cat"], np.uint8),
        cat_bitset,
        np.ascontiguousarray(
            trees.get("default_left", np.ones_like(trees["feature"], dtype=bool)),
            np.uint8),
        np.ascontiguousarray(trees["value"], np.float32),
        int(num_trees), max_nodes, cat_words, int(K), max(int(depth_bound), 1),
        score,
    )
    return score
