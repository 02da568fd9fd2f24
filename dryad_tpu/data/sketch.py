"""Canonical quantile sketch → per-feature bin mapper.

This is the host-side, bit-exact ground truth required by the north-star
("Quantile sketching ... stay[s] bit-identical with the CPU reference",
BASELINE.json:5).  Every consumer — the CPU reference trainer, the TPU engine,
and predict on either backend — bins through the *same* frozen edges produced
here, so bit-identity of binned ids is structural rather than numerical.

Binning semantics (frozen contract, shared with data/binning.py and the
device predict path):

* bin id 0 is **always** the missing (NaN) bin, for every feature.
* numerical feature with edges ``e[0..k-1]`` (ascending float32):
  ``bin(x) = 1 + searchsorted(e, x, side='left')`` — i.e. x <= e[i] lands in
  bin i+1; x greater than every edge lands in bin k+1.  Total bins = k+2
  (missing + k+1 value bins), bounded by ``max_bins``.
* categorical feature: categories ranked by (frequency desc, value asc);
  rank r maps to bin r+1; categories beyond the vocab and unseen-at-predict
  values map to the overflow bin (the last bin id).

A split at (feature f, threshold bin t) sends rows with ``bin <= t`` left.
Because the missing bin is 0, missing always travels left; t = 0 expresses
"split missing off from everything else".  (Learned per-node default
direction is layered on top by the grower; the mapper stays direction-free.)
"""

from __future__ import annotations

import dataclasses
import io
from typing import Sequence

import numpy as np

from dryad_tpu.obs.registry import default_registry

MISSING_BIN = 0


@dataclasses.dataclass
class FeatureBins:
    """Frozen binning recipe for one feature."""

    is_categorical: bool
    # numerical: ascending upper-boundary edges (float32); len k → bins 1..k+1
    edges: np.ndarray
    # categorical: vocab values sorted ascending + their bin ids
    cat_values: np.ndarray
    cat_bins: np.ndarray
    n_bins: int  # total bins including the missing bin (and overflow bin for cats)

    @property
    def overflow_bin(self) -> int:
        return self.n_bins - 1


def _sketch_numerical(col: np.ndarray, max_bins: int) -> FeatureBins:
    from dryad_tpu import native

    edges = native.sketch_numerical(col, max_bins)
    if edges is not None:
        return FeatureBins(
            False, edges, np.empty(0, np.float32), np.empty(0, np.int32),
            int(edges.size) + 2,
        )
    return _sketch_numerical_np(col, max_bins)


def _sketch_numerical_np(col: np.ndarray, max_bins: int) -> FeatureBins:
    """Pure-numpy canonical sketch — the bit-exact spec the native path must match."""
    finite = col[np.isfinite(col)]
    if finite.size == 0:
        edges = np.empty((0,), np.float32)
        return FeatureBins(False, edges, np.empty(0, np.float32), np.empty(0, np.int32), 2)
    distinct = np.unique(finite)
    max_edges = max_bins - 2  # bins = missing + (edges+1)
    if distinct.size - 1 <= max_edges:
        # One bin per distinct value; boundaries midway between neighbours.
        edges = ((distinct[:-1] + distinct[1:]) * np.float32(0.5)).astype(np.float32)
        # A midpoint can collapse onto the lower value for adjacent floats;
        # that still separates the pair (x <= edge keeps the lower value left).
    else:
        # Equal-frequency cuts over the sorted sample, deduplicated so heavy
        # ties never straddle a boundary.
        svals = np.sort(finite)
        pos = (np.arange(1, max_edges + 1, dtype=np.int64) * svals.size) // (max_edges + 1)
        edges = np.unique(svals[pos].astype(np.float32))
    return FeatureBins(
        False, edges.astype(np.float32), np.empty(0, np.float32), np.empty(0, np.int32),
        int(edges.size) + 2,
    )


def _sketch_categorical(col: np.ndarray, max_bins: int, feature: int | None = None) -> FeatureBins:
    """``feature`` given: gauge ``dryad_cat_levels{feature, kind}`` is set to
    the categories kept (a bin each) and pooled into the overflow bin."""
    finite = col[np.isfinite(col)]
    vals, counts = np.unique(finite, return_counts=True)
    # rank by (count desc, value asc) — deterministic
    order = np.lexsort((vals, -counts))
    n_kept = int(min(vals.size, max_bins - 2))  # reserve missing(0) + overflow(last)
    if feature is not None:
        _note_levels(feature, n_kept, vals.size - n_kept)
    kept = vals[order[:n_kept]]
    bins = np.arange(1, n_kept + 1, dtype=np.int32)
    # store sorted by value for searchsorted lookup
    sort_idx = np.argsort(kept, kind="stable")
    return FeatureBins(
        True,
        np.empty(0, np.float32),
        kept[sort_idx].astype(np.float32),
        bins[sort_idx].astype(np.int32),
        n_kept + 2,
    )


def _note_levels(feature: int, kept: int, pooled: int) -> None:
    reg = default_registry()
    if not reg.enabled:
        return
    gauge = reg.gauge("dryad_cat_levels",
                      "Categories of a categorical column at the sketch: kept "
                      "(a bin each) / pooled (into the overflow bin)")
    gauge.labels(feature=str(feature), kind="kept").set(float(kept))
    gauge.labels(feature=str(feature), kind="pooled").set(float(pooled))


def sketch_features(
    X: np.ndarray,
    max_bins: int = 256,
    categorical_features: Sequence[int] = (),
) -> "BinMapper":
    """Build the frozen per-feature bin mapper from training data.

    Deterministic pure-numpy canonical implementation; the optional C++
    accelerated path (dryad_tpu.native) must match it bit-for-bit.
    """
    X = np.asarray(X, dtype=np.float32)
    if X.ndim != 2:
        raise ValueError(f"X must be 2-D, got shape {X.shape}")
    cats = frozenset(int(c) for c in categorical_features)
    feats = [
        _sketch_categorical(X[:, f], max_bins, f) if f in cats
        else _sketch_numerical(X[:, f], max_bins)
        for f in range(X.shape[1])
    ]
    return BinMapper(feats, max_bins)


class BinMapper:
    """Frozen collection of per-feature binning recipes."""

    def __init__(self, features: list[FeatureBins], max_bins: int):
        self.features = features
        self.max_bins = int(max_bins)

    @property
    def num_features(self) -> int:
        return len(self.features)

    @property
    def n_bins(self) -> np.ndarray:
        return np.array([f.n_bins for f in self.features], np.int32)

    @property
    def total_bins(self) -> int:
        return int(self.n_bins.max(initial=2))

    @property
    def bin_dtype(self) -> np.dtype:
        return np.dtype(np.uint8 if self.total_bins <= 256 else np.uint16)

    @property
    def is_categorical(self) -> np.ndarray:
        return np.array([f.is_categorical for f in self.features], bool)

    def transform_column(self, col: np.ndarray, f: int) -> np.ndarray:
        fb = self.features[f]
        col = np.asarray(col, np.float32)
        out = np.zeros(col.shape, np.int32)
        missing = np.isnan(col)
        if fb.is_categorical:
            idx = np.searchsorted(fb.cat_values, col)
            idx_c = np.minimum(idx, max(fb.cat_values.size - 1, 0))
            if fb.cat_values.size:
                hit = fb.cat_values[idx_c] == col
                out = np.where(hit, fb.cat_bins[idx_c], fb.overflow_bin).astype(np.int32)
            else:
                out[:] = fb.overflow_bin
        else:
            out = (1 + np.searchsorted(fb.edges, col, side="left")).astype(np.int32)
        out[missing] = MISSING_BIN
        return out

    def transform(self, X: np.ndarray) -> np.ndarray:
        """Map raw features → bin ids, dtype uint8/uint16, shape (N, F)."""
        X = np.asarray(X, np.float32)
        out = np.empty(X.shape, self.bin_dtype)
        for f in range(self.num_features):
            out[:, f] = self.transform_column(X[:, f], f)
        return out

    # device-side view: edges padded to a rectangle for jnp bucketize
    def padded_edges(self) -> tuple[np.ndarray, np.ndarray]:
        k = max((f.edges.size for f in self.features), default=0)
        pad = np.full((self.num_features, max(k, 1)), np.inf, np.float32)
        n_edges = np.zeros(self.num_features, np.int32)
        for i, f in enumerate(self.features):
            pad[i, : f.edges.size] = f.edges
            n_edges[i] = f.edges.size
        return pad, n_edges

    # ---- serialization -----------------------------------------------------
    def to_json_dict(self) -> dict:
        """JSON-safe structural dump for the versioned model TEXT format
        (Booster.save_text).  Floats pass through Python float (exact f64
        widening of the f32 edges), so json round-trips them bit-exactly;
        ±inf edges serialize as JSON Infinity (Python json default)."""
        return {
            "type": "plain",
            "max_bins": int(self.max_bins),
            "features": [
                {
                    "is_categorical": bool(f.is_categorical),
                    "edges": [float(e) for e in np.asarray(f.edges, np.float32)],
                    "cat_values": [float(v) for v in
                                   np.asarray(f.cat_values, np.float32)],
                    "cat_bins": [int(b) for b in f.cat_bins],
                    "n_bins": int(f.n_bins),
                }
                for f in self.features
            ],
        }

    @classmethod
    def from_json_dict(cls, d: dict) -> "BinMapper":
        feats = [
            FeatureBins(
                bool(f["is_categorical"]),
                np.asarray(f["edges"], np.float32),
                np.asarray(f["cat_values"], np.float32),
                np.asarray(f["cat_bins"], np.int32),
                int(f["n_bins"]),
            )
            for f in d["features"]
        ]
        return cls(feats, int(d["max_bins"]))

    def to_bytes(self) -> bytes:
        buf = io.BytesIO()
        arrs: dict[str, np.ndarray] = {
            "max_bins": np.array([self.max_bins], np.int64),
            "is_cat": self.is_categorical,
            "n_bins": self.n_bins,
        }
        for i, f in enumerate(self.features):
            arrs[f"edges_{i}"] = f.edges
            arrs[f"catv_{i}"] = f.cat_values
            arrs[f"catb_{i}"] = f.cat_bins
        np.savez_compressed(buf, **arrs)
        return buf.getvalue()

    @classmethod
    def from_bytes(cls, data: bytes) -> "BinMapper":
        with np.load(io.BytesIO(data)) as z:
            if "efb_base" in z.files:   # bundled-mapper container (EFB)
                from dryad_tpu.data.bundling import BundledMapper

                return BundledMapper.from_bytes(data)
            n = z["is_cat"].shape[0]
            feats = [
                FeatureBins(
                    bool(z["is_cat"][i]),
                    z[f"edges_{i}"],
                    z[f"catv_{i}"],
                    z[f"catb_{i}"],
                    int(z["n_bins"][i]),
                )
                for i in range(n)
            ]
            return cls(feats, int(z["max_bins"][0]))
