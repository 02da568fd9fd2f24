"""Dataset container: raw ingest → frozen sketch → binned matrix.

The public surface mirrors the reference's train-time data object implied by
``dryad.train(params, dataset)`` (BASELINE.json:5).  A Dataset owns:

* the frozen BinMapper (quantile sketch output — the bit-identity anchor),
* the binned matrix (N, F) uint8/uint16,
* labels, optional weights, and optional ranking query groups.

Validation sets bin through the *training* mapper (``Dataset.bind``), exactly
as predict does.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from dryad_tpu.data.binning import bin_csr, bin_matrix
from dryad_tpu.data.sketch import BinMapper, sketch_features
from dryad_tpu.obs.spans import span


class Dataset:
    def __init__(
        self,
        X: Optional[np.ndarray] = None,
        y: Optional[np.ndarray] = None,
        *,
        weight: Optional[np.ndarray] = None,
        group: Optional[np.ndarray] = None,
        categorical_features: Sequence[int] = (),
        max_bins: int = 256,
        mapper: Optional[BinMapper] = None,
        csr: Optional[tuple[np.ndarray, np.ndarray, np.ndarray, int]] = None,
        bundle: bool = True,
    ):
        if (X is None) == (csr is None):
            raise ValueError("provide exactly one of X (dense) or csr=(indptr, indices, values, num_features)")
        self.categorical_features = tuple(int(c) for c in categorical_features)
        if csr is not None:
            from dryad_tpu.data.bundling import BundledMapper, plan_bundles

            indptr, indices, values, num_features = csr
            if mapper is None:
                with span("data.sketch"):
                    base = _sketch_csr(indptr, indices, values, num_features,
                                       max_bins, self.categorical_features)
                with span("data.bin"):
                    Xb0 = bin_csr(indptr, indices, values, num_features, base)
                with span("data.sketch"):   # the plan reads the base bins
                    plan = plan_bundles(Xb0, base, max_bins) if bundle else []
                if plan:
                    # exclusive feature bundling: fold strictly-exclusive
                    # sparse columns (deterministic plan, stored in the
                    # mapper) — the grower sees fewer, denser features
                    mapper = BundledMapper(base, plan)
                    self.mapper = mapper
                    with span("data.bin"):
                        self.X_binned = mapper.fold(Xb0)
                else:
                    self.mapper = base
                    self.X_binned = Xb0
            elif isinstance(mapper, BundledMapper):
                self.mapper = mapper
                with span("data.bin"):
                    self.X_binned = mapper.fold(bin_csr(
                        indptr, indices, values, num_features, mapper.base))
            else:
                self.mapper = mapper
                with span("data.bin"):
                    self.X_binned = bin_csr(indptr, indices, values,
                                            num_features, mapper)
        else:
            X = np.asarray(X, np.float32)
            if mapper is None:
                with span("data.sketch"):
                    mapper = sketch_features(
                        X, max_bins=max_bins,
                        categorical_features=self.categorical_features)
            self.mapper = mapper
            with span("data.bin"):
                self.X_binned = bin_matrix(X, mapper)

        self.num_rows, self.num_features = self.X_binned.shape
        self._attach_targets(y, weight, group)

    _has_missing: Optional[bool] = None
    #: overridden by data.stream_dataset.StreamedDataset — trainers branch
    #: to bounded-read accessors instead of the resident X_binned
    is_streamed: bool = False

    @property
    def has_missing(self) -> bool:
        """True when any NUMERICAL column contains missing (bin 0) rows —
        the growers then scan splits in both missing directions.  On
        missing-free data the flag keeps the split scan single-plane, so
        compiled programs and grown trees are unchanged.  (Categorical
        missing learns its direction through subset membership instead.)"""
        if self._has_missing is None:
            zero_cols = (self.X_binned == 0).any(axis=0)
            eligible = ~self.mapper.is_categorical
            # bundled (EFB) columns: bin 0 means "all members default",
            # never "missing" — they must not trigger the two-plane scan
            bundled = getattr(self.mapper, "bundled_mask", None)
            if bundled is not None:
                eligible &= ~bundled
            self._has_missing = bool((zero_cols & eligible).any())
        return self._has_missing

    def _attach_targets(self, y, weight, group) -> None:
        """Validate + store labels/weights/query groups (shared by __init__
        and the from_binned factory so the checks can never drift)."""
        self.y = None if y is None else np.ascontiguousarray(y, np.float32)
        if self.y is not None and self.y.shape[0] != self.num_rows:
            raise ValueError("y length mismatch")
        self.weight = None if weight is None else np.ascontiguousarray(weight, np.float32)
        if self.weight is not None and self.weight.shape[0] != self.num_rows:
            raise ValueError(
                f"weight length {self.weight.shape[0]} != num_rows {self.num_rows}"
            )
        # ranking: group[i] = #rows in query i (LightGBM convention)
        self.group = None if group is None else np.ascontiguousarray(group, np.int64)
        if self.group is not None and int(self.group.sum()) != self.num_rows:
            raise ValueError("group sizes must sum to num_rows")
        self._device_cache = None

    def device_arrays(self):
        """Memoized device copies of (X_binned, y, weight).

        Repeated ``train`` calls on one Dataset skip the host->device
        upload — 280 MB of binned matrix at Higgs-10M scale.  The arrays
        are treated as
        immutable once uploaded; mutate ``X_binned``/``y`` in place and the
        cache goes stale (construct a new Dataset instead)."""
        if self._device_cache is None:
            import jax.numpy as jnp

            self._device_cache = (
                jnp.asarray(self.X_binned),
                None if self.y is None else jnp.asarray(self.y),
                None if self.weight is None else jnp.asarray(self.weight),
            )
        return self._device_cache

    @classmethod
    def from_binned(
        cls,
        X_binned: np.ndarray,
        mapper: BinMapper,
        y: Optional[np.ndarray] = None,
        *,
        weight: Optional[np.ndarray] = None,
        group: Optional[np.ndarray] = None,
        categorical_features: Sequence[int] = (),
    ) -> "Dataset":
        """Dataset over an already-binned matrix (streaming/out-of-core
        ingest) — runs the same label/weight/group validation as __init__."""
        ds = cls.__new__(cls)
        ds.categorical_features = tuple(int(c) for c in categorical_features)
        ds.mapper = mapper
        ds.X_binned = np.ascontiguousarray(X_binned, mapper.bin_dtype)
        ds.num_rows, ds.num_features = ds.X_binned.shape
        ds._attach_targets(y, weight, group)
        return ds

    def bind(self, X: np.ndarray, y: Optional[np.ndarray] = None, **kw) -> "Dataset":
        """Bin new data (validation/test) through this dataset's frozen mapper."""
        return Dataset(X, y, mapper=self.mapper, categorical_features=self.categorical_features, **kw)

    @property
    def query_offsets(self) -> Optional[np.ndarray]:
        if self.group is None:
            return None
        return np.concatenate([[0], np.cumsum(self.group)]).astype(np.int64)


def _sketch_csr(indptr, indices, values, num_features, max_bins, categorical_features):
    """Sketch from CSR by densifying per-feature value lists + implicit zeros.

    Implicit zeros participate in the sketch (they dominate Criteo-style
    data), represented by injecting the exact count of zeros per feature.
    """
    n = indptr.shape[0] - 1
    cols = np.asarray(indices)
    vals = np.asarray(values, np.float32)
    order = np.argsort(cols, kind="stable")
    cols_s, vals_s = cols[order], vals[order]
    bounds = np.searchsorted(cols_s, np.arange(num_features + 1))
    from dryad_tpu.data.sketch import FeatureBins, _sketch_categorical, _sketch_numerical  # noqa: PLC0415

    cats = frozenset(int(c) for c in categorical_features)
    feats: list[FeatureBins] = []
    for f in range(num_features):
        explicit = vals_s[bounds[f] : bounds[f + 1]]
        n_zero = n - explicit.size
        if n_zero > 0:
            col = np.concatenate([explicit, np.zeros(n_zero, np.float32)])
        else:
            col = explicit
        feats.append(_sketch_categorical(col, max_bins) if f in cats else _sketch_numerical(col, max_bins))
    return BinMapper(feats, max_bins)
