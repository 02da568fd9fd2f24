"""MSLR-WEB30K-shaped ranking task: 136 dense features, graded relevance 0-4,
rows in ragged query groups.

Copy of ``dryad_tpu.datasets.mslr_like``'s signal (a linear part over all
features with weights of deviation 0.3, a bias per query so that relevance
means something only inside a query, noise 0.7), with two departures that
the configuration's file lists under ``assumed``:

* query lengths are a rounded lognormal (median 100, sigma 0.6) clipped to
  ``min..max``, drawn until the rows are used up; the last query takes what is
  left, and one query is set to ``max`` so that the longest is the data set's
  own (``mslr_like`` draws lengths uniform in 5..120: no tail);
* the grades' thresholds are the normal quantiles of the shares 0.52 / 0.32 /
  0.13 / 0.02 / 0.01 at the score's analytic deviation instead of the
  sample's quantiles, so that row blocks are independent.

``make`` returns ``(q, y, lengths)``; the rows of a query are contiguous and
``lengths`` sums to ``rows``.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy.special import ndtri

from benchmark.datagen.blocks import Z_OF_BIN, block_rng, draw

TASK = "lambdarank"
SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)           # grades 0..4
QUERY_LENGTH = {"min": 1, "max": 1251, "median": 100, "sigma": 0.6}
ROWS_A_PASS = 1 << 18


def query_lengths(seed: int, stream: int, rows: int, spec: dict | None = None) -> np.ndarray:
    """int64 lengths that sum to ``rows``, the longest exactly ``spec["max"]``
    (where the rows allow one)."""
    spec = {**QUERY_LENGTH, **(spec or {})}
    lo, hi = int(spec["min"]), min(int(spec["max"]), rows)
    rng = block_rng(seed, 97, stream)
    mean = float(np.exp(np.log(spec["median"]) + spec["sigma"] ** 2 / 2))
    out = [np.array([hi], np.int64)]              # the data set's longest query
    left = rows - hi
    while left > 0:
        n = int(left / mean * 1.2) + 16
        draw_ = np.rint(rng.lognormal(np.log(spec["median"]), spec["sigma"], n))
        draw_ = np.clip(draw_, lo, hi).astype(np.int64)
        keep = np.searchsorted(np.cumsum(draw_), left, side="right")
        if keep == 0:                             # the next draw is longer than what is left
            out.append(np.array([left], np.int64))
            break
        out.append(draw_[:keep])
        left -= int(draw_[:keep].sum())
    lengths = np.concatenate(out)
    # the longest query goes to a seeded place, not to the front
    k = int(rng.integers(0, lengths.size))
    lengths[[0, k]] = lengths[[k, 0]]
    return lengths


def make(seed: int, rows: int, features: int, stream: int = 0, query_length: dict | None = None):
    """``(q uint8 [rows, features], y float32 [rows] in 0..4, lengths int64 [Q])``."""
    rng0 = block_rng(seed, 99, 0)
    w = (rng0.standard_normal(features) * 0.3).astype(np.float32)
    lengths = query_lengths(seed, stream, rows, query_length)
    bias = block_rng(seed, 98, stream).standard_normal(lengths.size).astype(np.float32)
    sd = np.sqrt(float(w @ w) + 1.0 + 0.49)
    cuts = (ndtri(np.cumsum(SHARES)[:-1]) * sd).astype(np.float32)

    def noise(rng, qb):
        return np.float32(0.7) * rng.standard_normal(qb.shape[0], dtype=np.float32)

    q, s = draw(seed, stream, rows, features, noise)
    s += np.repeat(bias, lengths)

    def linear(lo):
        hi = min(lo + ROWS_A_PASS, rows)
        s[lo:hi] += Z_OF_BIN[q[lo:hi]] @ w

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(linear, range(0, rows, ROWS_A_PASS)))
    y = np.digitize(s, cuts).astype(np.float32)
    return q, y, lengths
