"""Airline on-time shaped task (ASA Data Expo 2009, LightGBM's Expo row): 8
raw columns, 6 of them categorical, about 19 % of departures delayed.

Columns, in the order of the configuration's file:

    0 Month (12 values)      1 DayofMonth (31)     2 DayOfWeek (7)
    3 DepTime (numeric)      4 UniqueCarrier (22)  5 Origin (313)
    6 Dest (313)             7 Distance (numeric)

A categorical column holds integer codes ``0..K-1``; the two numeric ones are
drawn as bin indices 0..254, as every other family draws its features
(``blocks.py``).  Carriers and airports are hub-skewed: the value of rank
``r`` (1-based) is drawn with weight ``1 / r`` (Zipf, s = 1), and ranks are
dealt to codes by a permutation fixed by the seed, so a code's size says
nothing of its number.  Under the sketch's rule (254 categories kept a
column, by count) the 59 rarest airports of each airport column pool into
one overflow bin: about 3.3 % of the rows.

The label's logit is a sum of per-value random effects of every categorical
column (normal, drawn once a seed, so not monotone in the code: a threshold
on codes cannot isolate them), two interactions (Origin x the departure's
hour, UniqueCarrier x Month), a rising effect of DepTime and a small one of
Distance, shifted so that about 0.19 of the rows are positive at the
median seed (the effects of the most frequent codes move it by seed: 0.14
to 0.24 over forty seeds).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.datagen.blocks import BLOCK_VALUES, LEVELS, block_rng

TASK = "binary"
FEATURES = 8
CATEGORICAL = (0, 1, 2, 4, 5, 6)
CARDINALITY = {0: 12, 1: 31, 2: 7, 4: 22, 5: 313, 6: 313}
POSITIVE_RATE = 0.19
# the spread (standard deviation of the logit's part) of each column's
# random effects, of the two interactions, and the numeric columns' slopes
EFFECT_SD = {0: 0.3, 1: 0.15, 2: 0.22, 4: 0.45, 5: 0.55, 6: 0.45}
ORIGIN_HOUR_SD = 0.40
CARRIER_MONTH_SD = 0.30
DEPTIME_SLOPE = 1.4
DISTANCE_SLOPE = 0.15
# E[sigmoid(s + SHIFT)] = POSITIVE_RATE for the logit's spread at the
# seeds drawn: solved once, by bisection over a million rows
SHIFT = -1.83
# days of a 28/29-day month, of a 30-day one and of a 31-day one in a year
_DAY_WEIGHT = np.array([12.0] * 28 + [11.25, 11.0, 7.0])


def _zipf_weights(k: int) -> np.ndarray:
    w = 1.0 / np.arange(1, k + 1)
    return w / w.sum()


class _Tables:
    """Everything a seed fixes: the codes' ranks and the effects."""

    def __init__(self, seed: int):
        rng = block_rng(seed, 99, 0)
        self.rank_to_code = {f: rng.permutation(k) for f, k in CARDINALITY.items()}
        self.effect = {f: rng.standard_normal(k) * EFFECT_SD[f] for f, k in CARDINALITY.items()}
        self.origin_hour = rng.standard_normal((CARDINALITY[5], 24)) * ORIGIN_HOUR_SD
        self.carrier_month = rng.standard_normal((CARDINALITY[4], 12)) * CARRIER_MONTH_SD
        self.weights = {0: np.full(12, 1 / 12.0), 1: _DAY_WEIGHT / _DAY_WEIGHT.sum(),
                        2: np.full(7, 1 / 7.0), 4: _zipf_weights(22),
                        5: _zipf_weights(313), 6: _zipf_weights(313)}

    def draw_codes(self, rng, f: int, n: int) -> np.ndarray:
        """Codes of column ``f``: a rank by the column's weights, then the
        rank's code (Month, DayofMonth, DayOfWeek keep code = rank)."""
        cdf = np.cumsum(self.weights[f])
        rank = np.minimum(np.searchsorted(cdf, rng.random(n), side="right"), cdf.size - 1)
        if f in (4, 5, 6):
            return self.rank_to_code[f][rank]
        return rank

    def logit(self, q: np.ndarray) -> np.ndarray:
        s = np.zeros(q.shape[0], np.float64)
        for f in CATEGORICAL:
            s += self.effect[f][q[:, f]]
        hour = (q[:, 3].astype(np.int64) * 24) // LEVELS
        s += self.origin_hour[q[:, 5], hour]
        s += self.carrier_month[q[:, 4], q[:, 0]]
        s += DEPTIME_SLOPE * (q[:, 3] / (LEVELS - 1.0) - 0.5)
        s += DISTANCE_SLOPE * (q[:, 7] / (LEVELS - 1.0) - 0.5)
        return s + SHIFT


def make(seed: int, rows: int, features: int = FEATURES, stream: int = 0, threads: int = 8):
    """``(q uint16 [rows, 8], y float32 [rows])``; block ``b`` draws from
    ``Philox(key=[seed, stream << 32 | b])``, so the same seed gives the
    same rows whatever the thread count."""
    if features != FEATURES:
        raise ValueError(f"the Expo table has {FEATURES} columns, not {features}")
    tables = _Tables(seed)
    q = np.empty((rows, FEATURES), np.uint16)
    y = np.empty((rows,), np.float32)
    step = max(1, BLOCK_VALUES // FEATURES)
    starts = list(range(0, rows, step))

    def one(b):
        lo, hi = starts[b], min(starts[b] + step, rows)
        rng = block_rng(seed, stream, b)
        n = hi - lo
        for f in range(FEATURES):
            if f in CARDINALITY:
                q[lo:hi, f] = tables.draw_codes(rng, f, n)
            else:
                q[lo:hi, f] = rng.integers(0, LEVELS, size=n)
        p = 1.0 / (1.0 + np.exp(-tables.logit(q[lo:hi])))
        y[lo:hi] = (rng.random(n) < p).astype(np.float32)

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one, range(len(starts))))
    return q, y
