"""Production-line shaped task (Bosch Production Line Performance, Kaggle
2016; LightGBM's Bosch row): 968 numeric columns, about 81 % of the cells
empty, about 0.58 % of the parts failing.

Columns belong to stations and stations to four lines, as the competition's
``train_numeric.csv`` names them (``L<line>_S<station>_F<feature>``):

    line 0: stations  0-23, 168 columns     line 1: stations 24-25, 513 columns
    line 2: stations 26-28,  42 columns     line 3: stations 29-51, 245 columns

a line's columns dealt to its stations in order, as evenly as they go.  A
part passes through some stations only: each row takes one of ``PATHS``
paths, drawn with weights the seed fixes, and a path is a set of stations.
A station's columns are present together or missing together, so a row's
empty cells come in whole stations.  Most paths run through line 0 and then
line 3; the others through one of line 1's two stations.  The paths are
drawn from the seed and then evened out a station at a time until the
expected share of empty cells is ``MISSING_SHARE`` within ``SHARE_TOL``.

A present cell holds a value 1..63, uniform: ``LEVELS`` equal-frequency
values, what a quantile sketch of 63 bins makes of any continuous column.
``q`` holds 0 where the cell is empty; the program is handed ``NaN`` there
(``as_float``), so that its sketch puts the value ``v`` in bin ``v`` and the
empty cell in bin 0, the bin the reference reads.

The label's logit is an effect of the path (normal, drawn once a seed),
plus slopes of a few columns of different stations where they are present
(``INFORMATIVE``, each of random sign), shifted so that about
``POSITIVE_RATE`` of the rows are positive: the shift is solved once a
seed by bisection over a sample of the seed's own rows.  Whether an empty
cell should go with a column's low or its high values then depends on the
paths that skip its station, and differs between columns and nodes.

Block ``b`` of a seed draws from ``Philox(key=[seed, stream << 32 | b])``
(``blocks.block_rng``): the same seed gives the same rows whatever the
thread count, and stream 1 (the valid rows) is independent of stream 0.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.datagen.blocks import BLOCK_VALUES, block_rng

TASK = "binary"
FEATURES = 968
LINE_COLUMNS = (168, 513, 42, 245)
LINE_STATIONS = (24, 2, 3, 23)            # 52 stations
LEVELS = 63                               # present values 1..63; 0 is an empty cell
PATHS = 24
L1_PATHS = 6                              # paths through one of line 1's stations
MISSING_SHARE = 0.81
SHARE_TOL = 0.004
POSITIVE_RATE = 0.0058
PATH_EFFECT_SD = 0.9
INFORMATIVE = 12                          # columns whose present values move the logit
SLOPE_SD = 1.3
CALIBRATION_ROWS = 200_000


def station_columns() -> list[np.ndarray]:
    """The columns of each of the 52 stations, in order."""
    out, start = [], 0
    for cols, stations in zip(LINE_COLUMNS, LINE_STATIONS):
        for part in np.array_split(np.arange(start, start + cols), stations):
            out.append(part)
        start += cols
    return out


STATION_COLUMNS = station_columns()
STATION_LINE = np.repeat(np.arange(4), LINE_STATIONS)


class _Tables:
    """Everything a seed fixes: the paths, their weights, the effects and
    the shift."""

    def __init__(self, seed: int):
        rng = block_rng(seed, 99, 0)
        sizes = np.array([c.size for c in STATION_COLUMNS])
        visits = np.zeros((PATHS, len(STATION_COLUMNS)), bool)
        l1 = np.flatnonzero(STATION_LINE == 1)
        free = np.flatnonzero(STATION_LINE != 1)            # lines 0, 2 and 3
        for k in range(PATHS):
            if k < L1_PATHS:
                visits[k, l1[k % l1.size]] = True
                visits[k, free] = rng.random(free.size) < 0.08
            else:
                visits[k, free] = rng.random(free.size) < 0.4
        w = 1.0 / np.arange(1, PATHS + 1) ** 0.7
        self.weight = rng.permutation(w / w.sum())
        # even the expected empty share out, a line-0/2/3 station at a time
        target = (1.0 - MISSING_SHARE) * FEATURES
        for _ in range(10_000):
            present = float(self.weight @ (visits * sizes).sum(axis=1))
            if abs(present - target) <= SHARE_TOL * FEATURES:
                break
            k = int(rng.integers(PATHS))
            s = int(rng.choice(free))
            visits[k, s] = present < target
        else:
            raise RuntimeError("the paths could not be evened out")
        self.visits = visits
        self.present = np.zeros((PATHS, FEATURES), bool)
        for k in range(PATHS):
            for s in np.flatnonzero(visits[k]):
                self.present[k, STATION_COLUMNS[s]] = True
        self.path_effect = rng.standard_normal(PATHS) * PATH_EFFECT_SD
        stations = rng.choice(len(STATION_COLUMNS), INFORMATIVE, replace=False)
        self.informative = np.array([rng.choice(STATION_COLUMNS[s]) for s in stations])
        self.slope = rng.standard_normal(INFORMATIVE) * SLOPE_SD
        self.shift = 0.0
        crng = block_rng(seed, 98, 0)
        path = self.draw_paths(crng, CALIBRATION_ROWS)
        vals = crng.integers(1, LEVELS + 1, size=(CALIBRATION_ROWS, INFORMATIVE))
        s = self._logit(path, vals * self.present[path][:, self.informative])
        lo, hi = -20.0, 5.0
        for _ in range(60):
            mid = 0.5 * (lo + hi)
            lo, hi = (mid, hi) if np.mean(1 / (1 + np.exp(-(s + mid)))) < POSITIVE_RATE \
                else (lo, mid)
        self.shift = 0.5 * (lo + hi)

    def draw_paths(self, rng, n: int) -> np.ndarray:
        cdf = np.cumsum(self.weight)
        return np.minimum(np.searchsorted(cdf, rng.random(n), side="right"), PATHS - 1)

    def _logit(self, path: np.ndarray, vals: np.ndarray) -> np.ndarray:
        """``vals``: the informative columns' values, 0 where empty."""
        z = np.where(vals > 0, (vals - (LEVELS + 1) / 2.0) / (LEVELS / 2.0), 0.0)
        return self.path_effect[path] + z @ self.slope + self.shift

    def missing_share(self) -> float:
        return 1.0 - float(self.weight @ self.present.sum(axis=1)) / FEATURES


def make(seed: int, rows: int, features: int = FEATURES, stream: int = 0, threads: int = 8):
    """``(q uint8 [rows, 968], y float32 [rows])``, 0 in ``q`` an empty cell."""
    if features != FEATURES:
        raise ValueError(f"the Bosch table has {FEATURES} columns, not {features}")
    tables = _Tables(seed)
    q = np.empty((rows, FEATURES), np.uint8)
    y = np.empty((rows,), np.float32)
    step = max(1, BLOCK_VALUES // FEATURES)
    starts = list(range(0, rows, step))

    def one(b):
        lo, hi = starts[b], min(starts[b] + step, rows)
        rng = block_rng(seed, stream, b)
        path = tables.draw_paths(rng, hi - lo)
        vals = rng.integers(1, LEVELS + 1, size=(hi - lo, FEATURES), dtype=np.uint8)
        np.multiply(vals, tables.present[path], out=q[lo:hi])
        p = 1.0 / (1.0 + np.exp(-tables._logit(path, q[lo:hi, tables.informative])))
        y[lo:hi] = (rng.random(hi - lo) < p).astype(np.float32)

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one, range(len(starts))))
    return q, y


def as_float(q: np.ndarray) -> np.ndarray:
    """The table as the program is handed it: float32 values, NaN where empty."""
    x = q.astype(np.float32)
    x[q == 0] = np.nan
    return x
