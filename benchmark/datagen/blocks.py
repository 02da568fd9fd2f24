"""Seeded tabular data in row blocks, drawn by a few threads.

Every feature is drawn as a bin index 0..254, uniform, which is what a
quantile sketch makes of any continuous feature: equal-frequency bins.  The
program is handed the indices as float32 values and sketches them like any
other column (255 distinct values, one bin each, edges at the midpoints);
the reference works on the same values and needs no table of the program's.
The label's signal is a function of ``z = ndtri((q + 0.5) / 255)``, the
standard-normal score of the bin's centre, so the shapes are those of
``dryad_tpu.datasets.higgs_like`` and ``epsilon_like`` (which this copies;
the originals draw float64 normals for the whole matrix at once).

Block ``b`` of a seed draws from ``Philox(key=[seed, b])``: the same seed
gives the same rows whatever the thread count.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy.special import ndtri

LEVELS = 255
BLOCK_VALUES = 4_000_000       # values of q drawn per block

Z_OF_BIN = ndtri((np.arange(LEVELS) + 0.5) / LEVELS).astype(np.float32)


def block_rng(seed: int, stream: int, block: int) -> np.random.Generator:
    return np.random.Generator(np.random.Philox(
        key=[int(seed) % (1 << 63), (int(stream) << 32) | int(block)]))


def draw(seed: int, stream: int, rows: int, features: int, label_of_block,
         threads: int = 8):
    """``(q uint8 [rows, features], y float32 [rows])``.  ``label_of_block``
    takes ``(rng, q_block)`` and returns the block's labels."""
    q = np.empty((rows, features), np.uint8)
    y = np.empty((rows,), np.float32)
    step = max(1, BLOCK_VALUES // features)
    starts = list(range(0, rows, step))

    def one(b):
        lo, hi = starts[b], min(starts[b] + step, rows)
        rng = block_rng(seed, stream, b)
        q[lo:hi] = rng.integers(0, LEVELS, size=(hi - lo, features), dtype=np.uint8)
        y[lo:hi] = label_of_block(rng, q[lo:hi])

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one, range(len(starts))))
    return q, y
