"""Criteo-shaped click task: 67 dense columns, about 3.4 % positives.

The source's table (LightGBM ``docs/Experiments.rst``, Parallel Experiment) is
13 integer columns and, for each of 26 categorical ones, the click-through
rate and the count of its value: dense numeric columns, a rare positive.
Every column is drawn here as a bin index 0..254 (``blocks.draw``: what an
equal-frequency sketch makes of a continuous column; no missing value), and
the label follows ``higgs``'s signal over them (a linear part over every
column, ``sin(z0 z1)``, ``z2 z3``, ``z4^2``, ``|z5|``) with the logit shifted
so that about 0.034 of the rows are clicks, the terabyte logs' rate as
recalled.  At that rate a tree's first gradients are 0.034 - y: hessians of
0.033 a row, so ``min_sum_hessian_in_leaf`` 1e-3 never binds and
``min_data_in_leaf`` 20 does.
"""

from __future__ import annotations

import numpy as np

from benchmark.datagen.blocks import Z_OF_BIN, block_rng, draw

TASK = "binary"
POSITIVE_RATE = 0.034
# E[sigmoid(1.5 n + SHIFT)] = POSITIVE_RATE for a standard normal n
# (the standardised signal is close to one): solved once, by bisection
SHIFT = -4.303


def make(seed: int, rows: int, features: int, stream: int = 0):
    w = block_rng(seed, 99, 0).standard_normal(features).astype(np.float32)
    mean = 0.7 - 0.5 * np.sqrt(2.0 / np.pi)
    var = float(w @ w) + 0.81 * 0.5 + 0.64 + 0.49 * 2.0 + 0.25 * (1.0 - 2.0 / np.pi)
    scale = np.float32(1.5 / np.sqrt(var))

    def label(rng, q):
        z = Z_OF_BIN[q]
        s = (z @ w + 0.9 * np.sin(z[:, 0] * z[:, 1]) + 0.8 * (z[:, 2] * z[:, 3])
             + 0.7 * np.square(z[:, 4]) - 0.5 * np.abs(z[:, 5]))
        p = 1.0 / (1.0 + np.exp(-(scale * (s - np.float32(mean)) + np.float32(SHIFT))))
        return (rng.random(q.shape[0], dtype=np.float32) < p).astype(np.float32)

    return draw(seed, stream, rows, features, label)
