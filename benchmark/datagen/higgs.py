"""Higgs-shaped binary task: 28 dense features, a nonlinear signal.

Copy of ``dryad_tpu.datasets.higgs_like``'s signal (linear part over all
features, ``sin(z0 z1)``, ``z2 z3``, ``z4^2``, ``|z5|``), standardised by
its analytic mean and deviation instead of the sample's so that blocks are
independent.
"""

from __future__ import annotations

import numpy as np

from benchmark.datagen.blocks import Z_OF_BIN, block_rng, draw

TASK = "binary"


def make(seed: int, rows: int, features: int, stream: int = 0):
    w = block_rng(seed, 99, 0).standard_normal(features).astype(np.float32)
    mean = 0.7 - 0.5 * np.sqrt(2.0 / np.pi)
    var = float(w @ w) + 0.81 * 0.5 + 0.64 + 0.49 * 2.0 + 0.25 * (1.0 - 2.0 / np.pi)
    scale = np.float32(1.5 / np.sqrt(var))

    def label(rng, q):
        z = Z_OF_BIN[q]
        s = (z @ w + 0.9 * np.sin(z[:, 0] * z[:, 1]) + 0.8 * (z[:, 2] * z[:, 3])
             + 0.7 * np.square(z[:, 4]) - 0.5 * np.abs(z[:, 5]))
        p = 1.0 / (1.0 + np.exp(-scale * (s - np.float32(mean))))
        return (rng.random(q.shape[0], dtype=np.float32) < p).astype(np.float32)

    return draw(seed, stream, rows, features, label)
