"""Epsilon-shaped regression: 2000 dense features, one in twenty informative.

Copy of ``dryad_tpu.datasets.epsilon_like``'s signal (sparse linear part,
``0.5 sin(z0) z1``, noise 0.1); only the informative columns are mapped to
their normal scores.
"""

from __future__ import annotations

import numpy as np

from benchmark.datagen.blocks import Z_OF_BIN, block_rng, draw

TASK = "regression"


def make(seed: int, rows: int, features: int, stream: int = 0):
    rng0 = block_rng(seed, 99, 0)
    w = (rng0.standard_normal(features) * (rng0.random(features) < 0.05)).astype(np.float32)
    cols = np.flatnonzero(w)
    wc = w[cols]

    def label(rng, q):
        y = Z_OF_BIN[q[:, cols]] @ wc
        y += 0.5 * np.sin(Z_OF_BIN[q[:, 0]]) * Z_OF_BIN[q[:, 1]]
        y += 0.1 * rng.standard_normal(q.shape[0], dtype=np.float32)
        return y

    return draw(seed, stream, rows, features, label)
