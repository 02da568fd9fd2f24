"""The algorithm's work in one boosting iteration, from shapes alone.

What histogram gradient boosting needs, whatever implements it (so not the
one-hot products an MXU formulation spends, and nothing a compiler reports):

* one histogram pass a level over the rows of the level: every row-feature
  bin index is read (``bin_bytes`` each), the row's gradient and hessian are
  read (8 bytes a row), and each row-feature makes two additions (gradient
  and hessian into its bin);
* the histograms are written once a level: ``nodes * F * B`` pairs of
  float32 (small beside the reads, counted for completeness).

With histogram subtraction only the smaller child of every split is
accumulated, at most half the rows from level 1 on; the count takes that
floor, so a share of it cannot pass 100% by skipping work the algorithm
allows to skip.  ``K`` trees an iteration (classes) multiply everything.
"""

from __future__ import annotations


def hist_pass(rows: int, features: int, bins: int, nodes: int, bin_bytes: int = 1) -> dict:
    """Bytes moved and additions made by one histogram pass over ``rows``."""
    return {
        "bytes": rows * (features * bin_bytes + 8) + nodes * features * bins * 8,
        "ops": 2 * rows * features,
    }


def iteration(rows: int, features: int, bins: int, depth: int, trees: int = 1,
              bin_bytes: int = 1) -> dict:
    """One boosting iteration's histogram work: level 0 over all rows, each
    deeper level over half of them (the smaller children)."""
    total = {"bytes": 0, "ops": 0}
    for level in range(depth):
        level_rows = rows if level == 0 else rows // 2
        nodes = 2 ** level if level == 0 else 2 ** (level - 1)
        one = hist_pass(level_rows, features, bins, nodes, bin_bytes)
        total["bytes"] += one["bytes"]
        total["ops"] += one["ops"]
    return {k: v * trees for k, v in total.items()}


def of_shape(shape: dict) -> dict:
    """``iteration`` for a run's own shape, as the runner states it."""
    return iteration(shape["rows"], shape["features"], shape["bins"], shape["depth"],
                     shape["trees"], shape["bin_bytes"])


def least_seconds(work: dict, peaks: dict) -> dict:
    """The least time the chip needs for ``work`` and which bound sets it.
    The additions are float32 on the vector units, which the table of peaks
    does not rate; the bfloat16 matrix peak is an upper bound on them, so
    the compute bound here is a floor and the memory bound decides."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = work["ops"] / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "memory" if by_bytes >= by_ops else "compute"}


def share(work: dict, peaks: dict, measured_seconds: float):
    """Per cent of the roofline reached; None where nothing was measured."""
    if not measured_seconds or measured_seconds <= 0:
        return None
    return 100.0 * least_seconds(work, peaks)["seconds"] / measured_seconds
