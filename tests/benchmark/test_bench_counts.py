"""Operations and bytes from shapes, by hand for both configurations, and the
roofline share: a count's own least time reads 100 %, anything longer less."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.counts import gbdt_iteration as counts  # noqa: E402
from benchmark.harness import device  # noqa: E402

PEAKS = device.peaks("TPU v5 lite")

# Higgs: level 0 reads 10M x (28 + 8) bytes and writes 1 x 28 x 256 x 8; levels
# 1..7 read 5M x 36 each and write 2^(l-1) x 28 x 256 x 8
HIGGS_BYTES = (10_000_000 * 36 + 28 * 256 * 8
               + sum(5_000_000 * 36 + 2 ** (lv - 1) * 28 * 256 * 8 for lv in range(1, 8)))
HIGGS_OPS = 2 * 28 * (10_000_000 + 7 * 5_000_000)
# Epsilon: 400k x (2000 + 8) at level 0, 200k x 2008 at levels 1..5
EPS_BYTES = (400_000 * 2008 + 2000 * 256 * 8
             + sum(200_000 * 2008 + 2 ** (lv - 1) * 2000 * 256 * 8 for lv in range(1, 6)))
EPS_OPS = 2 * 2000 * (400_000 + 5 * 200_000)

SHAPES = {
    "higgs10m_d8": ((10_000_000, 28, 256, 8), HIGGS_BYTES, HIGGS_OPS),
    "epsilon400k_d6": ((400_000, 2000, 256, 6), EPS_BYTES, EPS_OPS),
}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_iteration_counts_by_hand(name):
    shape, want_bytes, want_ops = SHAPES[name]
    work = counts.iteration(*shape)
    assert work == {"bytes": want_bytes, "ops": want_ops}


def test_hist_pass_by_hand():
    assert counts.hist_pass(1000, 10, 256, 4) == {"bytes": 1000 * 18 + 4 * 10 * 256 * 8,
                                                  "ops": 20000}
    assert counts.hist_pass(1000, 10, 256, 4, bin_bytes=2)["bytes"] == 1000 * 28 + 4 * 10 * 256 * 8


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_least_time_reads_100_and_longer_reads_less(name):
    work = counts.iteration(*SHAPES[name][0])
    least = counts.least_seconds(work, PEAKS)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(work["bytes"] / 819e9)
    assert counts.share(work, PEAKS, least["seconds"]) == pytest.approx(100.0)
    assert counts.share(work, PEAKS, 2 * least["seconds"]) == pytest.approx(50.0)
    assert counts.share(work, PEAKS, 1000 * least["seconds"]) < 1.0
    assert counts.share(work, PEAKS, 0.0) is None


def test_trees_per_iteration_multiply():
    one = counts.iteration(1000, 8, 256, 3)
    seven = counts.iteration(1000, 8, 256, 3, trees=7)
    assert seven == {k: 7 * v for k, v in one.items()}
