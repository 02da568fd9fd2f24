"""The window clock, driven as the trainer drives it, on a clock of its own."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness.clock import StopJob, WindowClock  # noqa: E402


class FakeTime:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def drive(clock, now, chunk_iters=5, chunk_seconds=12.0, gap=0.5, max_chunks=50):
    """Chunks of ``chunk_iters`` iterations, each ``chunk_seconds`` long,
    ``gap`` seconds of host work between them; returns at StopJob."""
    it = 0
    for _ in range(max_chunks):
        try:
            clock.on_dispatch("dispatch", it)
        except StopJob:
            return True
        clock.on_dispatch("fetch", it)        # other sites are ignored
        now.t += chunk_seconds
        for j in range(it, it + chunk_iters):
            clock.on_iter(j, {"iteration": j, "valid_auc": 0.5 + j * 1e-3, "ch_max_effective": 0})
        it += chunk_iters
        now.t += gap
    return False


def test_window_opens_after_warmup_and_closes_on_a_chunk_boundary():
    now = FakeTime()
    opened, closed = [], []
    clock = WindowClock(51.0, 2, on_open=lambda: opened.append(now.t),
                        on_close=lambda: closed.append(now.t), now=now)
    assert drive(clock, now)
    # chunks complete at 112, 124.5, 137, ...: open at 124.5, close at the first
    # completion >= 175.5, which is 187 (five chunks later)
    assert opened == [124.5] and closed == [187.0]
    assert clock.window_s == pytest.approx(62.5)
    assert clock.window_iters == 25 and [c["n"] for c in clock.window_chunks] == [5] * 5
    assert clock.iters_done == 35
    assert clock.evals[34] == pytest.approx(0.534) and len(clock.evals) == 35


def test_traced_window_is_a_number_of_chunks():
    now = FakeTime()
    clock = WindowClock(0.0, 2, min_chunks=2, now=now)
    assert drive(clock, now)
    assert clock.window_iters == 10 and clock.window_s == pytest.approx(25.0)


def test_a_job_that_ends_early_has_no_closed_window():
    now = FakeTime()
    clock = WindowClock(51.0, 2, now=now)
    assert not drive(clock, now, max_chunks=3)
    assert clock.t_close is None and clock.window_s == 0.0


def test_per_iteration_dispatch_counts_one_chunk_an_iteration():
    now = FakeTime()
    clock = WindowClock(1.0, 2, now=now)
    for j in range(6):
        clock._cur = None
        now.t += 1.0
        clock.on_iter(j, {"valid_rmse": 1.0})
    assert clock.t_open == 102.0 and clock.t_close == 103.0 and clock.window_iters == 1
