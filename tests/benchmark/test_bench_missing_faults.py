"""Faults planted underneath the real path of the missing-value cell
(``bosch1m_leaf255.job_missing``), each driven through the cell's own runner
at rehearsal size on the CPU and read as not correct: the planes dropped (the
program told the table has no empty cell, so every empty cell goes left),
the empty cells of one level routed against the direction their nodes
learned, and the eval walk ignoring ``default_left``."""

import argparse
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchmark.harness import manifest as mf  # noqa: E402

CELL = "bosch1m_leaf255.job_missing"
SEED = 3800000011
MISROUTED_LEVEL = 1


@pytest.fixture(scope="module")
def cell():
    return mf.Cell(mf.load(), CELL)


def _clear():
    import jax

    jax.clear_caches()


@pytest.fixture()
def fresh_programs():
    """A planted fault changes what the grower traces: no program compiled
    before it may serve it, and none compiled with it may outlive it."""
    _clear()
    yield
    _clear()


def drive(cell, seed=SEED):
    from benchmark.runners import train_job_missing

    args = argparse.Namespace(seed=seed, seconds=0.5, trace=0, rehearse_cpu=True)
    return train_job_missing.run(cell, args, time.perf_counter())


def over_limits(out):
    return sorted(k for k, c in out["compared"].items()
                  if c["value"] is None or not c["value"] <= c["limit"])


def planes_dropped(monkeypatch):
    """``learn_missing`` off: the program is told the table has no empty
    cell, scans plane 0 alone, and every empty cell goes left."""
    import dryad_tpu as dryad

    monkeypatch.setattr(dryad.Dataset, "has_missing", property(lambda self: False))


def one_level_misrouted(monkeypatch):
    """At the expansion's level ``MISROUTED_LEVEL`` the rows whose cell is
    empty are routed as the largest value, so right whatever their node
    learned: against it at every node that learned "left".  The level loop
    is unrolled so that the one level can be told apart; the histograms and
    the tree's directions are the program's own."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.engine import levelwise

    level = {"d": None}
    real_loop, real_select = jax.lax.fori_loop, levelwise.select_bins

    def loop(lower, upper, body, init, **kw):
        if getattr(body, "__name__", "") != "level_body":
            return real_loop(lower, upper, body, init, **kw)
        state = init
        for d in range(lower, upper):
            level["d"] = d
            state = body(jnp.int32(d), state)
        level["d"] = None
        return state

    def select(Xb, rf):
        bins = real_select(Xb, rf)
        if level["d"] != MISROUTED_LEVEL:
            return bins
        return jnp.where(bins == 0, jnp.int32(1 << 12), bins)

    monkeypatch.setattr(jax.lax, "fori_loop", loop)
    monkeypatch.setattr(levelwise, "select_bins", select)


def eval_ignores_direction(monkeypatch):
    """The training eval's walk reads every node's ``default_left`` as set:
    the valid rows' empty cells all go left."""
    import jax.numpy as jnp

    from dryad_tpu.engine import train

    real = train.pack_node_words_device

    def pack(feature, threshold, left, right, default_left, is_cat):
        return real(feature, threshold, left, right, jnp.ones_like(default_left), is_cat)

    monkeypatch.setattr(train, "pack_node_words_device", pack)


FAULTS = {"planes_dropped": (planes_dropped, {"split_flip_share", "order_gain_gap"}),
          "one_level_misrouted": (one_level_misrouted, {"window_cover_gap"}),
          "eval_ignores_direction": (eval_ignores_direction, {"valid_metric_gap"})}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_underneath_the_real_path_is_not_correct(cell, monkeypatch, fresh_programs,
                                                         fault):
    plant, must = FAULTS[fault]
    plant(monkeypatch)
    out = drive(cell)
    assert out["correct"] is False, out["compared"]
    assert must <= set(over_limits(out)), out["compared"]
