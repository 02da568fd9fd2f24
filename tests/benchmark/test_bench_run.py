"""The one command, end to end on the CPU: a rehearsal of the headline cell,
the refusal without a chip, and a third cell added as data only.  The two
tests that train are marked ``slow`` (see ``test_bench_correct.py``)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from bench_fixtures import ROOT, copy_with_third_cell, run_cli  # noqa: E402

KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def last_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_rehearsal_runs_the_headline_cell_end_to_end():
    done = run_cli(ROOT, "--workload", "higgs10m_d8.job", "--seed", "2147483999",
                   "--seconds", "2", "--trace", "0", "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    line = last_line(done.stdout)
    assert set(line) == KEYS and list(line)[-1] == "compared"
    assert line["failed"] == 0 and line["attempted"] >= 5
    assert line["metrics"] == {}, "a rehearsal never prints a device metric"
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # judged against the cell's one set of limits, set at 10M rows: the exact
    # numbers hold at any size, the others are printed beside their limits
    assert set(line["compared"]) == set(json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", "higgs10m_d8.job.json")))["limits"])
    for name, (value, limit) in line["compared"].items():
        assert f"compared {name}: value" in done.stderr
        if limit == 0:
            assert value == 0, name


def test_without_a_chip_it_refuses_and_prints_no_result():
    done = run_cli(ROOT, "--workload", "higgs10m_d8.job", "--seed", "1", "--seconds", "1",
                   "--trace", "0")
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "refusing to run" in done.stderr


def test_bare_directory_exits_nonzero(tmp_path):
    import shutil

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    done = run_cli(str(tmp_path), "--workload", "higgs10m_d8.job", "--seed", "1",
                   "--seconds", "1", "--trace", "0", "--rehearse-cpu",
                   env_extra={"PYTHONPATH": ""})
    assert done.returncode != 0 and done.stdout.strip() == ""


@pytest.mark.slow
def test_a_third_cell_is_data_only(tmp_path):
    root = copy_with_third_cell(str(tmp_path))
    done = run_cli(root, "--workload", "tiny_d3.job2", "--seed", "7", "--seconds", "1",
                   "--trace", "1", "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    line = last_line(done.stdout)
    assert set(line) == KEYS and line["correct"] is True, done.stderr[-3000:]
    facts = json.loads(next(ln for ln in done.stderr.splitlines()
                            if ln.startswith("facts: "))[len("facts: "):])
    assert "ckpt_count" in facts["layer_metrics_read"]     # the new metric's reader ran
    assert facts["chunks"] == [2, 2]                      # --trace 1: trace_chunks chunks
