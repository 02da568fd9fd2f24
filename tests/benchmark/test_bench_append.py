"""Every accepted cell's manifest checks hold on a manifest grown by appending.

The manifest grows only at the end of each list: a check that compares it
entry by entry reads an entry inserted into a list as a change to the entry
after it.  So no cell's test may read a list by position, as the categorical
cell's once did (``workloads[-1] == CELL`` in ``test_bench_cat.py``, which a
cell appended after it failed), nor pin a tail of ``per_layer``, nor ask two
cells to report the same metrics, which a metric appended for one of them
breaks.  Here each cell's checks run on the accepted manifest with one more
cell appended to ``workloads`` and to every ``per_layer`` list that names the
headline cell, and metrics appended to ``per_layer`` for every cell, for
each accepted cell alone and for the leaf-wise cells."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import test_bench_bestfirst  # noqa: E402
import test_bench_cat  # noqa: E402
import test_bench_dp4  # noqa: E402
import test_bench_rank  # noqa: E402
import test_bench_scopes  # noqa: E402
import test_bench_setup  # noqa: E402
from bench_fixtures import copy_with_third_cell  # noqa: E402

from benchmark import layer_metrics  # noqa: E402
from benchmark.harness import manifest as mf  # noqa: E402

SHAM_CELL = "tiny_d3.job2"        # the cell, with its files, that ``copy_with_third_cell`` appends
CHECKS = {"higgs10m_d8.job": test_bench_scopes.check_manifest,
          "epsilon400k_d6.job": test_bench_scopes.check_manifest,
          test_bench_bestfirst.CELL: test_bench_bestfirst.check_manifest,
          test_bench_rank.CELL: test_bench_rank.check_manifest,
          test_bench_dp4.CELL: test_bench_dp4.check_manifest,
          test_bench_cat.CELL: test_bench_cat.check_manifest}
LEAFWISE = [test_bench_bestfirst.CELL, test_bench_rank.CELL, test_bench_dp4.CELL,
            test_bench_cat.CELL]
# metrics appended last, each a reader that reads nothing, and the cells each
# lists: none (every cell reports it), every cell, each accepted cell alone, the
# leaf-wise cells
SHAM_METRICS = {"sham_every": None, "sham_listed": "all",
                **{f"sham_only_{i}": [cell] for i, cell in enumerate(sorted(CHECKS))},
                "sham_leafwise": LEAFWISE}


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """``(manifest, root)``: a copy of the benchmark with ``SHAM_CELL`` and
    its metric ``ckpt_count`` appended by ``copy_with_third_cell``, the cell's
    name appended to every list that names ``higgs10m_d8.job`` and the sham
    metrics appended after that."""
    root = copy_with_third_cell(str(tmp_path_factory.mktemp("grown")))
    manifest = mf.load(root)
    for entry in manifest["per_layer"]:
        if "higgs10m_d8.job" in entry.get("workloads", ()):
            entry["workloads"].append(SHAM_CELL)
    cells = [w["name"] for w in manifest["workloads"]]
    for name, lists in SHAM_METRICS.items():
        with open(mf.metric_path(name, root), "w") as f:
            f.write("def read(facts):\n    return None\n")
        entry = {"name": name, "unit": "count", "better": "lower", "source": "program_counter",
                 "layer": "checkpoint", "moves": "iters_per_s"}
        if lists is not None:
            entry["workloads"] = list(cells) if lists == "all" else list(lists)
        manifest["per_layer"].append(entry)
    return manifest, root


@pytest.fixture()
def readers_of(monkeypatch):
    """The copy's readers importable by name beside the accepted ones, and
    gone from the process afterwards."""
    def extend(root):
        copied = os.path.join(root, "benchmark", "layer_metrics")
        monkeypatch.setattr(layer_metrics, "__path__", [*layer_metrics.__path__, copied])
    yield extend
    for name in ("ckpt_count", *SHAM_METRICS):
        sys.modules.pop(f"{layer_metrics.__name__}.{name}", None)


@pytest.mark.parametrize("cell", sorted(CHECKS))
def test_a_cells_manifest_checks_hold_after_appends(cell, grown, readers_of):
    manifest, root = grown
    readers_of(root)
    assert cell in [w["name"] for w in mf.load()["workloads"]]
    assert SHAM_CELL in [w["name"] for w in manifest["workloads"]]
    assert {"sham_every", "sham_listed", "ckpt_count"} <= {m["name"] for m in mf.Cell(
        manifest, SHAM_CELL, root).per_layer}
    theirs = {m["name"] for m in mf.Cell(manifest, cell, root).per_layer}
    assert {name for name, lists in SHAM_METRICS.items()
            if lists in (None, "all") or cell in lists} <= theirs
    CHECKS[cell](manifest, root)
    test_bench_setup.check_manifest(manifest, root)
