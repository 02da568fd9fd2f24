"""The manifest and what it names: legal, complete, found by name."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import manifest as mf  # noqa: E402

MANIFEST = mf.load(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = [m["name"] for m in MANIFEST["per_layer"]]


def test_manifest_is_sound():
    assert mf.problems(MANIFEST, ROOT) == []


def test_contract_shape():
    assert set(MANIFEST) == mf.TOP_KEYS
    assert 1 <= MANIFEST["run_seconds"] <= 51 and isinstance(MANIFEST["run_seconds"], int)
    assert MANIFEST["command"][:2] == ["python3", "benchmark/run.py"]
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in MANIFEST["paths"])
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    setup = next(m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.1
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert all(mf.NAME.match(k) for k in c["reduced"])
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_every_file_under_paths_has_a_legal_name():
    legal = mf.re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in MANIFEST["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert legal.match(rel), rel


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = mf.Cell(MANIFEST, name, ROOT)
    assert cell.config["name"] == cell.config_name
    assert cell.config["source"] == next(
        c["source"] for c in MANIFEST["configs"] if c["name"] == cell.config_name)
    assert hasattr(cell.runner(), "run")
    importlib.import_module("benchmark.datagen." + cell.config["data"]["family"])
    assert cell.limits, "a cell with no limits can never be correct"
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert cell.config["params"]["num_trees"] == 500


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader_resolves_by_name(name):
    assert callable(mf.metric_reader(name).read)


def test_peaks_are_keyed_by_device_kind():
    from benchmark.harness import device

    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("TPU v5e")
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        mf.Cell(MANIFEST, "no_such.cell", ROOT)


def test_problems_are_found():
    broken = json.loads(json.dumps(MANIFEST))
    broken["workloads"][0]["traffic"] = "no such traffic"
    broken["per_layer"][0]["unit"] = "per cent"
    broken["end_to_end"][0]["bound"] = 0.5
    found = " ".join(mf.problems(broken, ROOT))
    assert "traffic" in found and "unit" in found and "bound" in found
