"""A temporary copy of the benchmark with one more of everything, added as
files and manifest entries only: a configuration, a traffic mix, a cell, a
runner and a per-layer metric.  Nothing that is there is edited."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_CONFIG = {
    "name": "tiny_d3",
    "source": "a test's own: Higgs-shaped, cut to run in seconds on a CPU",
    "data": {"family": "higgs", "train_rows": 4000, "valid_rows": 1000, "features": 28},
    "params": {"objective": "binary", "metric": "auc", "growth": "depthwise", "max_depth": 3,
               "num_leaves": 8, "max_bins": 256, "learning_rate": 0.1, "num_trees": 60,
               "lambda_l2": 1.0, "min_child_weight": 0.001, "min_data_in_leaf": 20,
               "min_split_gain": 0.0, "hist_precision": "exact", "seed": 11},
    "checkpoint_every": 2,
}
TINY_LIMITS = {"job_died": 0, "checkpoint_iters_gap": 0, "init_score_gap": 1e-5,
               "level_gain_gap": 1e-3, "leaf_value_gap": 1e-3, "valid_metric_gap": 5e-4,
               "window_cover_gap": 0, "window_leaf_value_gap": 1e-3, "window_root_gain_gap": 1e-3}


def copy_with_third_cell(tmp: str) -> str:
    """Returns the root of the copy.  Existing files are copied untouched;
    the manifest gains entries."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(tmp, "benchmark")
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    before = {p: open(os.path.join(base, p), "rb").read()
              for base, _, files in os.walk(bench) for p in
              [os.path.join(base, f) for f in files]}
    with open(os.path.join(bench, "configs", "tiny_d3.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(bench, "traffic", "job2.json"), "w") as f:
        json.dump({"runner": "train_job2", "warmup_chunks": 2, "trace_chunks": 2,
                   "trace_seconds": 0, "reference_iterations": 2, "window_iterations": 2}, f)
    with open(os.path.join(bench, "runners", "train_job2.py"), "w") as f:
        f.write("from benchmark.runners.train_job import run  # noqa: F401\n")
    with open(os.path.join(bench, "layer_metrics", "ckpt_count.py"), "w") as f:
        f.write("def read(facts):\n"
                "    return sum(p.endswith('train.fetch.checkpoint') for p, _, _ in facts['spans'])\n")
    with open(os.path.join(bench, "workloads", "tiny_d3.job2.json"), "w") as f:
        json.dump({"limits": TINY_LIMITS}, f)
    manifest["configs"].append({"name": "tiny_d3", "source": TINY_CONFIG["source"],
                                "file": "benchmark/configs/tiny_d3.json", "reduced": [],
                                "why": "test"})
    manifest["workloads"].append({"name": "tiny_d3.job2", "config": "tiny_d3",
                                  "traffic": "job2", "chips": 1, "why": "test"})
    manifest["per_layer"].append({"name": "ckpt_count", "unit": "count", "better": "lower",
                                  "source": "program_span", "layer": "checkpoint",
                                  "moves": "iters_per_s", "workloads": ["tiny_d3.job2"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} was edited"
    return tmp


def run_cli(root: str, *argv: str, env_extra=None, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, os.path.join(root, "benchmark", "run.py"), *argv],
                          cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
