"""Where one run's ``setup_s`` went, from the program's own spans and counters.

    python3 benchmark/tools/setup_table.py --out <file.json> -- \
        --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs ``benchmark/run.py`` with the arguments after ``--`` in this process,
unedited, and listens beside it: every obs span the program closes (the
runner's own sink hears them too), the wall of the benchmark's own data
generator, and the program's jit counters as they stand when the window opens.
The window opens inside the ``train.callbacks`` span of the last warm-up
chunk, so that span's start is taken as the open (the runner's ``setup_s``,
which a traced run does not print, is later by the callback's first lines).

The file holds ``setup_s``, ``generator_s``, ``before_first_span_s`` (process
start, imports, the device check and the generator), every span path's wall
and count before the open, ``spans_s`` (the time some span was open: the
union, so a span inside another counts once), ``unnamed_s`` (``setup_s`` less
``before_first_span_s`` and ``spans_s``: host code between spans), and ``jit`` / ``cache`` / ``compiles``:
``dryad_prog_jit_seconds_total``, ``dryad_prog_cache_total`` and
``dryad_prog_backend_compiles_total`` by program family, at the open and at
the end of the run, and ``layer_metrics``: the seven readers of set-up
(``benchmark/layer_metrics/sketch_s.py`` ... ``backend_compile_s.py``) over
the whole run, which no entry of ``BENCHMARK.json`` names yet (PERF.md section
7), so this file is where a run reports them.  PERF.md section 5's set-up
table is made of these.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402  (its import is the run's T_START)

SETUP_READERS = ("sketch_s", "bin_s", "upload_s", "capture_s", "trace_lower_s",
                 "cache_read_s", "backend_compile_s")
COUNTERS = {"jit": "dryad_prog_jit_seconds_total", "cache": "dryad_prog_cache_total",
            "compiles": "dryad_prog_backend_compiles_total"}


def counters() -> dict:
    from dryad_tpu.obs.registry import default_registry

    snap = default_registry().snapshot()["counters"]
    return {key: dict(snap.get(name, {})) for key, name in COUNTERS.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("run_args", nargs="+", help="benchmark/run.py's own arguments")
    args = ap.parse_args(argv)
    run = argparse.ArgumentParser()
    run.add_argument("--workload", required=True)
    run.add_argument("--seed", type=int, default=0)
    known, _ = run.parse_known_args(args.run_args)

    from benchmark.harness import manifest as mf
    from dryad_tpu.obs import spans

    cell = mf.Cell(mf.load(), known.workload)
    warmup = int(cell.traffic["warmup_chunks"])

    generator = {"s": 0.0}
    family = importlib.import_module("benchmark.datagen." + cell.config["data"]["family"])
    make = family.make

    def timed_make(*a, **k):
        t0 = time.perf_counter()
        try:
            return make(*a, **k)
        finally:
            generator["s"] += time.perf_counter() - t0

    family.make = timed_make

    log: list = []
    at_open: dict = {}

    def keep(path, t0_s, dur_s, *a, **k):
        log.append((path, t0_s, dur_s))
        if path.endswith("train.callbacks") and not at_open \
                and sum(p.endswith("train.callbacks") for p, _, _ in log) == warmup:
            at_open.update(counters(), t_open=t0_s)

    set_sink = spans.set_trace_sink

    def tee(sink):
        set_sink(keep if sink is None else
                 lambda *a, **k: (keep(*a, **k), sink(*a, **k)))

    spans.set_trace_sink = tee
    set_sink(keep)
    try:
        rc = bench_run.main(args.run_args)
    finally:
        spans.set_trace_sink = set_sink
        set_sink(None)
        family.make = make
    if not at_open:
        print("setup_table: the window never opened", file=sys.stderr)
        return rc or 1

    t_open = at_open.pop("t_open")
    before = [(p, t0, d) for p, t0, d in log if t0 < t_open]
    by_path: dict = {}
    for p, _, d in before:
        row = by_path.setdefault(p, {"s": 0.0, "n": 0})
        row["s"] += d
        row["n"] += 1
    setup_s = t_open - bench_run.T_START
    first = min(t0 for _, t0, _ in before) - bench_run.T_START
    spans_s, covered = 0.0, 0.0      # the union of the spans, cut at the open
    for _, t0, d in sorted(before, key=lambda sp: sp[1]):
        end = min(t0 + d, t_open)
        spans_s += max(end - max(t0, covered), 0.0)
        covered = max(covered, end)
    out = {"cell": cell.name, "seed": known.seed, "run_args": args.run_args,
           "setup_s": setup_s, "generator_s": generator["s"],
           "before_first_span_s": first, "spans_s": spans_s,
           "unnamed_s": setup_s - first - spans_s,
           "spans": dict(sorted(by_path.items())),
           "at_open": at_open, "at_end": counters(),
           "layer_metrics": {name: mf.metric_reader(name).read({}) for name in SETUP_READERS}}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1)
    print(f"setup_table: setup_s {setup_s:.2f} = before the first span {first:.2f} "
          f"(generator {generator['s']:.2f}) + spans {spans_s:.2f} + unnamed "
          f"{out['unnamed_s']:.2f}; wrote {args.out}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
