"""The readings that the limits of ``correct`` are set from, in one process.

    python3 benchmark/tools/readings.py --workload <cell> --seeds 12 --controls 3 --grown 3

For each of ``--seeds`` fresh seeds it drives the cell's own runner for
``--seconds`` and prints the numbers compared: the lower readings are the
largest of these.  Every row, the program's and each stand-in's, goes
through the harness's own ``judge`` with the cell's limits, and its verdict
is printed beside it.

On the first ``--controls`` of those seeds it then puts stand-ins in the
job's place that differ from it in the last ``window_iterations`` trees, the
ones grown inside the window: (a) the control, those trees' leaves restated
by the reference from gradients rounded to bfloat16, the nearest precision
below the configuration's; and the faults a training cell can have: (c) half
of the batch left out, (d) a step that returns its state unchanged (the last
tree empty), (e) one answer altered where it is produced (the last tree's
root threshold moved).

On ``--grown`` further seeds the stand-in is the reference itself, growing
the first ``reference_iterations`` trees: (a), (b) the same in float32,
which has to pass, (c) to (e), and (f) the device eval scoring half of the
valid rows.  The upper readings are the
smallest of (a) and of each fault.  Not part of a benchmark run; PERF.md
section 2 records what it printed.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--grown", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_400_000_000)
    ap.add_argument("--seconds", type=float, default=51.0,
                    help="window of the program's runs: the cell's own, so that the last "
                         "trees are as late as a run's")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import numpy as np

    from benchmark.harness import manifest as mf
    from benchmark.harness.result import judge
    from benchmark.reference.gbdt import Reference, Rows

    cell = mf.Cell(mf.load(), args.workload)
    runner = cell.runner()
    k, kw = int(cell.traffic["reference_iterations"]), int(cell.traffic["window_iterations"])
    params = dict(cell.config["params"])
    rows = []

    def report(kind: str, seed: int, numbers: dict) -> None:
        numbers = {"job_died": 0.0, "checkpoint_iters_gap": 0.0, **numbers}
        ok, compared = judge(numbers, cell.limits)
        over = [n for n, c in compared.items() if c["value"] is None or not c["value"] <= c["limit"]]
        row = {"kind": kind, "seed": seed, "correct": ok, "over": over,
               **{key: numbers.get(key) for key in runner.NUMBERS}}
        print("reading " + json.dumps(row), flush=True)
        rows.append(row)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f)

    def emptied(tree):
        return dataclasses.replace(tree, feature=np.full_like(tree.feature, -1),
                                   value=np.zeros_like(tree.value))

    def moved(tree):
        threshold = tree.threshold.copy()
        threshold[0] = (int(threshold[0]) + 40) % 250 + 0.5
        return dataclasses.replace(tree, threshold=threshold)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        run_args = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                                      rehearse_cpu=args.rehearse_cpu)
        out = runner.run(cell, run_args, time.perf_counter())
        report("program", seed, out["numbers"])
        job = out["job"]
        if i >= args.controls or job is None:
            continue
        q, y, qv, yv = runner.make_data(cell.config, seed, args.rehearse_cpu)
        ref = Reference(params, Rows(q, y), Rows(qv, yv))
        n = q.shape[0] // 2
        half = Reference(params, Rows(q[:n], y[:n]), None)
        last = len(job["trees"]) - 1
        stand_ins = {
            "window_control_bfloat16": ref.restate(job, kw, bf16=True),
            "window_fault_half_batch": half.restate(job, kw),
            "window_fault_state_unchanged":
                {**job, "trees": job["trees"][:last] + [emptied(job["trees"][last])]},
            "window_fault_answer_altered":
                {**job, "trees": job["trees"][:last] + [moved(job["trees"][last])]},
        }
        for kind, stand_in in stand_ins.items():
            # the first trees are the job's own: their numbers are the program's row's
            report(kind, seed, {**out["numbers"], **ref.follow_window(stand_in, kw)})
        del ref, half

    for i in range(args.grown):
        seed = args.first_seed + 104729 * (i + 1)
        q, y, qv, yv = runner.make_data(cell.config, seed, args.rehearse_cpu)
        ref = Reference(params, Rows(q, y), Rows(qv, yv))

        def read(kind, job):
            report(kind, seed, {**ref.follow(job, k), **ref.follow_window(job, kw)})

        read("control_bfloat16", ref.grow(k, bf16=True))
        t0 = time.perf_counter()
        sound = ref.grow(k)
        print(f"reference grew {k} trees in {time.perf_counter() - t0:.1f}s", flush=True)
        if i == 0:
            read("reference_float32", sound)
        unchanged = copy.deepcopy(sound)
        unchanged["trees"][1] = emptied(unchanged["trees"][1])
        read("fault_state_unchanged", unchanged)
        altered = copy.deepcopy(sound)
        altered["trees"][0] = moved(altered["trees"][0])
        read("fault_answer_altered", altered)
        del ref
        n = q.shape[0] // 2
        half = Reference(params, Rows(q[:n], y[:n]), Rows(qv, yv)).grow(k)
        ref = Reference(params, Rows(q, y), Rows(qv, yv))
        read("fault_half_batch", half)
        half_eval = copy.deepcopy(sound)
        nv = qv.shape[0] // 2
        half_valid = Rows(qv[:nv], yv[:nv])
        small = Reference(params, half_valid, half_valid)
        for it in list(half_eval["evals"]):
            vs = small.valid.start(half_eval["init_score"])
            for tree in sound["trees"][: it + 1]:
                vs = small.add_tree(small.valid, vs, tree, tree.value)
            half_eval["evals"][it] = small.valid_metric(vs)
        read("fault_eval_on_half", half_eval)
        del ref, small
    kinds = sorted({r["kind"] for r in rows})
    for key in runner.NUMBERS:
        for kind in kinds:
            vals = [r[key] for r in rows if r["kind"] == kind and r.get(key) is not None]
            if vals:
                print(f"summary {key} {kind}: min {min(vals):.3e} max {max(vals):.3e} n {len(vals)}")
    for kind in kinds:
        verdicts = [r["correct"] for r in rows if r["kind"] == kind]
        print(f"verdict {kind}: correct on {sum(verdicts)} of {len(verdicts)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
