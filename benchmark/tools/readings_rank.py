"""The readings that the ranking cell's limits are set from, in one process.

    python3 benchmark/tools/readings_rank.py --workload <cell> --seeds 0 --grown 3 --faulted 2

``readings_bestfirst.py`` for a cell whose reference is ``gbdt_rank.py``.  On
each of ``--seeds`` fresh seeds it drives the cell's own runner and prints the
numbers compared (a run of the benchmark prints the same on its ``compared``
lines, so seeds that were run anyway need not be run again here).  On
``--grown`` further seeds the stand-in is the reference itself, growing the
first ``reference_iterations`` trees best-first under its own λ-gradients,
put in the job's place:

(a) the control: gradients and hessians rounded to bfloat16 before they are
    summed, the nearest precision below the configuration's float32;
(b) the same in float32, which has to pass;

and on the first ``--faulted`` of those seeds also the faults:

(c) a step that returns its state unchanged (the second tree empty);
(d) one answer altered where it is produced (the first tree's root threshold
    moved 40 bins);
(e) half of the batch left out (the first half of the queries);
(f) the device eval scoring half of the valid queries;
(g) a tree grown level by level to the same number of leaves;
(h) |dNDCG| left out of the gradients (RankNet's);
(i) the truncation ignored (every pair of a query);
(j) every inner query boundary shifted by one row;
(k) NDCG taken over the valid set as one query.

Every row goes through the harness's own ``judge`` with the cell's limits, and
its verdict is printed beside it.  Not part of a benchmark run; PERF.md
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
    ap.add_argument("--seeds", type=int, default=0)
    ap.add_argument("--grown", type=int, default=3)
    ap.add_argument("--faulted", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=3_200_000_000)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import numpy as np

    from benchmark.harness import manifest as mf
    from benchmark.harness.result import judge
    from benchmark.reference.gbdt_rank import (GRADIENT_FAULTS, RankBestFirst, RankLevelwise,
                                               RankRows)

    cell = mf.Cell(mf.load(), args.workload)
    runner = cell.runner()
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    k, kw = int(cell.traffic["reference_iterations"]), int(cell.traffic["window_iterations"])
    params, cap = runner.job_params(cell.config, args.rehearse_cpu)
    rows = []

    def report(kind: str, seed: int, numbers: dict) -> None:
        numbers = {"job_died": 0.0, "checkpoint_iters_gap": 0.0, **numbers}
        ok, compared = judge(numbers, cell.limits)
        over = [n for n, c in compared.items() if c["value"] is None or not c["value"] <= c["limit"]]
        row = {"kind": kind, "seed": seed, "correct": ok, "over": over,
               **{key: numbers.get(key) for key in runner.NUMBERS},
               "cap_stopped_steps": numbers.get("cap_stopped_steps"),
               "tree_depths": numbers.get("tree_depths")}
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

    def first_half(q, y, lengths):
        keep = lengths[: lengths.size // 2]
        n = int(keep.sum())
        return RankRows(q[:n], y[:n], keep)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        run_args = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                                      rehearse_cpu=args.rehearse_cpu)
        out = runner.run(cell, run_args, time.perf_counter())
        report("program", seed, {**out["numbers"],
                                 "cap_stopped_steps": out["facts"]["cap_stopped_steps"],
                                 "tree_depths": out["facts"]["tree_depths"]})

    for i in range(args.grown):
        seed = args.first_seed + 104729 * (i + 1)
        (q, y, lengths), (qv, yv, lengths_v) = runner.make_data(cell.config, seed,
                                                                 args.rehearse_cpu)
        train, valid = RankRows(q, y, lengths), RankRows(qv, yv, lengths_v)
        ref = RankBestFirst(params, train, valid, cap)

        def read(kind, job):
            t0 = time.perf_counter()
            first = ref.follow(job, k)
            report(kind, seed, {**first, **ref.follow_window(job, kw)})
            print(f"{kind} followed in {time.perf_counter() - t0:.1f}s", flush=True)
            return first

        read("control_bfloat16", ref.grow(k, bf16=True))
        t0 = time.perf_counter()
        sound = ref.grow(k)
        print(f"reference grew {k} trees in {time.perf_counter() - t0:.1f}s", flush=True)
        followed = read("reference_float32", sound)
        if i >= args.faulted:
            del ref, train, valid
            continue
        unchanged = copy.deepcopy(sound)
        unchanged["trees"][1] = emptied(unchanged["trees"][1])
        read("fault_state_unchanged", unchanged)
        altered = copy.deepcopy(sound)
        altered["trees"][0] = moved(altered["trees"][0])
        read("fault_answer_altered", altered)
        leaves = max(int((t.feature >= 0).sum()) + 1 for t in sound["trees"])
        depth = max(leaves - 1, 1).bit_length()
        read("fault_grown_level_by_level",
             RankLevelwise({**params, "num_leaves": leaves, "max_depth": depth},
                           train, valid).grow(k))
        for fault in GRADIENT_FAULTS:
            read("fault_" + fault, RankBestFirst(params, train, valid, cap, fault).grow(k))
        # the valid metric made anew, every other number the sound job's: NDCG
        # over the valid set as one query, and the device eval on half of the queries
        one = RankBestFirst(params, train, valid, cap, "ndcg_one_query")
        half_valid = first_half(qv, yv, lengths_v)
        small = RankBestFirst(params, half_valid, half_valid, cap)
        for kind, judge_with in (("fault_ndcg_one_query", one), ("fault_eval_on_half", small)):
            gap = 0.0
            for row in followed["per_tree"]:
                vs = judge_with.valid.start(0.0)
                for tree in sound["trees"][: row["iteration"] + 1]:
                    vs = judge_with.add_tree(judge_with.valid, vs, tree, tree.value)
                gap = max(gap, abs(judge_with.valid_metric(vs) - row["valid_metric"][1]))
            report(kind, seed, {**followed, **ref.follow_window(sound, kw),
                                "valid_metric_gap": gap})
        del ref, one, small, half_valid
        half = RankBestFirst(params, first_half(q, y, lengths), valid, cap).grow(k)
        ref = RankBestFirst(params, train, valid, cap)
        read("fault_half_batch", half)
        del ref, train, valid
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
