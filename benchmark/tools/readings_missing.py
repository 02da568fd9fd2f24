"""The readings that the missing-value cell's limits are set from, in one process.

    python3 benchmark/tools/readings_missing.py --workload <cell> --seeds 8 --grown 2
    python3 benchmark/tools/readings_missing.py --workload <cell> \
        --program-seeds 4100001013,4100001019 --grown 0

``readings_bestfirst.py`` for a cell whose reference is
``gbdt_bestfirst_missing.py``.  On each of ``--seeds`` fresh seeds (or the
``--program-seeds`` named) it drives the cell's own runner and prints the
numbers compared.  On ``--grown`` further seeds the stand-in is the
reference itself, growing the first ``reference_iterations`` trees
best-first with both planes, put in the job's place:

(a) the control: gradients and hessians rounded to bfloat16 before they are
    summed, the nearest precision below the configuration's float32;
(b) the same in float32, which has to pass;
(c) a step that returns its state unchanged (the second tree empty);
(d) one answer altered where it is produced (the first tree's root
    threshold moved 20 bins);
(e) half of the batch left out;
(f) the device eval scoring half of the valid rows;
(g) a tree grown level by level to the same number of leaves;
(h) the planes dropped: plane 0 alone scanned, so every empty cell goes
    left (the program with ``learn_missing`` off);
(i) the empty cells of every split at depth 1 routed against the direction
    the tree keeps;
(j) the eval walk ignoring ``default_left``: the valid rows' empty cells
    all sent left, the trees as they were grown.

The control, (b), (h), (i) and (j) run on every grown seed, the other faults
on the first; ``--stand-ins`` names a subset (``control``, ``float32``,
``faults``).  Every row goes through the harness's own ``judge`` with the
cell's limits, and its verdict is printed beside it, with the split nodes'
flips: the share past 1e-9 of the gain (``strict_flip_share``), the largest
loss over the split's terms (``flip_rel_max``), and the loss of the node
that carries the share past its limit (``flip_rel_at_limit``).  Not part of
a benchmark run; PERF.md section 2 records what it printed.
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
    ap.add_argument("--grown", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=4_100_000_000)
    ap.add_argument("--program-seeds", default="",
                    help="comma-separated seeds for the program, in place of --seeds")
    ap.add_argument("--stand-ins", default="control,float32,faults")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from benchmark.harness import manifest as mf
    from benchmark.harness.result import judge
    from benchmark.reference import gbdt
    from benchmark.reference.gbdt_bestfirst_missing import BestFirstMissing, Rows

    cell = mf.Cell(mf.load(), args.workload)
    runner = cell.runner()
    k, kw = int(cell.traffic["reference_iterations"]), int(cell.traffic["window_iterations"])
    params, cap = runner.job_params(cell.config, args.rehearse_cpu)
    stand_ins = set(args.stand_ins.split(","))
    rows = []

    def flip_columns(flips: dict) -> dict:
        rel, nodes = flips.get("flip_rel") or [], int(flips.get("split_nodes") or 0)
        k = int(cell.limits["split_flip_share"] * nodes) + 1      # flips that fail the limit
        return {"split_nodes": nodes, "strict_flip_share": flips.get("strict_flip_share"),
                "flip_rel_max": rel[0] if rel else 0.0,
                "flip_rel_at_limit": rel[k - 1] if len(rel) >= k else 0.0,
                "flip_rel": rel[:8]}

    def report(kind: str, seed: int, numbers: dict) -> None:
        numbers = {"job_died": 0.0, "checkpoint_iters_gap": 0.0, **numbers}
        ok, _ = judge(numbers, cell.limits)
        over = [n for n, lim in cell.limits.items()
                if numbers.get(n) is None or not numbers[n] <= lim]
        row = {"kind": kind, "seed": seed, "correct": ok, "over": over,
               **{key: numbers.get(key) for key in runner.NUMBERS},
               **flip_columns(numbers),
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
        t = int(threshold[0]) if np.isfinite(threshold[0]) else 0
        threshold[0] = (t + 20) % 62 + 0.5
        return dataclasses.replace(tree, threshold=threshold)

    program_seeds = ([int(x) for x in args.program_seeds.split(",")] if args.program_seeds
                     else [args.first_seed + 7919 * i for i in range(args.seeds)])
    for seed in program_seeds:
        run_args = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                                      rehearse_cpu=args.rehearse_cpu)
        out = runner.run(cell, run_args, time.perf_counter())
        report("program", seed, {**out["numbers"], **(out["facts"].get("flips") or {}),
                                 "cap_stopped_steps": out["facts"]["cap_stopped_steps"],
                                 "tree_depths": out["facts"]["tree_depths"]})
        print(f"program facts.missing {json.dumps(out['facts']['missing'])}", flush=True)

    for i in range(args.grown):
        seed = args.first_seed + 104729 * (i + 1)
        q, y, qv, yv = runner.make_data(cell.config, seed, args.rehearse_cpu)
        train, valid = Rows(q, y), Rows(qv, yv)
        ref = BestFirstMissing(params, train, valid, cap)

        def read(kind, job, ref=ref, seed=seed):
            t0 = time.perf_counter()
            first = ref.follow(job, k)
            report(kind, seed, {**first, **ref.follow_window(job, kw)})
            print(f"{kind} followed in {time.perf_counter() - t0:.1f}s", flush=True)
            return first

        def grown(train=train, **kind):
            t0 = time.perf_counter()
            job = BestFirstMissing(params, train, valid, cap, **kind).grow(k)
            print(f"grew {k} trees {kind} in {time.perf_counter() - t0:.1f}s", flush=True)
            return job

        if "control" in stand_ins:
            read("control_bfloat16", ref.grow(k, bf16=True))
        if not stand_ins & {"float32", "faults"}:
            continue
        sound = grown()
        followed = read("reference_float32", sound)
        if "faults" not in stand_ins:
            continue
        read("fault_planes_dropped", grown(planes=1))
        read("fault_one_level_misrouted", grown(misroute_depth=1))
        read("fault_eval_ignores_direction", grown(eval_ignores_direction=True))
        if i:
            continue
        unchanged = copy.deepcopy(sound)
        unchanged["trees"][1] = emptied(unchanged["trees"][1])
        read("fault_state_unchanged", unchanged)
        altered = copy.deepcopy(sound)
        altered["trees"][0] = moved(altered["trees"][0])
        read("fault_answer_altered", altered)
        read("fault_grown_level_by_level", grown(level_order=True))
        # the device eval on half of the valid rows: every other number is the
        # sound job's, so only the reported metrics are made anew
        nv = qv.shape[0] // 2
        half_valid = Rows(qv[:nv], yv[:nv])
        small = BestFirstMissing(params, half_valid, half_valid, cap)
        gap = 0.0
        for row in followed["per_tree"]:
            vs = small.valid.start(sound["init_score"])
            for tree in sound["trees"][: row["iteration"] + 1]:
                vs = small.add_tree(small.valid, vs, tree, tree.value)
            gap = max(gap, gbdt.metric_gap(ref.metric, small.valid_metric(vs),
                                           row["valid_metric"][1]))
        report("fault_eval_on_half", seed, {**followed, **ref.follow_window(sound, kw),
                                            "valid_metric_gap": gap})
        del small, half_valid
        n = q.shape[0] // 2
        read("fault_half_batch", grown(train=Rows(q[:n], y[:n])))
        del ref, train, valid
    kinds = sorted({r["kind"] for r in rows})
    for key in (*runner.NUMBERS, "strict_flip_share", "flip_rel_max", "flip_rel_at_limit"):
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
