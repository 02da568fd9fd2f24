"""The readings that the data-parallel cell's limits are set from.

    python3 benchmark/tools/readings_dp4.py --workload criteo12m_leaf255.job_dp4 --grown 1

``readings_bestfirst.py`` for the cell whose job is sharded over four chips.
The stand-ins are the reference itself in the job's place, over all rows as
one table, and need **one chip, not four** (on more they spread their
histogram passes, ``gbdt_bestfirst_dp.BestFirstSpread``).  On each of
``--grown`` seeds, the first ``reference_iterations`` trees grown best-first
by the reference are put in the job's place:

(a) the control: gradients and hessians rounded to bfloat16 before they are
    summed, the nearest precision below the configuration's float32;
(b) the same in float32, which has to pass;
(c) a step that returns its state unchanged (the second tree empty);
(d) one answer altered where it is produced (the first tree's root
    threshold moved 40 bins);
(e) half of the batch left out;
(f) the device eval scoring half of the valid rows;
(g) a tree grown level by level to the same number of leaves;
(h) this cell's own: **one shard's part left out of the sum**, the last
    quarter of the rows missing from every histogram (a psum that loses a
    shard), which ``window_cover_gap`` and ``split_flip_share`` must fail.

A seed's kinds run in the order control, float32, (h), (e), (c), (d), (f),
(g) and ``--out`` is rewritten after every row, so a call cut by its time
limit keeps the rows this cell's limits rest on.  On one chip at the cell's
size a seed with every kind takes 668 s, one with ``--only
control_bfloat16,fault_quarter_left_out`` (the float32 stand-in always
runs) 348 s (my chip run, PR 34).

The program's own readings are the ``compared`` lines of the cell's runs
(``--seeds`` drives the cell's runner here, on four chips).  Every row goes
through the harness's own ``judge`` with the cell's limits.  Not part of a
benchmark run; PERF.md section 2 records what it printed.
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

FAULTS = ("fault_state_unchanged", "fault_answer_altered", "fault_grown_level_by_level",
          "fault_eval_on_half", "fault_half_batch", "fault_quarter_left_out")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=0)
    ap.add_argument("--grown", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=3_400_000_000)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--only", default="", help="comma-separated kinds; all when empty")
    ap.add_argument("--trees", type=int, default=0,
                    help="trees a stand-in grows and is followed over; the traffic's when 0")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import numpy as np

    from benchmark.harness import manifest as mf
    from benchmark.harness.result import judge
    from benchmark.reference import gbdt
    from benchmark.reference.gbdt_bestfirst_dp import BestFirstSpread, Rows

    cell = mf.Cell(mf.load(), args.workload)
    runner = cell.runner()
    k, kw = int(cell.traffic["reference_iterations"]), int(cell.traffic["window_iterations"])
    if args.trees:
        k = kw = args.trees
    params, cap = runner.job_params(cell.config, args.rehearse_cpu)
    only = {kind for kind in args.only.split(",") if kind}
    rows = []

    def wanted(kind: str) -> bool:
        return not only or kind in only

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

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        run_args = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                                      rehearse_cpu=args.rehearse_cpu)
        out = runner.run(cell, run_args, time.perf_counter())
        report("program", seed, {**out["numbers"],
                                 "cap_stopped_steps": out["facts"]["cap_stopped_steps"],
                                 "tree_depths": out["facts"]["tree_depths"]})

    devices = jax.devices()

    def reference(train, valid):
        return BestFirstSpread(params, train, valid, cap, devices)

    for i in range(args.grown):
        seed = args.first_seed + 104729 * (i + 1)
        q, y, qv, yv = runner.make_data(cell.config, seed, args.rehearse_cpu)
        train, valid = Rows(q, y), Rows(qv, yv)
        ref = reference(train, valid)

        def read(kind, job):
            t0 = time.perf_counter()
            first = ref.follow(job, k)
            report(kind, seed, {**first, **ref.follow_window(job, kw)})
            print(f"{kind} followed in {time.perf_counter() - t0:.1f}s", flush=True)
            return first

        if wanted("control_bfloat16"):
            read("control_bfloat16", ref.grow(k, bf16=True))
        if only == {"control_bfloat16"}:
            continue                    # the float32 stand-in is every other kind's start
        t0 = time.perf_counter()
        sound = ref.grow(k)
        print(f"reference grew {k} trees in {time.perf_counter() - t0:.1f}s", flush=True)
        followed = read("reference_float32", sound)
        # part of the batch left out of every sum: the stand-in grows on the
        # first rows alone, the reference follows it on all of them
        for kind, share in (("fault_quarter_left_out", 4), ("fault_half_batch", 2)):
            if not wanted(kind):
                continue
            n = q.shape[0] - q.shape[0] // share
            del ref
            part = reference(Rows(q[:n], y[:n]), valid).grow(k)
            ref = reference(train, valid)
            read(kind, part)
        if wanted("fault_state_unchanged"):
            unchanged = copy.deepcopy(sound)
            unchanged["trees"][1] = emptied(unchanged["trees"][1])
            read("fault_state_unchanged", unchanged)
        if wanted("fault_answer_altered"):
            altered = copy.deepcopy(sound)
            altered["trees"][0] = moved(altered["trees"][0])
            read("fault_answer_altered", altered)
        if wanted("fault_eval_on_half"):
            # the device eval on half of the valid rows: every other number is
            # the sound job's, so only the reported metrics are made anew
            nv = qv.shape[0] // 2
            half_valid = Rows(qv[:nv], yv[:nv])
            small = reference(half_valid, half_valid)
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
        if wanted("fault_grown_level_by_level"):
            leaves = max(int((t.feature >= 0).sum()) + 1 for t in sound["trees"])
            depth = max(leaves - 1, 1).bit_length()
            by_level = gbdt.Reference({**params, "num_leaves": leaves, "max_depth": depth},
                                      train, valid).grow(k)
            read("fault_grown_level_by_level", by_level)
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
