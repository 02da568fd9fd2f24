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
its verdict is printed beside it.

With ``--hessian-look`` each grown seed also gets the readings that the
reference's floor band (``gbdt_rank.FLOOR_ROUNDING_RANK``) is set from, at the
scores before each of the reference's first trees:

* ``band``: every child of the tree's splits, its hessian sum from the
  program's own λ-pass (``dryad_tpu.engine.lambdarank._lambda_grad_padded``,
  float32 on the default device) and from the bfloat16 control's hessians,
  each against the reference's, relative: the band's lower and upper readings;
* ``ops``: on a sample of queries drawn from the seed, each operation of the
  λ-pass evaluated by the device on the pass's own float32 inputs against
  float64, and the hessians rebuilt in float64 with one operation at a time
  taken from the device: the operation whose substitution gives the program's
  shortfall is the one that carries it.

Not part of a benchmark run; PERF.md section 2 records what it printed.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def node_hessians(ref, tree, h) -> np.ndarray:
    """float64 hessian sum of every node of ``tree`` over the training rows
    (``h`` a host column, one value a row)."""
    from benchmark.reference.gbdt_bestfirst import depths_of

    depth = depths_of(tree)
    leaf = ref.train.host(ref.route(ref.train, tree, int(depth.max())))
    sums = np.bincount(leaf, weights=np.asarray(h, np.float64), minlength=tree.feature.size)
    for n in np.argsort(-depth, kind="stable"):
        if tree.feature[n] >= 0:
            sums[n] = sums[tree.left[n]] + sums[tree.right[n]]
    return sums


def gap_summary(got: np.ndarray, want: np.ndarray) -> dict:
    """Relative gaps ``got / want - 1``: median, least and largest, and the
    largest in size."""
    rel = got / want - 1.0
    return {"median": float(np.median(rel)), "min": float(rel.min()), "max": float(rel.max()),
            "largest": float(np.abs(rel).max()), "n": int(rel.size)}


def lambda_ops(score, rel, lengths, sigma: float, truncation: int, ids, S: int) -> dict:
    """The λ-pass's operations over the queries ``ids``, as the program's
    ``_lambda_grad_padded`` takes them (float32 scores, each pair of a higher
    and a lower grade where either ranks in the top ``truncation``): the
    device's result of each on its float32 inputs against float64's, and each
    row's hessian rebuilt in float64 with each operation in turn, and all of
    them, taken from the device.  A discount enters twice, in a pair's
    ``|d_i - d_j|`` and in the query's ideal DCG, and the rebuild takes it
    from the same table in both, as the program does."""
    import jax
    import jax.numpy as jnp

    offsets = np.concatenate([[0], np.cumsum(lengths)])
    grades = np.arange(5, dtype=np.float32)
    ranks = np.arange(S, dtype=np.float32)
    rows, rank_rows, pi, pj, x32, qid, ideal, ideal_at = [], [], [], [], [], [], [], []
    base = 0
    for n, q in enumerate(ids):
        a, b = int(offsets[q]), int(offsets[q + 1])
        s, r = score[a:b], np.maximum(rel[a:b], 0.0)
        rank = np.empty(b - a, np.int64)
        rank[np.argsort(-s, kind="stable")] = np.arange(b - a)
        top = rank < truncation
        i, j = np.nonzero((r[:, None] > r[None, :]) & (top[:, None] | top[None, :]))
        rows.append(np.arange(a, b))
        rank_rows.append(rank)
        pi.append(base + i)
        pj.append(base + j)
        x32.append(np.float32(sigma) * (s[i] - s[j]))
        qid.append(np.full(i.size, n))
        ideal.append(np.sort(r)[::-1].astype(np.int64))
        ideal_at.append(np.full(b - a, n))
        base += b - a
    rows, rank_rows, pi, pj, x32, qid, ideal, ideal_at = map(
        np.concatenate, (rows, rank_rows, pi, pj, x32, qid, ideal, ideal_at))
    ideal_rank = np.arange(ideal.size) - np.repeat(
        np.flatnonzero(np.r_[True, ideal_at[1:] != ideal_at[:-1]]),
        np.bincount(ideal_at, minlength=len(ids)))
    grade_rows = np.maximum(rel[rows], 0.0).astype(np.int64)

    @jax.jit
    def device(x, grades, ranks):
        e = jnp.exp(x)
        rho = 1.0 / (1.0 + e)
        return {"exp": e, "rho": rho, "rho_one_minus_rho": rho * (1.0 - rho),
                "power": jnp.power(2.0, grades) - 1.0, "log2": jnp.log2(ranks + 2.0),
                "discount": 1.0 / jnp.log2(ranks + 2.0)}

    @jax.jit
    def reciprocal(m):
        return jnp.where(m > 0, 1.0 / m, 0.0)

    dev = {k: np.asarray(v, np.float64) for k, v in jax.device_get(device(
        jnp.asarray(x32), jnp.asarray(grades), jnp.asarray(ranks))).items()}
    x64 = x32.astype(np.float64)
    exact = {"exp": np.exp(x64), "rho": 1.0 / (1.0 + np.exp(x64)),
             "power": np.exp2(grades.astype(np.float64)) - 1.0,
             "log2": np.log2(ranks.astype(np.float64) + 2.0)}
    exact["discount"] = 1.0 / exact["log2"]
    exact["rho_one_minus_rho"] = exact["rho"] * (1.0 - exact["rho"])

    def max_dcg(gain, disc):
        return np.bincount(ideal_at, weights=gain[ideal] * disc[ideal_rank],
                           minlength=len(ids)).astype(np.float32)

    m32 = max_dcg(exact["power"], exact["discount"])
    dev["inverse"] = np.asarray(reciprocal(jnp.asarray(m32)), np.float64)
    exact["inverse"] = np.divide(1.0, m32.astype(np.float64), where=m32 > 0,
                                 out=np.zeros(m32.size))
    ops = {}
    for k in exact:
        with np.errstate(invalid="ignore", divide="ignore"):
            err = dev[k] / exact[k] - 1.0
        err = err[exact[k] != 0]
        ops[k] = {"mean": float(err.mean()), "largest": float(np.abs(err).max()),
                  "n": int(err.size)}
    ops["log2"]["by_rank"] = [float(e) for e in (dev["log2"] / exact["log2"] - 1.0)[:32]]

    gi, gj = grade_rows[pi], grade_rows[pj]

    def hessians(use: set) -> np.ndarray:
        if "rho_one_minus_rho" in use:
            p = dev["rho_one_minus_rho"]
        else:
            rho = dev["rho"] if "rho" in use else (
                1.0 / (1.0 + dev["exp"]) if "exp" in use else exact["rho"])
            p = rho * (1.0 - rho)
        gain = (dev if "power" in use else exact)["power"]
        disc = (dev["discount"] if "discount" in use
                else 1.0 / dev["log2"] if "log2" in use else exact["discount"])
        m = max_dcg(gain, disc)
        inv = (np.asarray(reciprocal(jnp.asarray(m)), np.float64) if "inverse" in use
               else np.divide(1.0, m.astype(np.float64), where=m > 0, out=np.zeros(m.size)))
        pair = (sigma * sigma * p * np.abs(gain[gi] - gain[gj])
                * np.abs(disc[rank_rows[pi]] - disc[rank_rows[pj]]) * inv[qid])
        return (np.bincount(pi, weights=pair, minlength=rows.size)
                + np.bincount(pj, weights=pair, minlength=rows.size))

    built = {"float64": hessians(set())}
    for k in ("exp", "rho", "rho_one_minus_rho", "power", "log2", "discount", "inverse"):
        built[k] = hessians({k})
    built["all"] = hessians({"rho_one_minus_rho", "power", "discount", "inverse"})
    return {"rows": rows, "ops": ops, "hessians": built, "pairs": int(pi.size)}


def hessian_look(ref, job: dict, iterations: int, seed: int, sample: int) -> list[dict]:
    """The band's readings and the λ-pass's operations at the scores before
    each of the first ``iterations`` trees of ``job`` (the reference's own)."""
    import jax.numpy as jnp

    from dryad_tpu.engine.lambdarank import PaddingPlan, _lambda_grad_padded

    rows = ref.train
    lengths = rows.lengths
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    plan = PaddingPlan(offsets, truncation=ref.truncation)
    rel = rows.y_host.astype(np.float32)
    ids = np.sort(np.random.default_rng([seed, 4040]).choice(
        lengths.size, min(sample, lengths.size), replace=False))
    score = rows.start(0.0)
    out = []
    for it, tree in enumerate(job["trees"][:iterations]):
        s32 = rows.host(score).astype(np.float32)
        h_ref = rows.host(ref.grad_hess(score)[1]).astype(np.float32)
        h_prog = np.asarray(_lambda_grad_padded(
            jnp.asarray(s32), jnp.asarray(rel), plan.row_ids, plan.col_ids, plan.Q, plan.S,
            ref.sigma, ref.truncation)[1], np.float32)
        h_ctrl = np.asarray(jnp.asarray(h_ref).astype(jnp.bfloat16).astype(jnp.float32))
        sums = {k: node_hessians(ref, tree, h) for k, h in
                (("reference", h_ref), ("program", h_prog), ("control", h_ctrl))}
        child = np.flatnonzero(np.arange(tree.feature.size) > 0)
        child = child[np.isin(child, np.concatenate([tree.left[tree.feature >= 0],
                                                     tree.right[tree.feature >= 0]]))]
        want = sums["reference"][child]
        floor = float(ref.p["min_child_weight"])
        near = np.abs(want / floor - 1.0)
        band = {"iteration": it, "children": int(child.size),
                "program": gap_summary(sums["program"][child], want),
                "control": gap_summary(sums["control"][child], want),
                "rows_program": gap_summary(h_prog.astype(np.float64).sum(keepdims=True),
                                            h_ref.astype(np.float64).sum(keepdims=True)),
                "children_within": {f"{b:g}": int((near <= b).sum())
                                    for b in (1e-5, 1e-4, 1e-3)}}
        looked = lambda_ops(s32, rel, lengths, ref.sigma, ref.truncation, ids, plan.S)
        at = looked["rows"]
        base = looked["hessians"]["float64"]
        keep = base > 0
        rebuilt = {k: (float(v[keep].sum() / base[keep].sum() - 1.0))
                   for k, v in looked["hessians"].items() if k != "float64"}
        rebuilt["program"] = float(h_prog[at][keep].astype(np.float64).sum()
                                   / base[keep].sum() - 1.0)
        rebuilt["reference"] = float(h_ref[at][keep].astype(np.float64).sum()
                                     / base[keep].sum() - 1.0)
        rebuilt["program_vs_all_largest"] = float(np.abs(
            h_prog[at][keep] / looked["hessians"]["all"][keep] - 1.0).max())
        ops = {"iteration": it, "queries": int(ids.size), "pairs": looked["pairs"],
               "score_spread": float(np.std(s32[at])),
               "device_against_float64": looked["ops"], "hessian_sum_gap": rebuilt}
        for kind, row in (("band", band), ("ops", ops)):
            row = {"kind": kind, "seed": seed, **row}
            print("look " + json.dumps(row), flush=True)
            out.append(row)
        score = ref.add_tree(rows, score, tree, tree.value)
    return out


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
    ap.add_argument("--hessian-look", type=int, default=0, metavar="QUERIES",
                    help="on each grown seed also read the floor band's gaps and the "
                         "λ-pass's operations, the latter over this many queries")
    args = ap.parse_args(argv)

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
    rows, looks = [], []

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
        if args.hessian_look:
            looks += hessian_look(ref, sound, k, seed, args.hessian_look)
            if args.out:
                with open(os.path.splitext(args.out)[0] + "_look.json", "w") as f:
                    json.dump(looks, f)
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
    bands = [r for r in looks if r["kind"] == "band"]
    for side in ("program", "control") if bands else ():
        print(f"summary band {side}: largest child gap "
              f"{max(r[side]['largest'] for r in bands):.3e}, least of the trees' largest "
              f"{min(r[side]['largest'] for r in bands):.3e}, median of medians "
              f"{float(np.median([r[side]['median'] for r in bands])):.3e}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
