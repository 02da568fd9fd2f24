"""The plain reference for a LambdaMART job: ``gbdt_bestfirst.py``'s best-first
growth under λ-gradients over ragged query groups, and NDCG@k.

Everything that grows or follows a tree is ``gbdt.py``'s and
``gbdt_bestfirst.py``'s, imported (float32 histograms in three bfloat16
limbs, float64 gains, the best-first replay); this module brings what a
ranking job adds, and imports nothing of the program:

* **λ-gradients**, ``lambda_grad_hess``: per query a stable descending sort of
  the scores (ties keep the row order inside the query), gains ``2^rel - 1``,
  discounts ``1 / log2(rank + 2)``, and for every pair of documents with
  different grades of which at least one ranks in the top ``truncation``:
  ``rho = 1 / (1 + exp(sigma (s_hi - s_lo)))``, ``|dNDCG| = |gain_i - gain_j|
  |disc_i - disc_j| / maxDCG`` (maxDCG over all of the query's documents),
  ``lambda = sigma rho |dNDCG|`` pushed down on the better document's
  gradient and up on the worse one's, ``sigma^2 rho (1 - rho) |dNDCG|`` on
  both hessians.  The formulation is not the program's (a masked ``S x S``
  grid of a plan padded to the longest query): queries are sorted by length
  and taken in batches padded to the batch's longest, and of a query's grid
  only the ``min(truncation, L) x L`` rows of the top-ranked documents are
  made, each unordered pair once (6.8e7 cells a pass at the MS LTR shape, where
  the padded plan walks 3.0e10).  **float64 on the host** (numpy, a few
  threads): the choice is the simpler of the two the issue allows, it needs no
  device blocks and no ``highest`` precision, and a pass costs about a second.
  Sums are then rounded to float32 once, as the histograms take them.
* **the floor's band**, ``FLOOR_ROUNDING_RANK``: the job's λ-hessians are
  float32 on the chip, whose ``log2`` reads the discounts' logarithms high by
  up to 5.7e-5 of themselves, so its hessian sums lie under these by about
  8e-6 of themselves and differ by up to 2.2e-5 (a child's sum, the job's
  λ-pass on the same scores against this module's, summed alike); a candidate
  whose child lies within the band of ``min_child_weight`` counts as either,
  as in ``gbdt_bestfirst.py``.  ``tools/readings_rank.py --hessian-look``
  reads the gaps and names the operation (readings and the choice: PERF.md
  section 2).
* **NDCG@k**, ``ndcg_at``: per query in float64, the same sort, a query whose
  ideal DCG is 0 counted as 1 (LightGBM's convention, and the program's).
* **ties.**  After one tree the scores take at most ``num_leaves`` values, so
  most of a query is tied and its ranking is the row order.  The reference
  carries its scores forward with the **job's own leaf values** (float32 adds
  in tree order, as the program's score column), so its ties are the job's
  ties; the leaf values themselves are held by ``leaf_value_gap``, which sets
  them against ``-lr G/(H + l2)`` of the reference's own gradients.
  ``valid_metric_gap`` is absolute: NDCG lives in [0, 1].
* faults for the readings (``benchmark/tools/readings_rank.py``), planted in
  the gradients or the metric of the reference put in the job's place:
  ``no_delta_ndcg`` (RankNet's gradients), ``all_pairs`` (truncation ignored),
  ``shifted_boundaries`` (every inner query boundary one row late),
  ``ndcg_one_query`` (the valid set scored as one query).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np

from benchmark.reference.gbdt import INNER, ROWS, Reference, Rows, leaf_value_gap
from benchmark.reference.gbdt_bestfirst import BestFirst

CELLS_A_BATCH = 1 << 21       # pair cells of one batch of queries
FLOOR_ROUNDING_RANK = 1e-4    # relative: 4.5 times the largest gap of a child's hessian sum
GRADIENT_FAULTS = ("no_delta_ndcg", "all_pairs", "shifted_boundaries")


def _batches(lengths: np.ndarray, top: int | None):
    """Queries sorted by length, cut into runs of at most ``CELLS_A_BATCH``
    pair cells: ``(query ids, width)``."""
    order = np.argsort(lengths, kind="stable")
    out, lo = [], 0
    while lo < order.size:
        hi = lo
        while hi < order.size:
            width = int(lengths[order[hi]])
            rows = width if top is None else min(top, width)
            if hi > lo and (hi + 1 - lo) * rows * width > CELLS_A_BATCH:
                break
            hi += 1
        out.append((order[lo:hi], int(lengths[order[hi - 1]])))
        lo = hi
    return out


def _padded(ids, width, offsets, lengths, score, rel):
    """One batch of queries, each ``[queries, width]``: the row index and
    presence of every slot in row order, the ranking ``order`` (absent slots
    last), and score, grade and presence in ranked order."""
    col = np.arange(width)
    present = col[None, :] < lengths[ids, None]
    row = np.where(present, offsets[ids, None] + col[None, :], 0)
    s = np.where(present, score[row], -np.inf)
    r = np.where(present, rel[row], 0.0)
    order = np.argsort(-s, axis=1, kind="stable")
    return (row, present, order, np.take_along_axis(s, order, 1),
            np.take_along_axis(r, order, 1), np.take_along_axis(present, order, 1))


def lambda_grad_hess(score, rel, lengths, sigma: float = 1.0, truncation: int | None = 30,
                     delta_ndcg: bool = True, threads: int = 8):
    """float32 ``(g, h)`` of every row; the docstring above has the equations.
    ``truncation=None`` keeps every pair; ``delta_ndcg=False`` weighs every
    pair 1 (RankNet)."""
    score = np.asarray(score, np.float64).reshape(-1)
    rel = np.asarray(rel, np.float64).reshape(-1)
    lengths = np.asarray(lengths, np.int64)
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    g = np.zeros(score.shape[0], np.float64)
    h = np.zeros(score.shape[0], np.float64)

    def one(batch):
        ids, width = batch
        row, present, order, s, r, there = _padded(ids, width, offsets, lengths, score, rel)
        top = width if truncation is None else min(int(truncation), width)
        pos = np.arange(width)
        disc = 1.0 / np.log2(pos + 2.0)
        gain = np.where(there, np.exp2(r) - 1.0, 0.0)
        ideal = -np.sort(-gain, axis=1)                    # gains are monotone in the grade
        max_dcg = (ideal * disc).sum(axis=1)
        inv = np.where(max_dcg > 0, 1.0 / np.where(max_dcg > 0, max_dcg, 1.0), 0.0)
        with np.errstate(invalid="ignore", over="ignore"):
            sign = np.sign(r[:, :top, None] - r[:, None, :])
            pair = ((pos[None, None, :] > pos[None, :top, None]) & (sign != 0)
                    & there[:, :top, None] & there[:, None, :])
            rho = 1.0 / (1.0 + np.exp(sigma * sign * (s[:, :top, None] - s[:, None, :])))
            weight = 1.0
            if delta_ndcg:
                weight = (np.abs(gain[:, :top, None] - gain[:, None, :])
                          * np.abs(disc[None, :top, None] - disc[None, None, :])
                          * inv[:, None, None])
            lam = np.where(pair, sigma * rho * weight, 0.0)
            hes = np.where(pair, sigma * sigma * rho * (1.0 - rho) * weight, 0.0)
        gs = (sign * lam).sum(axis=1)                      # the pair's lower-ranked document
        hs = hes.sum(axis=1)
        gs[:, :top] -= (sign * lam).sum(axis=2)            # its top-ranked one
        hs[:, :top] += hes.sum(axis=2)
        gq, hq = np.empty_like(gs), np.empty_like(hs)
        np.put_along_axis(gq, order, gs, axis=1)
        np.put_along_axis(hq, order, hs, axis=1)
        g[row[present]] = gq[present]
        h[row[present]] = hq[present]

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one, _batches(lengths, truncation)))
    return g.astype(np.float32), h.astype(np.float32)


def ndcg_at(rel, score, lengths, k: int = 10) -> float:
    """Mean NDCG@k over the queries, float64; a query with no relevant
    document counts as 1."""
    score = np.asarray(score, np.float64).reshape(-1)
    rel = np.asarray(rel, np.float64).reshape(-1)
    lengths = np.asarray(lengths, np.int64)
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    total = 0.0
    for ids, width in _batches(lengths, k):
        _, _, _, _, r, there = _padded(ids, width, offsets, lengths, score, rel)
        top = min(k, width)
        disc = 1.0 / np.log2(np.arange(top) + 2.0)
        gain = np.where(there, np.exp2(r) - 1.0, 0.0)
        dcg = (gain[:, :top] * disc).sum(axis=1)
        idcg = (-np.sort(-gain, axis=1)[:, :top] * disc).sum(axis=1)
        total += float(np.where(idcg == 0, 1.0, dcg / np.where(idcg == 0, 1.0, idcg)).sum())
    return total / max(lengths.size, 1)


class RankRows(Rows):
    """``Rows`` in query groups: ``lengths[i]`` rows of query ``i``, contiguous."""

    def __init__(self, q: np.ndarray, y: np.ndarray, lengths: np.ndarray):
        super().__init__(q, y)
        self.lengths = np.asarray(lengths, np.int64)
        if int(self.lengths.sum()) != self.n:
            raise ValueError("query lengths must sum to the rows")

    def device(self, x: np.ndarray):
        """A host column in the blocks' shape, padded with noughts."""
        x = np.pad(np.asarray(x, np.float32), (0, self.padded - self.n))
        return jnp.asarray(x).reshape(self.outer, INNER, ROWS)


class _RankJob:
    """What a ranking job changes of a reference: the gradients, the metric,
    the initial score, and whose leaf values carry the scores."""

    def rank_setup(self, fault: str | None = None):
        p = self.p
        self.sigma = float(p.get("sigmoid", 1.0))
        self.truncation = int(p.get("lambdarank_truncation", 30))
        self.ndcg_k = int(p.get("ndcg_at", 10))
        self.fault = fault
        self.metric = "ndcg"

    def grad_hess(self, score):
        rows, fault = self.train, self.fault
        lengths = rows.lengths
        if fault == "shifted_boundaries" and lengths.size > 1:
            lengths = lengths.copy()
            lengths[0] += 1
            lengths[-1] -= 1
            lengths = lengths[lengths > 0]
        g, h = lambda_grad_hess(rows.host(score), rows.y_host, lengths, self.sigma,
                                None if fault == "all_pairs" else self.truncation,
                                delta_ndcg=fault != "no_delta_ndcg")
        return rows.device(g), rows.device(h)

    def valid_metric(self, vscore) -> float:
        rows = self.valid
        lengths = np.array([rows.n]) if self.fault == "ndcg_one_query" else rows.lengths
        return ndcg_at(rows.y_host, rows.host(vscore), lengths, self.ndcg_k)

    def grow(self, iterations: int, bf16: bool = False) -> dict:
        """Train ``iterations`` trees: what a job would hand over, with the
        valid metric after each.  The initial score of lambdarank is 0."""
        score = self.train.start(0.0)
        vscore = self.valid.start(0.0) if self.valid else None
        trees, evals = [], {}
        for it in range(iterations):
            g, h = self.grad_hess(score)
            tree, _ = self.one_tree(g, h, None, bf16)
            score = self.add_tree(self.train, score, tree, tree.value)
            if self.valid:
                vscore = self.add_tree(self.valid, vscore, tree, tree.value)
                evals[it] = self.valid_metric(vscore)
            trees.append(tree)
        return {"trees": trees, "init_score": 0.0, "evals": evals}

    def window(self, job: dict, iterations: int, bf16: bool = False):
        """``gbdt.Reference.window`` under this job's gradients."""
        rows, trees = self.train, job["trees"]
        score = rows.start(0.0)
        for it, tree in enumerate(trees):
            if it >= len(trees) - iterations:
                g, h = self.grad_hess(score)
                yield it, tree, self.one_tree(g, h, tree, bf16, levels=1)[1]
            score = self.add_tree(rows, score, tree, tree.value)


class RankLevelwise(_RankJob, Reference):
    """``gbdt.Reference`` (level by level) under λ-gradients: the stand-in of
    the fault "a tree grown level by level"."""

    def __init__(self, params: dict, train: RankRows, valid: RankRows | None):
        Reference.__init__(self, params, train, valid)
        self.rank_setup()


class RankBestFirst(_RankJob, BestFirst):
    floor_rounding = FLOOR_ROUNDING_RANK

    def __init__(self, params: dict, train: RankRows, valid: RankRows | None, depth_cap: int,
                 fault: str | None = None):
        BestFirst.__init__(self, params, train, valid, depth_cap)
        self.rank_setup(fault)

    def follow(self, job: dict, iterations: int) -> dict:
        """``BestFirst.follow`` for a ranking job: the reference's own
        λ-gradients from scores carried with the job's own leaf values, and
        NDCG@k of the valid rows under the job's trees."""
        out = {"init_score_gap": abs(float(job["init_score"])),
               "split_flip_share": 1.0, "leaf_value_gap": 0.0, "valid_metric_gap": 0.0,
               "order_gain_gap": 0.0, "cap_stopped_steps": [], "tree_depths": [],
               "per_tree": []}
        flipped = split = 0
        score = self.train.start(0.0)
        vscore = self.valid.start(float(job["init_score"])) if self.valid else None
        trees = job["trees"]

        def gap(got, want):
            return abs(got - want) if np.isfinite(got) else float("inf")

        for it in range(min(iterations, len(trees))):
            g, h = self.grad_hess(score)
            tree, facts = self.one_tree(g, h, trees[it])
            leaf_gap, worst = leaf_value_gap(tree, facts)
            score = self.add_tree(self.train, score, tree, tree.value)
            row = {"iteration": it, "order_gain_gap": facts["order_gain_gap"],
                   "leaf_value_gap": leaf_gap, "leaves": int(facts["leaves"].sum()),
                   "depth": facts["depth"], "cap_stopped_steps": facts["cap_stopped_steps"],
                   "flips": facts["flips"], "root_gain_gap": facts["level_gain_gap"][0],
                   "hessian_sum": float(np.asarray(h, np.float64).sum()),
                   "worst_order_steps": [int(k) for k in np.argsort(facts["order_gaps"])[::-1][:3]],
                   "worst_leaf_gap": float(worst.max()) if worst.size else None}
            if self.valid:
                vscore = self.add_tree(self.valid, vscore, tree, tree.value)
                want = self.valid_metric(vscore)
                got = job["evals"].get(it, float("nan"))
                row["valid_metric"] = [got, want]
                out["valid_metric_gap"] = max(out["valid_metric_gap"], gap(got, want))
            flipped += facts["flips"][0][0]
            split += facts["flips"][0][1]
            out["order_gain_gap"] = max(out["order_gain_gap"], facts["order_gain_gap"])
            out["leaf_value_gap"] = max(out["leaf_value_gap"], leaf_gap)
            out["cap_stopped_steps"].append(facts["cap_stopped_steps"])
            out["tree_depths"].append(facts["depth"])
            out["per_tree"].append(row)
        if split:
            out["split_flip_share"] = flipped / split
        if self.valid and trees:
            last = len(trees) - 1
            for tree in trees[min(iterations, len(trees)):]:
                vscore = self.add_tree(self.valid, vscore, tree, tree.value)
            want = self.valid_metric(vscore)
            got = job["evals"].get(last, float("nan"))
            out["last_valid_metric"] = [last, got, want]
            out["valid_metric_gap"] = max(out["valid_metric_gap"], gap(got, want))
        return out
