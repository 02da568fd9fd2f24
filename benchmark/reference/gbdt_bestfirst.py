"""The plain reference for leaf-wise (best-first) growth.

``gbdt.py`` walks ``max_depth`` levels with a leaf budget a level; a
best-first tree has no levels and, with ``max_depth = -1``, no depth either.
This module keeps everything of ``gbdt.py`` that does not walk levels (the
rows, the one-hot histograms in three bfloat16 limbs, the float64 gains, the
routing by thresholds, the metrics; it imports nothing of the program) and
states the growth policy anew:

    The tree starts as one leaf.  At each of ``num_leaves - 1`` steps the
    leaf of largest best-split gain is split, among the leaves that may
    still be split: its depth is under ``depth_cap``, it holds at least
    ``2 min_data_in_leaf`` rows, and its best split (each child with
    ``min_data_in_leaf`` rows and ``min_child_weight`` hessian) gains more
    than ``min_split_gain``.  Growth stops when no leaf may be split.

``depth_cap`` comes from the configuration's file (the program's documented
policy for unbounded leaf-wise growth); the reference applies it as stated.

``follow`` (the first trees of a job): its own gradients from its own scores,
its own float32 histograms of **every node of the job's tree, split nodes
and leaves**, one pass over the rows per depth of the tree.  From them the
level-wise reference's numbers (``split_flip_share``, ``leaf_value_gap``,
``valid_metric_gap``, ``init_score_gap``) and ``order_gain_gap``: the job's
splits are replayed with a frontier of leaves in the job's own order
(``split_order``), and at each step the best gain among the frontier leaves
that may be split, less the gain of the leaf the job split, over the former,
is taken; the worst step counts.  A tree grown level by level, or one whose
selection skipped a leaf, fails it.  Printed and not compared:
``cap_stopped_steps`` (steps at which the frontier's best leaf lay at
``depth_cap`` and was passed over for that alone) and the tree's depth.

``follow_window``, ``restate`` and ``grow`` are ``gbdt.Reference``'s own,
over this module's ``one_tree`` and ``add_tree``.  ``grow`` is sequential
best-first with a look-ahead: the children of the ``LOOKAHEAD`` best leaves
are made in one pass over the rows (a leaf's histogram does not depend on
when its parent is split), so a tree costs about ten passes, not 254.

The floor ``min_child_weight`` binds here (100 where a leaf holds a few
hundred rows).  The reference applies it to both children as the
configuration states; only a candidate whose hessian sum lies within
``floor_rounding`` of the floor counts as either: the reference's best takes
it as refused, the job's own choice as allowed.  The band is the rounding of
the job's hessian sums against the reference's: ``FLOOR_ROUNDING`` where both
take the same hessians, a subclass's own where the job computes them otherwise.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmark.reference.gbdt import (BINS, Reference, Rows, Tree, _add_values, _grad_hess,
                                      _merge, _node_sums, _route_step, init_score,
                                      leaf_value_gap, metric_gap, node_rows)

LOOKAHEAD = 36            # leaves whose children one pass of ``grow`` makes
FLOOR_ROUNDING = 1e-5     # relative: float32 sums of up to 1e7 hessians


def depths_of(tree: Tree) -> np.ndarray:
    """Depth of every node reachable from the root; -1 for the others."""
    depth = np.full(tree.feature.shape[0], -1, np.int32)
    depth[0] = 0
    stack = [0]
    while stack:
        n = stack.pop()
        if tree.feature[n] >= 0:
            for c in (int(tree.left[n]), int(tree.right[n])):
                depth[c] = depth[n] + 1
                stack.append(c)
    return depth


def split_order(tree: Tree, gain_of=None) -> list[int]:
    """The job's split nodes in the order it split them.  The program numbers
    nodes in execution order: step ``k`` makes the children ``2k + 1`` and
    ``2k + 2``.  A tree that is not numbered so is replayed greedily over its
    own splits: of the frontier leaves it did split, the one of largest
    ``gain_of`` first."""
    splits = [n for n in np.flatnonzero(depths_of(tree) >= 0) if tree.feature[n] >= 0]
    by_left = sorted(splits, key=lambda n: int(tree.left[n]))
    if all(int(tree.left[n]) == 2 * k + 1 and int(tree.right[n]) == 2 * k + 2
           for k, n in enumerate(by_left)):
        return [int(n) for n in by_left]
    order, frontier = [], [0]
    while True:
        ready = [n for n in frontier if tree.feature[n] >= 0]
        if not ready:
            return order
        n = max(ready, key=lambda m: gain_of[m] if gain_of is not None else -m)
        frontier.remove(n)
        frontier += [int(tree.left[n]), int(tree.right[n])]
        order.append(int(n))


class BestFirst(Reference):
    floor_rounding = FLOOR_ROUNDING

    def __init__(self, params: dict, train: Rows, valid: Rows | None, depth_cap: int):
        super().__init__(params, train, valid)
        self.depth_cap = int(depth_cap)
        self.max_leaves = int(params["num_leaves"])

    # -- gains under the floor's two readings -------------------------------
    def gains_either(self, hist: np.ndarray):
        """``gains`` with the hessian floor raised by its rounding (what is
        surely allowed) and lowered by it (what may be)."""
        p = self.p
        l2 = float(p["lambda_l2"])
        G, H, C = (hist[i, :, 0, :].sum(axis=1) for i in range(3))
        GL, HL, CL = (np.cumsum(hist[i], axis=2) for i in range(3))
        GR, HR, CR = (G[:, None, None] - GL, H[:, None, None] - HL, C[:, None, None] - CL)
        with np.errstate(invalid="ignore", divide="ignore"):
            gain = 0.5 * (GL * GL / (HL + l2) + GR * GR / (HR + l2)
                          - (G * G / (H + l2))[:, None, None])
        rows_ok = (CL >= p["min_data_in_leaf"]) & (CR >= p["min_data_in_leaf"])

        def kept(floor):
            return np.where(rows_ok & (HL >= floor) & (HR >= floor), gain, -np.inf)

        floor = float(p["min_child_weight"])
        band = self.floor_rounding
        return kept(floor * (1 + band)), kept(floor * (1 - band)), G, H, C

    def may_split(self, depth, count, best):
        """The leaves that may still be split, but for the depth cap."""
        p = self.p
        return ((count >= 2 * p["min_data_in_leaf"]) & np.isfinite(best)
                & (best > p["min_split_gain"])), depth < self.depth_cap

    # -- moving rows: a tree has the depth it has ----------------------------
    def add_tree(self, rows: Rows, score, tree: Tree, values: np.ndarray):
        node = self.route(rows, tree, int(depths_of(tree).max()))
        return _add_values(score, node, jnp.asarray(values, jnp.float32))

    # -- every node of a given tree ------------------------------------------
    def node_facts(self, g, h, tree: Tree, bf16: bool, levels: int | None):
        """Histograms of the nodes of the first ``levels`` depths of ``tree``
        (all of them when None), one pass a depth: per node its depth, the
        best gain (floor surely kept), the gain of the tree's own split (floor
        maybe kept), and its rows.  Then the rows of every leaf."""
        rows = self.train
        M = tree.feature.shape[0]
        depth = depths_of(tree)
        last = int(depth.max())
        best = np.full(M, -np.inf)
        got = np.zeros(M)
        count = np.zeros(M)
        node = jnp.where(rows.real, 0, -1).astype(jnp.int32)
        tabs = self.tables(tree)
        for d in range(last + 1):
            if levels is None or d < levels:
                at = np.flatnonzero(depth == d)
                slot_of = np.full(M + 1, -1, np.int32)          # last entry: padded rows
                slot_of[at] = np.arange(len(at))
                hist = self.level_hist(jnp.asarray(slot_of)[node], g, h, len(at), bf16)
                sure, maybe, _, _, C = self.gains_either(hist)
                best[at] = sure.reshape(len(at), -1).max(axis=1)
                count[at] = C
                for i, n in enumerate(at):
                    f = int(tree.feature[n])
                    if f >= 0:
                        t = int(np.floor(tree.threshold[n]))
                        own = maybe[i, f, t] if 0 <= t < BINS else -np.inf
                        got[n] = float(own) if np.isfinite(own) and own > 0 else 0.0
            if d < last:
                node = _route_step(rows.q, node, *tabs)
        sums = _merge(np.asarray(_node_sums(node, g, h, nodes=M, bf16=bf16)), bf16)
        return depth, best, got, count, sums

    def order_gaps(self, tree: Tree, depth, best, got, count) -> dict:
        """Replay the tree's splits over a frontier of leaves.  A step's gap
        is the share of the frontier's best gain that the job's choice lost;
        1 where it split a leaf that may not be split, or stopped while one
        still might."""
        p = self.p
        can, shallow = self.may_split(depth, count, best)
        frontier = {0}
        gaps, stopped = [], 0

        def top_of():
            leaves = np.fromiter(frontier, int)
            open_ = leaves[can[leaves] & shallow[leaves]]
            top = float(best[open_].max()) if open_.size else 0.0
            any_depth = leaves[can[leaves]]
            past = any_depth[np.argmax(best[any_depth])] if any_depth.size else None
            return top, bool(past is not None and not shallow[past] and best[past] > top)

        order = split_order(tree, got)
        for n in order:
            top, capped = top_of()
            stopped += capped
            if not (shallow[n] and count[n] >= 2 * p["min_data_in_leaf"]
                    and got[n] > p["min_split_gain"]):
                gaps.append(1.0)
            else:
                gaps.append(max(top - got[n], 0.0) / top if top > 0 else 0.0)
            frontier.discard(n)
            frontier |= {int(tree.left[n]), int(tree.right[n])}
        if len(order) < self.max_leaves - 1 and top_of()[0] > 0:
            gaps.append(1.0)
        gaps = [float(gap) for gap in gaps]
        return {"order_gain_gap": max(gaps, default=0.0), "order_gaps": gaps,
                "cap_stopped_steps": stopped}

    # -- one tree: follow the job's, or grow one ------------------------------
    def one_tree(self, g, h, given: Tree | None, bf16: bool = False, levels: int | None = None):
        """With ``given``: ``(given, facts)``, the reference's own numbers for
        every node of the tree (or of its first ``levels`` depths; the rows go
        down the rest by its thresholds).  Without: a tree grown best-first."""
        if given is None:
            return self.grow_tree(g, h, bf16)
        depth, best, got, count, sums = self.node_facts(g, h, given, bf16, levels)
        values = np.where(given.feature < 0, np.nan_to_num(self.leaf_values(sums)), 0.0)
        split = np.flatnonzero((depth >= 0) & (given.feature >= 0)
                               & (depth < (levels if levels is not None else depth.max() + 1)))
        flipped = int(sum(best[n] - got[n] > 1e-9 * abs(best[n]) for n in split))
        root = (best[0] - got[0]) / best[0] if best[0] > 0 else float(got[0] <= 0 and split.size > 0)
        facts = {"level_gain_gap": [max(float(root), 0.0)], "flips": [(flipped, int(split.size))],
                 "leaves": (given.feature < 0) & (sums[2] > 0), "values": values,
                 "count": sums[2], "depth": int(depth.max())}
        if levels is None:
            facts.update(self.order_gaps(given, depth, best, got, count))
        return given, facts

    def grow_tree(self, g, h, bf16: bool = False):
        """Sequential best-first growth.  ``E`` is the tree of every split
        made so far, the chosen ones and the look-ahead's; the rows sit at its
        leaves.  The tree handed back holds the chosen splits alone, numbered
        in the order they were made."""
        rows, L = self.train, self.max_leaves
        cap_e = 8 * L + 1
        e_feat = np.full(cap_e, -1, np.int32)
        e_thr = np.zeros(cap_e, np.float32)
        e_left = np.zeros(cap_e, np.int32)
        e_right = np.zeros(cap_e, np.int32)
        e_depth = np.zeros(cap_e, np.int32)
        e_best = np.full(cap_e, -np.inf)
        e_split = np.zeros((cap_e, 2), np.int64)          # best (feature, bin)
        e_sums = np.zeros((3, cap_e))
        n_e = 1
        node = jnp.where(rows.real, 0, -1).astype(jnp.int32)

        def histograms(ids):
            slot_of = np.full(cap_e + 1, -1, np.int32)
            slot_of[ids] = np.arange(len(ids))
            hist = self.level_hist(jnp.asarray(slot_of)[node], g, h, len(ids), bf16)
            gain, G, H, C = self.gains(hist)
            flat = gain.reshape(len(ids), -1)
            e_best[ids] = flat.max(axis=1)
            e_split[ids] = np.stack(np.divmod(flat.argmax(axis=1), BINS), axis=1)
            e_sums[:, ids] = np.stack([G, H, C])

        def open_(ids):
            ids = np.asarray(ids, int)
            can, shallow = self.may_split(e_depth[ids], e_sums[2, ids], e_best[ids])
            return can & shallow

        histograms(np.array([0]))
        tree = Tree(np.full(2 * L + 1, -1, np.int32), np.zeros(2 * L + 1, np.float32),
                    np.zeros(2 * L + 1, np.int32), np.zeros(2 * L + 1, np.int32),
                    np.zeros(2 * L + 1, np.float64), np.zeros(2 * L + 1, np.float64))
        slots = [(0, 0)]                                  # (node of E, node of the tree)
        n_t = 1
        for step in range(L - 1):
            ids = [e for e, _ in slots]
            ok = open_(ids)
            if not ok.any():
                break
            s = int(np.argmax(np.where(ok, e_best[ids], -np.inf)))      # first of the largest
            e, t = slots[s]
            if e_feat[e] < 0:
                # look ahead: split the best open leaves that have no children yet
                ahead = [x for x in np.asarray(ids)[ok] if e_feat[x] < 0]
                room = (cap_e - n_e) // 2 - (L - 2 - step)       # the later steps' own
                ahead = sorted(ahead, key=lambda x: -e_best[x])[:min(LOOKAHEAD, room)]
                made = []
                for x in ahead:
                    f, b = e_split[x]
                    e_feat[x], e_thr[x] = f, b + 0.5
                    e_left[x], e_right[x] = n_e, n_e + 1
                    e_depth[[n_e, n_e + 1]] = e_depth[x] + 1
                    made += [n_e, n_e + 1]
                    n_e += 2
                node = _route_step(rows.q, node, jnp.asarray(e_feat), jnp.asarray(e_thr),
                                   jnp.asarray(e_left), jnp.asarray(e_right))
                histograms(np.array(made))
            tree.feature[t], tree.threshold[t] = e_feat[e], e_thr[e]
            tree.left[t], tree.right[t] = n_t, n_t + 1
            slots[s] = (int(e_left[e]), n_t)              # the left child keeps the slot
            slots.append((int(e_right[e]), n_t + 1))
            n_t += 2
        for e, t in slots:
            tree.value[t] = np.nan_to_num(self.leaf_values(e_sums[:, e]))
            tree.cover[t] = e_sums[2, e]
        tree.cover = node_rows(tree, tree.cover)
        return tree, {}

    # -- the first trees of a job ---------------------------------------------
    def follow(self, job: dict, iterations: int) -> dict:
        """The numbers that decide ``correct`` for the first ``iterations``
        trees of ``job`` (``trees``, ``init_score``, ``evals``), and the valid
        metric of all its trees at its last iteration."""
        s0 = init_score(self.train.y_host, self.objective)
        out = {"init_score_gap": abs(float(job["init_score"]) - s0) / max(abs(s0), 1.0),
               "split_flip_share": 1.0, "leaf_value_gap": 0.0, "valid_metric_gap": 0.0,
               "order_gain_gap": 0.0, "cap_stopped_steps": [], "tree_depths": [],
               "per_tree": []}
        flipped = split = 0
        score = self.train.start(s0)
        vscore = self.valid.start(s0) if self.valid else None
        trees = job["trees"]
        for it in range(min(iterations, len(trees))):
            g, h = _grad_hess(score, self.train.y, objective=self.objective)
            tree, facts = self.one_tree(g, h, trees[it])
            leaf_gap, worst = leaf_value_gap(tree, facts)
            score = self.add_tree(self.train, score, tree, facts["values"])
            row = {"iteration": it, "order_gain_gap": facts["order_gain_gap"],
                   "leaf_value_gap": leaf_gap, "leaves": int(facts["leaves"].sum()),
                   "depth": facts["depth"], "cap_stopped_steps": facts["cap_stopped_steps"],
                   "flips": facts["flips"], "root_gain_gap": facts["level_gain_gap"][0],
                   "worst_order_steps": [int(k) for k in np.argsort(facts["order_gaps"])[::-1][:3]],
                   "worst_leaf_gap": float(worst.max()) if worst.size else None}
            if self.valid:
                vscore = self.add_tree(self.valid, vscore, tree, facts["values"])
                want = self.valid_metric(vscore)
                got = job["evals"].get(it, float("nan"))
                row["valid_metric"] = [got, want]
                out["valid_metric_gap"] = max(out["valid_metric_gap"],
                                              metric_gap(self.metric, got, want))
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
            vs = self.valid.start(float(job["init_score"]))
            for tree in trees:
                vs = self.add_tree(self.valid, vs, tree, tree.value)
            want = self.valid_metric(vs)
            got = job["evals"].get(last, float("nan"))
            out["last_valid_metric"] = [last, got, want]
            out["valid_metric_gap"] = max(out["valid_metric_gap"],
                                          metric_gap(self.metric, got, want))
        return out
