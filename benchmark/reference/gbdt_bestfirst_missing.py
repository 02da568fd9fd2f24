"""The best-first reference with learned missing-value directions.

``gbdt_bestfirst.BestFirst`` scans one plane: a split sends ``x <= t`` left,
and a value the table does not hold has no place of its own.  This module
keeps its growth policy, its numbers and ``gbdt.py``'s arithmetic (one-hot
histograms in three bfloat16 limbs, float32 sums, float64 gains; it imports
nothing of the program and takes no table of it) and states anew what a
table with empty cells needs:

* **Bins.**  A row's bin of a column is its value ``1..bins-1``, and 0
  where the cell is empty (``datagen/bosch.py``'s ``q``).  Histograms have
  ``bins`` lanes a column, the configuration's ``max_bins`` (64): a
  256-lane histogram of the same rows would be the same sums with 192 empty
  lanes, at four times the work.  Rows are padded to blocks of
  ``ROWS x INNER`` = 8192, so that a rehearsal's few thousand rows do not
  cost 32768.
* **The split rule, both planes.**  At a node, for a column ``f`` and a
  threshold ``t``: plane 0 ("missing goes left") sends bins ``0..t`` left;
  plane 1 ("missing goes right") sends bins ``1..t`` left and bin 0 right.
  Both are scored with ``gbdt.py``'s gain and floors.  A plane-1 split whose
  right child would hold no row of bins ``1..`` (only empty cells) is
  excluded: it is plane 0's ``t = 0`` with its sides swapped.  The best of
  a node is the first of the largest gain in the order plane, column,
  threshold, so plane 0 wins a tie, as it wins every split on a column with
  no empty cell at the node.
* **Routing.**  Every tree node carries ``default_left``.  A row whose cell
  is empty goes left where the node's ``default_left`` says so, and right
  otherwise; any other row goes left where ``x <= threshold``.  So in the
  grower, in the window's routing and in the valid set's scores alike.
  ``MissTree`` is ``gbdt.Tree`` with that flag; a job's tree is read back
  with it (``train_job_missing.trees_of_missing``).

``follow`` scores the job's split of a node on the plane its
``default_left`` names, so a direction the job got wrong loses the gain the
other plane had and counts as a flip.  ``split_flip_share`` counts a split
node that loses more than ``FLIP_ROUNDING`` of the sum of its best split's
three terms and more than 1e-9 of the gain (``strict_flip_share`` the
latter alone), as ``gbdt_bestfirst_cat`` does: within a node, two stations
whose empty cells are each other's complement give the same partition from
two columns with the sides swapped, and float32 sums rank such a pair by
rounding alone.  ``flip_rel`` keeps the largest losses.

Faults of this configuration that can be grown in the job's place
(``benchmark/tools/readings_missing.py``): ``planes=1`` scans plane 0 alone
(the program with ``learn_missing`` off: missing rows always go left);
``misroute_depth=d`` routes the empty cells of every split at depth ``d``
against the direction the tree keeps; ``level_order=True`` grows the same
number of leaves a level at a time; ``eval_ignores_direction=True`` scores
the valid rows with every empty cell sent left (the eval walk ignoring
``default_left``), the trees as grown.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gbdt import (Tree, _add_values, _channels, _merge, _node_sums,
                                      _pad_slots, _pick, node_rows)
from benchmark.reference.gbdt_bestfirst import (FLOOR_ROUNDING, LOOKAHEAD, BestFirst,
                                                depths_of)

ROWS = 512           # rows of one block
INNER = 16           # blocks of one partial sum
LANES = 32768        # widest one-hot of a column group: group * bins
# a split node's loss over its three terms past which it counts as flipped
# (the module's docstring); PERF.md section 2 gives the readings it sits
# between
FLIP_ROUNDING = 6e-6
FLIP_REL_KEPT = 32


@dataclasses.dataclass
class MissTree(Tree):
    """A tree whose every split node says where an empty cell goes."""
    default_left: np.ndarray | None = None     # bool [M]


class Rows:
    """One set of rows on the device (bins, 0 an empty cell), padded to
    whole partial sums of ``INNER`` blocks of ``ROWS``."""

    def __init__(self, q: np.ndarray, y: np.ndarray):
        self.n, self.features = q.shape
        per = ROWS * INNER
        self.outer = max(1, -(-self.n // per))
        self.padded = self.outer * per
        pad = self.padded - self.n
        shape = (self.outer, INNER, ROWS)
        self.q = jnp.asarray(np.pad(q, ((0, pad), (0, 0)))).reshape(shape + (self.features,))
        self.y = jnp.asarray(np.pad(np.asarray(y, np.float32), (0, pad))).reshape(shape)
        self.real = jnp.asarray(np.arange(self.padded) < self.n).reshape(shape)
        self.y_host = np.asarray(y, np.float32)

    def start(self, score0: float):
        return jnp.full((self.outer, INNER, ROWS), np.float32(score0), jnp.float32)

    def host(self, x) -> np.ndarray:
        return np.asarray(x).reshape(-1)[: self.n]


@functools.partial(jax.jit, static_argnames=("slots", "group", "bins", "bf16"))
def _level_hist(q, slot, g, h, f0, *, slots: int, group: int, bins: int, bf16: bool):
    """``gbdt._level_hist`` at ``bins`` lanes a column:
    [channels * slots, group * bins] for the columns ``f0 .. f0 + group``."""
    rows = q.shape[2]
    col = jnp.arange(slots, dtype=jnp.int32)
    lanes = jnp.arange(bins, dtype=jnp.uint8)

    def block(acc, xs):
        qx, sx, gx, hx = xs
        qx = jax.lax.dynamic_slice_in_dim(qx, f0, group, axis=1)
        w = _channels(gx, hx, bf16)                                # [rows, C]
        at = sx[:, None] == col[None, :]                           # [rows, slots]
        a = jnp.where(at[:, None, :], w[:, :, None], jnp.bfloat16(0)).reshape(rows, -1)
        b = (qx[:, :, None] == lanes).astype(jnp.bfloat16).reshape(rows, group * bins)
        part = jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        return acc + part, None

    def partial_sum(acc, xs):
        part, _ = jax.lax.scan(block, jnp.zeros_like(acc), xs)
        return acc + part, None

    channels = 3 if bf16 else 7
    zero = jnp.zeros((channels * slots, group * bins), jnp.float32)
    out, _ = jax.lax.scan(partial_sum, zero, (q, slot, g, h))
    return out


@jax.jit
def _route_step(q, node, feature, threshold, dleft, left, right):
    """One level down: a row at a split node goes left where its cell is
    empty and the node's ``dleft`` is set, or where its cell holds a value
    ``<= threshold``; a row at a leaf, or at no node (-1), stays."""
    col = jnp.arange(feature.shape[0], dtype=jnp.int32)
    feats = jnp.arange(q.shape[-1], dtype=jnp.int32)

    def block(_, xs):
        qx, nx = xs
        at = nx[..., None] == col
        f = _pick(at, feature)
        x = jnp.sum(jnp.where(f[..., None] == feats, qx.astype(jnp.int32), 0), axis=-1)
        go_left = jnp.where(x == 0, _pick(at, dleft) > 0,
                            x.astype(jnp.float32) <= _pick(at, threshold))
        child = jnp.where(go_left, _pick(at, left), _pick(at, right))
        return None, jnp.where((nx < 0) | (f < 0), nx, child)

    return jax.lax.scan(block, None, (q, node))[1]


def _group(features: int, bins: int) -> int:
    cap = max(1, LANES // bins)
    if features <= cap:
        return features
    return max(d for d in range(1, cap + 1) if features % d == 0)


class BestFirstMissing(BestFirst):
    """``BestFirst`` over rows whose bin 0 is an empty cell, with both
    planes scanned (the module's docstring)."""

    floor_rounding = FLOOR_ROUNDING

    def __init__(self, params: dict, train: Rows, valid: Rows | None, depth_cap: int,
                 planes: int = 2, misroute_depth: int | None = None,
                 level_order: bool = False, eval_ignores_direction: bool = False):
        super().__init__(params, train, valid, depth_cap)
        self.bins = int(params["max_bins"])
        self.group = _group(train.features, self.bins)
        self.planes = int(planes)
        self.misroute_depth = misroute_depth
        self.level_order = level_order
        self.eval_ignores_direction = eval_ignores_direction
        self.flip_log: list = []

    # -- histograms and the two planes ----------------------------------------
    def level_hist(self, slot, g, h, n: int, bf16: bool) -> np.ndarray:
        """float64 [3, n, F, bins]: G, H and count of every node of a level."""
        channels = 3 if bf16 else 7
        slots = _pad_slots(n, channels)
        parts = []
        for f0 in range(0, self.train.features, self.group):
            out = _level_hist(self.train.q, slot, g, h, jnp.int32(f0), slots=slots,
                              group=self.group, bins=self.bins, bf16=bf16)
            out = np.asarray(out).reshape(channels, slots, self.group, self.bins)[:, :n]
            parts.append(_merge(out, bf16))
        return np.concatenate(parts, axis=2)

    def plane_gains(self, hist: np.ndarray, floor: float) -> np.ndarray:
        """float64 [n, planes, F, bins]: the gain of every (plane, column,
        threshold) of every node, -inf where a child misses a floor or the
        split is excluded."""
        p = self.p
        l2 = float(p["lambda_l2"])
        G, H, C = (hist[i, :, 0, :].sum(axis=1)[:, None, None] for i in range(3))
        sides = [np.cumsum(hist[i], axis=2) for i in range(3)]               # plane 0
        planes = [sides]
        if self.planes > 1:
            empty = [hist[i, :, :, :1] for i in range(3)]
            planes.append([s - e for s, e in zip(sides, empty)])             # plane 1
        out = []
        for k, (GL, HL, CL) in enumerate(planes):
            GR, HR, CR = G - GL, H - HL, C - CL
            ok = ((CL >= p["min_data_in_leaf"]) & (CR >= p["min_data_in_leaf"])
                  & (HL >= floor) & (HR >= floor))
            if k == 1:
                ok &= CR > hist[2, :, :, :1]          # a row of bins 1.. goes right
            with np.errstate(invalid="ignore", divide="ignore"):
                gain = 0.5 * (GL * GL / (HL + l2) + GR * GR / (HR + l2) - G * G / (H + l2))
            out.append(np.where(ok, gain, -np.inf))
        return np.stack(out, axis=1)

    def gains_either(self, hist: np.ndarray):
        """``plane_gains`` with the hessian floor raised by its rounding
        (surely allowed) and lowered by it (maybe), and the node's totals."""
        floor = float(self.p["min_child_weight"])
        band = self.floor_rounding
        G, H, C = (hist[i, :, 0, :].sum(axis=1) for i in range(3))
        return (self.plane_gains(hist, floor * (1 + band)),
                self.plane_gains(hist, floor * (1 - band)), G, H, C)

    def best_of(self, gain: np.ndarray):
        """Per node: the best gain and its (plane, column, threshold), the
        first of the largest in that order."""
        flat = gain.reshape(gain.shape[0], -1)
        at = flat.argmax(axis=1)
        F, B = gain.shape[2:]
        plane, rest = np.divmod(at, F * B)
        f, t = np.divmod(rest, B)
        return flat.max(axis=1), plane, f, t

    # -- moving rows ------------------------------------------------------------
    @staticmethod
    def route_tables(feature, threshold, dleft, left, right):
        return (jnp.asarray(feature, jnp.int32), jnp.asarray(threshold, jnp.float32),
                jnp.asarray(dleft, jnp.int32), jnp.asarray(left, jnp.int32),
                jnp.asarray(right, jnp.int32))

    def tables(self, tree: MissTree):
        return self.route_tables(tree.feature, tree.threshold, tree.default_left,
                                 tree.left, tree.right)

    def route(self, rows: Rows, tree: MissTree, levels: int, tabs=None):
        node = jnp.zeros((rows.outer, INNER, ROWS), jnp.int32)
        tabs = tabs or self.tables(tree)
        for _ in range(levels):
            node = _route_step(rows.q, node, *tabs)
        return node

    def add_tree(self, rows: Rows, score, tree: MissTree, values: np.ndarray):
        tabs = None
        if self.eval_ignores_direction and rows is self.valid:
            tabs = self.route_tables(tree.feature, tree.threshold,
                                     np.ones_like(tree.feature, bool), tree.left, tree.right)
        node = self.route(rows, tree, int(depths_of(tree).max()), tabs)
        return _add_values(score, node, jnp.asarray(values, jnp.float32))

    # -- every node of a given tree ---------------------------------------------
    def node_facts(self, g, h, tree: MissTree, bf16: bool, levels: int | None):
        """``BestFirst.node_facts`` over both planes: a node's own gain is
        that of its split on the plane its ``default_left`` names."""
        rows = self.train
        M = tree.feature.shape[0]
        depth = depths_of(tree)
        last = int(depth.max())
        best = np.full(M, -np.inf)
        got = np.zeros(M)
        count = np.zeros(M)
        scale = np.zeros(M)
        l2 = float(self.p["lambda_l2"])
        node = jnp.where(rows.real, 0, -1).astype(jnp.int32)
        tabs = self.tables(tree)
        for d in range(last + 1):
            if levels is None or d < levels:
                at = np.flatnonzero(depth == d)
                slot_of = np.full(M + 1, -1, np.int32)          # last entry: padded rows
                slot_of[at] = np.arange(len(at))
                hist = self.level_hist(jnp.asarray(slot_of)[node], g, h, len(at), bf16)
                sure, maybe, G, H, C = self.gains_either(hist)
                best[at] = sure.reshape(len(at), -1).max(axis=1)
                count[at] = C
                scale[at] = 2 * (np.maximum(best[at], 0) + G * G / (H + l2))
                for i, n in enumerate(at):
                    f = int(tree.feature[n])
                    if f < 0:
                        continue
                    thr = float(tree.threshold[n])
                    t = int(np.floor(thr)) if np.isfinite(thr) else 0
                    plane = 0 if tree.default_left[n] else 1
                    own = (maybe[i, plane, f, t]
                           if plane < maybe.shape[1] and 0 <= t < self.bins else -np.inf)
                    got[n] = float(own) if np.isfinite(own) and own > 0 else 0.0
            if d < last:
                node = _route_step(rows.q, node, *tabs)
        sums = _merge(np.asarray(_node_sums(node, g, h, nodes=M, bf16=bf16)), bf16)
        self.node_gains = (best, got, scale)
        return depth, best, got, count, sums

    def one_tree(self, g, h, given: MissTree | None, bf16: bool = False,
                 levels: int | None = None):
        """``BestFirst.one_tree`` with a split counted as flipped past the
        float32 band (the module's docstring)."""
        tree, facts = super().one_tree(g, h, given, bf16, levels)
        if given is None:
            return tree, facts
        depth = depths_of(given)
        deep = depth.max() + 1 if levels is None else levels
        split = np.flatnonzero((depth >= 0) & (given.feature >= 0) & (depth < deep))
        best, got, scale = (a[split] for a in self.node_gains)
        lost = best - got
        strict = lost > 1e-9 * np.abs(best)
        with np.errstate(invalid="ignore", divide="ignore"):
            rel = np.where(strict, lost / scale, 0.0)
        facts["flips"] = [(int((rel > FLIP_ROUNDING).sum()), int(split.size))]
        if levels is None:
            self.flip_log.append((rel[strict], int(split.size)))
        return tree, facts

    def follow(self, job: dict, iterations: int) -> dict:
        """``BestFirst.follow``, and over its trees' split nodes:
        ``strict_flip_share``, the largest losses over the terms
        (``flip_rel``) and the share whose empty cells go right."""
        self.flip_log = []
        out = super().follow(job, iterations)
        rel = np.sort(np.concatenate([r for r, _ in self.flip_log] + [np.zeros(0)]))[::-1]
        nodes = sum(n for _, n in self.flip_log)
        out["split_nodes"] = nodes
        out["strict_flip_share"] = rel.size / nodes if nodes else 0.0
        out["flip_rel"] = [float(x) for x in rel[:FLIP_REL_KEPT]]
        return out

    # -- a tree grown best-first ------------------------------------------------
    def grow_tree(self, g, h, bf16: bool = False):
        """``BestFirst.grow_tree`` over both planes: a split of ``E`` keeps
        its direction, and the tree handed back carries it."""
        rows, L = self.train, self.max_leaves
        cap_e = 8 * L + 1
        e_feat = np.full(cap_e, -1, np.int32)
        e_thr = np.zeros(cap_e, np.float32)
        e_dleft = np.ones(cap_e, bool)
        e_left = np.zeros(cap_e, np.int32)
        e_right = np.zeros(cap_e, np.int32)
        e_depth = np.zeros(cap_e, np.int32)
        e_best = np.full(cap_e, -np.inf)
        e_split = np.zeros((cap_e, 3), np.int64)          # best (plane, column, bin)
        e_sums = np.zeros((3, cap_e))
        n_e = 1
        node = jnp.where(rows.real, 0, -1).astype(jnp.int32)
        floor = float(self.p["min_child_weight"])

        def histograms(ids):
            slot_of = np.full(cap_e + 1, -1, np.int32)
            slot_of[ids] = np.arange(len(ids))
            hist = self.level_hist(jnp.asarray(slot_of)[node], g, h, len(ids), bf16)
            best, plane, f, t = self.best_of(self.plane_gains(hist, floor))
            e_best[ids] = best
            e_split[ids] = np.stack([plane, f, t], axis=1)
            e_sums[:, ids] = np.stack([hist[i, :, 0, :].sum(axis=1) for i in range(3)])

        def open_(ids):
            ids = np.asarray(ids, int)
            can, shallow = self.may_split(e_depth[ids], e_sums[2, ids], e_best[ids])
            return can & shallow

        def routed():
            dleft = e_dleft.copy()
            if self.misroute_depth is not None:
                at = (e_feat >= 0) & (e_depth == self.misroute_depth)
                dleft[at] = ~dleft[at]
            return self.route_tables(e_feat, e_thr, dleft, e_left, e_right)

        histograms(np.array([0]))
        size = 2 * L + 1
        tree = MissTree(np.full(size, -1, np.int32), np.zeros(size, np.float32),
                        np.zeros(size, np.int32), np.zeros(size, np.int32),
                        np.zeros(size, np.float64), np.zeros(size, np.float64),
                        np.ones(size, bool))
        slots = [(0, 0)]                                  # (node of E, node of the tree)
        n_t = 1
        for step in range(L - 1):
            ids = np.asarray([e for e, _ in slots])
            ok = open_(ids)
            if not ok.any():
                break
            key = np.where(ok, e_best[ids], -np.inf)
            if self.level_order:                          # the shallowest open leaves first
                key = np.where(ok & (e_depth[ids] == e_depth[ids][ok].min()), key, -np.inf)
            s = int(np.argmax(key))                       # first of the largest
            e, t = slots[s]
            if e_feat[e] < 0:
                # look ahead: split the best open leaves that have no children yet
                ahead = [x for x in ids[ok] if e_feat[x] < 0 and x != e]
                room = (cap_e - n_e) // 2 - (L - 2 - step)       # the later steps' own
                ahead = [e] + sorted(ahead, key=lambda x: -e_best[x])[:min(LOOKAHEAD, room) - 1]
                made = []
                for x in ahead:
                    plane, f, b = e_split[x]
                    e_feat[x], e_thr[x], e_dleft[x] = f, b + 0.5, plane == 0
                    e_left[x], e_right[x] = n_e, n_e + 1
                    e_depth[[n_e, n_e + 1]] = e_depth[x] + 1
                    made += [n_e, n_e + 1]
                    n_e += 2
                node = _route_step(rows.q, node, *routed())
                histograms(np.array(made))
            tree.feature[t], tree.threshold[t] = e_feat[e], e_thr[e]
            tree.default_left[t] = e_dleft[e]
            tree.left[t], tree.right[t] = n_t, n_t + 1
            slots[s] = (int(e_left[e]), n_t)              # the left child keeps the slot
            slots.append((int(e_right[e]), n_t + 1))
            n_t += 2
        for e, t in slots:
            tree.value[t] = np.nan_to_num(self.leaf_values(e_sums[:, e]))
            tree.cover[t] = e_sums[2, e]
        tree.cover = node_rows(tree, tree.cover)
        return tree, {}
