"""The best-first reference with category-subset splits.

``gbdt_bestfirst.BestFirst`` knows thresholds alone.  This module keeps its
growth policy, its numbers and ``gbdt.py``'s arithmetic (the one-hot
histograms in three bfloat16 limbs, float32 sums, float64 gains; it imports
nothing of the program and takes no table of it) and adds what a table with
categorical columns needs:

* **Its own categories.**  ``Categories`` bins each categorical column of the
  train rows by the rule the configuration states: the codes seen ranked by
  (count descending, code ascending), bins 1..254 for the first 254, one
  overflow bin after them for every other code (and for a code the train
  rows never held), bin 0 for a missing value.  A numeric column keeps its
  value 0..254 as its bin, as in ``gbdt.py``.
* **The subset scan.**  For a categorical column a node's bins are put in
  the order of ``G_b / (H_b + 10)`` ascending, bins with no row last, ties
  by the bin (a stable sort); prefix sums over that order give the gains
  ``gbdt.py``'s formula gives, with the same floors; the left set of the
  prefix ``t`` is the bins of rank at most ``t``.  A row of that column goes
  left if and only if its bin is in the set; the missing bin is a member
  like any other.
* **Trees in raw space.**  ``CatTree`` adds ``cat_left[n, code]`` (the last
  column: a missing value): the codes a categorical node sends left, as the
  job's model applies them to raw rows.  The reference turns it into a
  membership row over its own bins to route, and its own grown trees back
  into codes, so that a job's tree and a stand-in's are read alike.

``split_flip_share`` counts a node as flipped where the job's split loses
more gain against the reference's best than float32 can resolve: more than
``FLIP_ROUNDING`` of the sum of the best split's three terms
(``GL^2/(HL+l2) + GR^2/(HR+l2) + G^2/(H+l2)``, of which the gain is half a
difference), and more than 1e-9 of the gain (the numeric cells' rule,
which alone counts ``strict_flip_share``).  A subset scan needs the band
where a threshold scan hardly does: at the best prefix of the ratio order
the next category's ratio is nearly the cut's, so neighbouring prefixes
differ in gain by a second-order amount, and a float32 order and float32
prefix sums pick a neighbour of the float64 best on a share of the nodes.
``float32_scan=True`` grows that witness: this reference's own histograms,
ordered and scanned in float32.  ``follow`` keeps, beside the share, every
split node's loss over its terms (``flip_rel``, largest first) where it
loses more than 1e-9 of the gain, so that a reading shows where it sits
against the band.  PERF.md section 2 gives the chip readings the band was
set from: the program's largest loss, the witness's, and the bfloat16
control's.  ``order_gain_gap``, ``leaf_value_gap`` and the window's numbers
are ``BestFirst``'s, over this routing.

Two faults of this configuration can be grown in the job's place
(``benchmark/tools/readings_cat.py``): ``scan_categories=False`` scans a
categorical column in bin order as if it were numeric (the left set is a
prefix of bins), ``misroute=True`` moves one category (bin 1, the column's
most frequent) to the other side of every categorical split where rows are
routed, while the tree keeps the set it chose.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gbdt import (BINS, INNER, ROWS, Tree, _merge, _node_sums, _pick,
                                      node_rows)
from benchmark.reference.gbdt_bestfirst import (FLOOR_ROUNDING, LOOKAHEAD, BestFirst,
                                                Rows, depths_of)

CAT_SMOOTH = 10.0
MOVED_BIN = 1           # the category the routing fault moves
# a split node's loss over its three terms past which it counts as flipped
# (the module's docstring), set between chip readings at the cell's size
# (PERF.md section 2): a sound job's largest 3.7e-7 (eight seeds; the
# float32-scan witness 2.9e-7), the bfloat16 control's loss at the share's
# limit 1.0e-4 the smallest (three seeds): 16 times from each
FLIP_ROUNDING = 6e-6
FLIP_REL_KEPT = 32      # the largest losses ``follow`` hands back


@dataclasses.dataclass
class CatTree(Tree):
    """A tree whose nodes on a categorical column send left the codes
    ``cat_left[n, code]``; column ``codes`` is a missing value."""
    cat_left: np.ndarray | None = None      # bool [M, codes + 1]


class Categories:
    """The reference's own category -> bin rule, fitted on the train rows."""

    def __init__(self, raw: np.ndarray, columns, codes: int, max_bins: int = BINS):
        self.columns = tuple(int(f) for f in columns)
        self.codes = int(codes)
        self.bin_of, self.overflow, self.pooled = {}, {}, {}
        for f in self.columns:
            counts = np.bincount(raw[:, f], minlength=self.codes)[: self.codes]
            seen = np.flatnonzero(counts)
            ranked = seen[np.lexsort((seen, -counts[seen]))]
            kept = ranked[: max_bins - 2]
            table = np.full(self.codes + 1, kept.size + 1, np.int32)    # the overflow bin
            table[kept] = np.arange(1, kept.size + 1, dtype=np.int32)
            table[self.codes] = 0                                        # missing
            self.bin_of[f] = table
            self.overflow[f] = kept.size + 1
            # the codes of the overflow bin, most frequent first, then the unseen
            unseen = np.setdiff1d(np.arange(self.codes), seen)
            self.pooled[f] = np.concatenate([ranked[kept.size:], unseen])

    def bins(self, raw: np.ndarray) -> np.ndarray:
        """uint8 bins of ``raw`` rows: categorical columns by this rule, a
        numeric column's value as its own bin."""
        out = np.asarray(raw).astype(np.uint8)
        for f in self.columns:
            out[:, f] = self.bin_of[f][np.minimum(raw[:, f], self.codes)]
        return out

    def pooled_share(self, raw: np.ndarray) -> dict:
        """Per categorical column, the share of ``raw``'s rows in its overflow bin."""
        return {f: float(np.mean(self.bin_of[f][np.minimum(raw[:, f], self.codes)]
                                 == self.overflow[f])) for f in self.columns}

    def to_bins(self, f: int, codes_left: np.ndarray) -> np.ndarray:
        """A node's codes sent left -> its membership row over this rule's
        bins.  The overflow bin takes the side of its most frequent code."""
        mask = np.zeros(BINS, bool)
        table = self.bin_of[f]
        kept = table[: self.codes] != self.overflow[f]
        mask[table[: self.codes][kept]] = codes_left[: self.codes][kept]
        pooled = self.pooled[f]
        mask[self.overflow[f]] = bool(codes_left[pooled[0]]) if pooled.size else False
        mask[0] = bool(codes_left[self.codes])
        return mask

    def to_codes(self, f: int, mask: np.ndarray) -> np.ndarray:
        """A membership row over this rule's bins -> the codes sent left."""
        return np.asarray(mask, bool)[self.bin_of[f]]


@jax.jit
def _route_step_cat(q, node, feature, threshold, left, right, is_cat, catmask):
    """``gbdt._route_step`` with membership: a row at a node on a
    categorical column goes left if its bin is in the node's row of
    ``catmask`` (bfloat16 0/1 [M, BINS]); picked by a one-hot product, which
    is exact for 0/1 values."""
    col = jnp.arange(feature.shape[0], dtype=jnp.int32)
    feats = jnp.arange(q.shape[-1], dtype=jnp.int32)
    bins = jnp.arange(BINS, dtype=jnp.int32)

    def block(_, xs):
        qx, nx = xs
        at = nx[..., None] == col
        f = _pick(at, feature)
        x = jnp.sum(jnp.where(f[..., None] == feats, qx.astype(jnp.int32), 0), axis=-1)
        num_left = x.astype(jnp.float32) <= _pick(at, threshold)
        member = jnp.einsum("irm,mb->irb", at.astype(jnp.bfloat16), catmask,
                            preferred_element_type=jnp.float32)
        cat_left = jnp.sum(jnp.where(x[..., None] == bins, member, 0.0), axis=-1) > 0.5
        go_left = jnp.where(_pick(at, is_cat) > 0, cat_left, num_left)
        child = jnp.where(go_left, _pick(at, left), _pick(at, right))
        return None, jnp.where((nx < 0) | (f < 0), nx, child)

    return jax.lax.scan(block, None, (q, node))[1]


class BestFirstCat(BestFirst):
    """``BestFirst`` over rows binned by ``cats`` (train and valid alike),
    with subset scans on ``cats.columns``."""

    def __init__(self, params: dict, train: Rows, valid: Rows | None, depth_cap: int,
                 cats: Categories, scan_categories: bool = True, misroute: bool = False,
                 float32_scan: bool = False):
        super().__init__(params, train, valid, depth_cap)
        self.cats = cats
        self.cat_columns = list(cats.columns)
        self.scan_categories = scan_categories
        self.misroute = misroute
        self.float32_scan = float32_scan
        self.flip_log: list = []

    # -- the subset scan ------------------------------------------------------
    def ordered(self, hist: np.ndarray):
        """float64 [3, n, F, B] -> (the same in scan order, order [n, F, B]):
        a categorical column's bins by G/(H + 10), empty bins last."""
        n, F, B = hist.shape[1:]
        order = np.broadcast_to(np.arange(B), (n, F, B)).copy()
        cat = self.cat_columns if self.scan_categories else []
        if cat:
            G, H, C = hist[0][:, cat], hist[1][:, cat], hist[2][:, cat]
            with np.errstate(invalid="ignore", divide="ignore"):
                ratio = np.where(C > 0, G / (H + CAT_SMOOTH), np.inf)
            order[:, cat] = np.argsort(ratio, axis=2, kind="stable")
        return np.take_along_axis(hist, order[None], axis=3), order

    def mask_gain(self, h3: np.ndarray, mask: np.ndarray, floor: float) -> float:
        """Gain of sending left the bins of ``mask`` at one node and column
        (``h3``: [3, B]), or -inf where a child misses a floor."""
        p = self.p
        l2 = float(p["lambda_l2"])
        G, H, C = h3.sum(axis=1)
        GL, HL, CL = h3[:, mask].sum(axis=1)
        GR, HR, CR = G - GL, H - HL, C - CL
        if (min(CL, CR) < p["min_data_in_leaf"]) or min(HL, HR) < floor:
            return -np.inf
        return 0.5 * (GL * GL / (HL + l2) + GR * GR / (HR + l2) - G * G / (H + l2))

    # -- moving rows ----------------------------------------------------------
    def node_masks(self, tree: CatTree) -> np.ndarray:
        """bool [M, BINS]: each categorical node's membership over this rule's bins."""
        M = tree.feature.shape[0]
        masks = np.zeros((M, BINS), bool)
        for n in np.flatnonzero(tree.feature >= 0):
            f = int(tree.feature[n])
            if f in self.cat_columns:
                masks[n] = self.cats.to_bins(f, tree.cat_left[n])
        return masks

    def route_tables(self, feature, threshold, left, right, masks):
        is_cat = np.isin(feature, self.cat_columns) & (feature >= 0)
        return (jnp.asarray(feature, jnp.int32), jnp.asarray(threshold, jnp.float32),
                jnp.asarray(left, jnp.int32), jnp.asarray(right, jnp.int32),
                jnp.asarray(is_cat, jnp.int32), jnp.asarray(masks, jnp.bfloat16))

    def tables(self, tree: CatTree):
        return self.route_tables(tree.feature, tree.threshold, tree.left, tree.right,
                                 self.node_masks(tree))

    def route(self, rows: Rows, tree: CatTree, levels: int):
        node = jnp.zeros((rows.outer, INNER, ROWS), jnp.int32)
        tabs = self.tables(tree)
        for _ in range(levels):
            node = _route_step_cat(rows.q, node, *tabs)
        return node

    # -- every node of a given tree --------------------------------------------
    def node_facts(self, g, h, tree: CatTree, bf16: bool, levels: int | None):
        """``BestFirst.node_facts`` with subset scans, and a categorical
        node's own gain that of its left set."""
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
        masks = self.node_masks(tree)
        tabs = self.route_tables(tree.feature, tree.threshold, tree.left, tree.right, masks)
        maybe_floor = float(self.p["min_child_weight"]) * (1 - FLOOR_ROUNDING)
        for d in range(last + 1):
            if levels is None or d < levels:
                at = np.flatnonzero(depth == d)
                slot_of = np.full(M + 1, -1, np.int32)          # last entry: padded rows
                slot_of[at] = np.arange(len(at))
                hist = self.level_hist(jnp.asarray(slot_of)[node], g, h, len(at), bf16)
                sure, maybe, G, H, C = self.gains_either(self.ordered(hist)[0])
                best[at] = sure.reshape(len(at), -1).max(axis=1)
                count[at] = C
                # the best split's three terms: 2 (gain + G^2/(H+l2))
                scale[at] = 2 * (np.maximum(best[at], 0) + G * G / (H + l2))
                for i, n in enumerate(at):
                    f = int(tree.feature[n])
                    if f < 0:
                        continue
                    if f in self.cat_columns:
                        own = self.mask_gain(hist[:, i, f], masks[n], maybe_floor)
                    else:
                        t = int(np.floor(tree.threshold[n]))
                        own = maybe[i, f, t] if 0 <= t < BINS else -np.inf
                    got[n] = float(own) if np.isfinite(own) and own > 0 else 0.0
            if d < last:
                node = _route_step_cat(rows.q, node, *tabs)
        sums = _merge(np.asarray(_node_sums(node, g, h, nodes=M, bf16=bf16)), bf16)
        self.node_gains = (best, got, scale)    # for ``one_tree``'s flips
        return depth, best, got, count, sums

    def one_tree(self, g, h, given: CatTree | None, bf16: bool = False,
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
        ``strict_flip_share`` and the largest losses over the terms,
        ``flip_rel``."""
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
        """``BestFirst.grow_tree`` with subset scans: a categorical split of
        ``E`` keeps its membership row, and the tree handed back its codes.
        With ``float32_scan`` the order and the gains are computed in
        float32 (the leaves' sums stay float64)."""
        rows, L = self.train, self.max_leaves
        cap_e = 8 * L + 1
        e_feat = np.full(cap_e, -1, np.int32)
        e_thr = np.zeros(cap_e, np.float32)
        e_left = np.zeros(cap_e, np.int32)
        e_right = np.zeros(cap_e, np.int32)
        e_depth = np.zeros(cap_e, np.int32)
        e_best = np.full(cap_e, -np.inf)
        e_split = np.zeros((cap_e, 2), np.int64)          # best (feature, rank)
        e_order = np.zeros((cap_e, BINS), np.int64)       # the best feature's scan order
        e_mask = np.zeros((cap_e, BINS), bool)
        e_sums = np.zeros((3, cap_e))
        n_e = 1
        node = jnp.where(rows.real, 0, -1).astype(jnp.int32)

        def histograms(ids):
            slot_of = np.full(cap_e + 1, -1, np.int32)
            slot_of[ids] = np.arange(len(ids))
            hist = self.level_hist(jnp.asarray(slot_of)[node], g, h, len(ids), bf16)
            scan = hist.astype(np.float32) if self.float32_scan else hist
            hist_o, order = self.ordered(scan)
            gain = self.gains(hist_o)[0]
            G, H, C = (hist[i, :, 0, :].sum(axis=1) for i in range(3))
            flat = gain.reshape(len(ids), -1)
            e_best[ids] = flat.max(axis=1)
            split = np.stack(np.divmod(flat.argmax(axis=1), BINS), axis=1)
            e_split[ids] = split
            e_order[ids] = order[np.arange(len(ids)), split[:, 0]]
            e_sums[:, ids] = np.stack([G, H, C])

        def open_(ids):
            ids = np.asarray(ids, int)
            can, shallow = self.may_split(e_depth[ids], e_sums[2, ids], e_best[ids])
            return can & shallow

        def routed_masks():
            masks = e_mask.copy()
            if self.misroute:
                cat = np.isin(e_feat, self.cat_columns) & (e_feat >= 0)
                masks[cat, MOVED_BIN] = ~masks[cat, MOVED_BIN]
            return masks

        histograms(np.array([0]))
        size = 2 * L + 1
        tree = CatTree(np.full(size, -1, np.int32), np.zeros(size, np.float32),
                       np.zeros(size, np.int32), np.zeros(size, np.int32),
                       np.zeros(size, np.float64), np.zeros(size, np.float64),
                       np.zeros((size, self.cats.codes + 1), bool))
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
                    if f in self.cat_columns:
                        e_mask[x, e_order[x, : b + 1]] = True
                    e_left[x], e_right[x] = n_e, n_e + 1
                    e_depth[[n_e, n_e + 1]] = e_depth[x] + 1
                    made += [n_e, n_e + 1]
                    n_e += 2
                node = _route_step_cat(rows.q, node, *self.route_tables(
                    e_feat, e_thr, e_left, e_right, routed_masks()))
                histograms(np.array(made))
            f = int(e_feat[e])
            tree.feature[t], tree.threshold[t] = f, e_thr[e]
            if f in self.cat_columns:
                tree.cat_left[t] = self.cats.to_codes(f, e_mask[e])
            tree.left[t], tree.right[t] = n_t, n_t + 1
            slots[s] = (int(e_left[e]), n_t)              # the left child keeps the slot
            slots.append((int(e_right[e]), n_t + 1))
            n_t += 2
        for e, t in slots:
            tree.value[t] = np.nan_to_num(self.leaf_values(e_sums[:, e]))
            tree.cover[t] = e_sums[2, e]
        tree.cover = node_rows(tree, tree.cover)
        return tree, {}

    def as_cat_tree(self, tree: Tree) -> CatTree:
        """A threshold tree over this rule's bins (``gbdt.Reference``'s own
        growth) as a ``CatTree``: a threshold ``t`` on a categorical column
        sends left the codes whose bin is at most ``t``."""
        M = tree.feature.shape[0]
        cat_left = np.zeros((M, self.cats.codes + 1), bool)
        for n in np.flatnonzero(tree.feature >= 0):
            f = int(tree.feature[n])
            if f in self.cat_columns:
                t = int(np.floor(tree.threshold[n]))
                cat_left[n] = self.cats.to_codes(f, np.arange(BINS) <= t)
        return CatTree(tree.feature, tree.threshold, tree.left, tree.right, tree.value,
                       tree.cover, cat_left)
