"""The plain reference: histogram gradient boosting in straightforward jax.numpy.

It imports nothing of the program and takes nothing the program made.  Its
inputs are the benchmark's own data (bin indices 0..254 per feature, labels)
and the configuration's stated parameters.  It can do two things:

* ``follow`` the first iterations of a job: for each tree the job grew, it
  computes its own gradients from its own scores, its own full histograms of
  every node of every level (sums of float32 gradients, exact products,
  float32 accumulation in blocks), and from them (a) the best split gain of
  each node, against the gain of the split the job chose (per level the
  share of the best gains that was lost, and over all trees the share of
  split nodes whose choice was not the best), (b) the Newton
  value of each leaf, against the job's (the tree's output over the training
  rows: the norm of the difference over the norm), (c) the valid metric, against the
  one the job reported.
* ``follow_window`` the last trees of the job's checkpoint, which a full run
  grew inside its measured window: scores brought forward through the job's
  earlier trees, then for each of those trees its own gradients, the rows
  routed by the tree's thresholds, and from the sums of every leaf (no
  histograms but the root's) (d) the rows of every node, against the counts
  the job recorded (exact), (e) the Newton value of each leaf, against the
  job's, (f) the best gain at the root, against that of the job's root split.
* ``grow`` trees itself from the same histograms, in float32 or with the
  gradients rounded to bfloat16 (the control: the nearest precision below
  the configuration's), so that its trees can be put in the job's place.

Arithmetic.  A level's histograms are ``A^T B`` with ``A`` the gradient of a
row placed in its node's column and ``B`` the one-hot of the row's bins.  B
is 0/1, exact in bfloat16, so each float32 gradient is cut into three
bfloat16 limbs by bit masks (8+8+8 mantissa bits, products exact) and the
MXU accumulates in float32: float32 sums at three passes instead of
``Precision.HIGHEST``'s six.  Rows go in blocks; 16 blocks make a partial
sum, partial sums are added: no sum runs over more than a few hundred terms.
Gains and leaf values are then worked out on the host in float64.

Semantics as the configuration states them: depth-wise growth, a split at
(feature f, threshold t) sends ``x <= t`` left; gain
``0.5 (GL^2/(HL+l2) + GR^2/(HR+l2) - G^2/(H+l2))``; a child needs
``min_data_in_leaf`` rows and ``min_child_weight`` hessian; a node is split
only where the best gain exceeds ``min_split_gain``; at most ``num_leaves``
leaves, the best gains of a level first; leaf value
``-learning_rate G/(H+l2)``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

BINS = 256
ROWS = 2048          # rows of one block
INNER = 16           # blocks of one partial sum
LANES = 32768        # widest one-hot of a feature group: group * BINS


@dataclasses.dataclass
class Tree:
    """One tree, in raw feature space.  ``feature[n] < 0`` marks a leaf."""
    feature: np.ndarray     # int32 [M]
    threshold: np.ndarray   # float32 [M]: x <= threshold goes left
    left: np.ndarray        # int32 [M]
    right: np.ndarray       # int32 [M]
    value: np.ndarray       # float64 [M], leaves only
    cover: np.ndarray | None = None   # float64 [M]: training rows that reach each node


def _limbs(x):
    """float32 -> three bfloat16 limbs whose sum is x to 24 bits.  Bit-mask
    truncation: a round trip through bfloat16 is folded away under jit."""
    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32) & jnp.uint32(0xFFFF0000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)
    hi = top(x)
    mid = top(x - hi)
    lo = x - hi - mid
    return [hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)]


def _channels(g, h, bf16: bool):
    """Columns of the weight matrix for one block: gradient and hessian (as
    limbs, or rounded to bfloat16 for the control) and the count."""
    one = jnp.ones_like(g, jnp.bfloat16)
    if bf16:
        return jnp.stack([g.astype(jnp.bfloat16), h.astype(jnp.bfloat16), one], axis=1)
    return jnp.stack(_limbs(g) + _limbs(h) + [one], axis=1)


def _merge(sums: np.ndarray, bf16: bool) -> np.ndarray:
    """[channels, ...] float32 sums -> [3, ...] float64 (G, H, count)."""
    s = np.asarray(sums, np.float64)
    if bf16:
        return s
    return np.stack([s[0] + s[1] + s[2], s[3] + s[4] + s[5], s[6]])


@functools.partial(jax.jit, static_argnames=("slots", "group", "bf16"))
def _level_hist(q, slot, g, h, f0, *, slots: int, group: int, bf16: bool):
    """Histograms of one feature group: [channels * slots, group * BINS].
    ``q`` [outer, INNER, ROWS, F] uint8; ``slot`` the column of each row's
    node, -1 for a row whose node is not of this level."""
    rows = q.shape[2]
    col = jnp.arange(slots, dtype=jnp.int32)
    bins = jnp.arange(BINS, dtype=jnp.uint8)

    def block(acc, xs):
        qx, sx, gx, hx = xs
        qx = jax.lax.dynamic_slice_in_dim(qx, f0, group, axis=1)
        w = _channels(gx, hx, bf16)                                # [rows, C]
        at = sx[:, None] == col[None, :]                           # [rows, slots]
        a = jnp.where(at[:, None, :], w[:, :, None], jnp.bfloat16(0))
        a = a.reshape(rows, -1)                                    # [rows, C * slots]
        b = (qx[:, :, None] == bins).astype(jnp.bfloat16).reshape(rows, group * BINS)
        part = jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        return acc + part, None

    def partial_sum(acc, xs):
        zero = jnp.zeros_like(acc)
        part, _ = jax.lax.scan(block, zero, xs)
        return acc + part, None

    channels = 3 if bf16 else 7
    zero = jnp.zeros((channels * slots, group * BINS), jnp.float32)
    out, _ = jax.lax.scan(partial_sum, zero, (q, slot, g, h))
    return out


@functools.partial(jax.jit, static_argnames=("nodes", "bf16"))
def _node_sums(node, g, h, *, nodes: int, bf16: bool):
    """Sums of gradient, hessian and count per node id: [channels, nodes]."""
    col = jnp.arange(nodes, dtype=jnp.int32)

    def block(acc, xs):
        nx, gx, hx = xs
        w = jnp.where(nx[:, None] >= 0, _channels(gx, hx, bf16), jnp.bfloat16(0))
        at = (nx[:, None] == col[None, :]).astype(jnp.bfloat16)
        part = jax.lax.dot_general(w, at, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        return acc + part, None

    def partial_sum(acc, xs):
        part, _ = jax.lax.scan(block, jnp.zeros_like(acc), xs)
        return acc + part, None

    zero = jnp.zeros((3 if bf16 else 7, nodes), jnp.float32)
    out, _ = jax.lax.scan(partial_sum, zero, (node, g, h))
    return out


def _pick(at, table):
    """``table[node]`` for every row, by the comparison ``at`` of its node
    with every index: a gather of ten million indices from a small table is
    slow on the chip.  Nought where the node is no index."""
    return jnp.sum(jnp.where(at, table, jnp.zeros((), table.dtype)), axis=-1)


@jax.jit
def _route_step(q, node, feature, threshold, left, right):
    """One level down: rows at an internal node move to a child; a row at a
    leaf, or at no node (-1), stays.  Block by block."""
    col = jnp.arange(feature.shape[0], dtype=jnp.int32)
    feats = jnp.arange(q.shape[-1], dtype=jnp.int32)

    def block(_, xs):
        qx, nx = xs
        at = nx[..., None] == col
        f = _pick(at, feature)
        x = jnp.sum(jnp.where(f[..., None] == feats, qx.astype(jnp.int32), 0), axis=-1)
        go_left = x.astype(jnp.float32) <= _pick(at, threshold)
        child = jnp.where(go_left, _pick(at, left), _pick(at, right))
        return None, jnp.where((nx < 0) | (f < 0), nx, child)

    return jax.lax.scan(block, None, (q, node))[1]


@jax.jit
def _add_values(score, node, values):
    """``score + values[node]``, block by block."""
    col = jnp.arange(values.shape[0], dtype=jnp.int32)

    def block(_, xs):
        sx, nx = xs
        return None, sx + _pick(nx[..., None] == col, values)

    return jax.lax.scan(block, None, (score, node))[1]


@functools.partial(jax.jit, static_argnames=("objective",))
def _grad_hess(score, y, *, objective: str):
    if objective == "binary":
        p = 1.0 / (1.0 + jnp.exp(-score))
        return p - y, p * (1.0 - p)
    if objective == "regression":
        return score - y, jnp.ones_like(score)
    raise ValueError(f"the reference has no objective {objective!r}")


def init_score(y: np.ndarray, objective: str) -> float:
    mean = float(np.mean(np.asarray(y, np.float64)))
    if objective == "binary":
        p = min(max(mean, 1e-12), 1 - 1e-12)
        return float(np.log(p / (1 - p)))
    return mean


def metric_value(name: str, y: np.ndarray, score: np.ndarray) -> float:
    y = np.asarray(y, np.float64)
    s = np.asarray(score, np.float64)
    if name == "auc":
        from scipy.stats import rankdata

        pos = y > 0.5
        n_pos, n_neg = int(pos.sum()), int((~pos).sum())
        ranks = rankdata(s)            # midranks
        return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg))
    if name == "rmse":
        return float(np.sqrt(np.mean((s - y) ** 2)))
    if name == "binary_logloss":
        return float(np.mean(np.logaddexp(0.0, s) - y * s))
    raise ValueError(f"the reference has no metric {name!r}")


def metric_gap(name: str, got: float, want: float) -> float:
    """Absolute for a metric that lives in [0, 1], else relative."""
    if not np.isfinite(got):
        return float("inf")
    return abs(got - want) if name == "auc" else abs(got - want) / max(abs(want), 1e-30)


def leaf_value_gap(tree: Tree, facts: dict):
    """The tree's output over the training rows, the job's leaf values
    against the reference's: norm of the difference over the norm; and the
    worst single leaf, against the larger of its value and the median one."""
    lv = facts["leaves"]
    ref, got, rows = facts["values"][lv], tree.value[lv], facts["count"][lv]
    floor = float(np.median(np.abs(ref))) if ref.size else 0.0
    worst = np.abs(got - ref) / np.maximum(np.abs(ref), max(floor, 1e-30))
    # a leaf the job holds and no row reaches, or the other way round
    stray = bool(((tree.feature < 0) & (tree.value != 0) & ~lv).any())
    if stray or not ref.size:
        return float("inf"), worst
    return float(np.sqrt((rows * (got - ref) ** 2).sum()
                         / max((rows * ref ** 2).sum(), 1e-300))), worst


def node_rows(tree: Tree, leaf_rows: np.ndarray) -> np.ndarray:
    """Rows that reach every node, from the rows that end in every leaf."""
    rows = np.where(tree.feature < 0, leaf_rows, 0.0).astype(np.float64)
    order, stack = [], [0]
    while stack:
        n = stack.pop()
        if tree.feature[n] >= 0:
            order.append(n)
            stack += [int(tree.left[n]), int(tree.right[n])]
    for n in reversed(order):                   # children before their parent
        rows[n] = rows[tree.left[n]] + rows[tree.right[n]]
    return rows


class Rows:
    """One set of rows on the device, padded to whole partial sums."""

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


def _group(features: int) -> int:
    cap = max(1, LANES // BINS)
    if features <= cap:
        return features
    return max(d for d in range(1, cap + 1) if features % d == 0)


def _pad_slots(n: int, channels: int) -> int:
    """Columns of the node one-hot, padded so that channels * slots fills
    whole MXU tiles of 128 and few programs are compiled."""
    want = 128
    while want < channels * n:
        want *= 2
    return max(n, want // channels)


class Reference:
    def __init__(self, params: dict, train: Rows, valid: Rows | None):
        self.p = params
        self.train, self.valid = train, valid
        self.objective = params["objective"]
        self.metric = params.get("metric") or {"binary": "auc", "regression": "rmse"}[self.objective]
        self.group = _group(train.features)

    # -- histograms and gains ---------------------------------------------
    def level_hist(self, slot, g, h, n: int, bf16: bool) -> np.ndarray:
        """float64 [3, n, F, BINS]: G, H and count of every node of a level."""
        channels = 3 if bf16 else 7
        slots = _pad_slots(n, channels)
        F = self.train.features
        parts = []
        for f0 in range(0, F, self.group):
            out = _level_hist(self.train.q, slot, g, h, jnp.int32(f0),
                              slots=slots, group=self.group, bf16=bf16)
            out = np.asarray(out).reshape(channels, slots, self.group, BINS)[:, :n]
            parts.append(_merge(out, bf16))
        return np.concatenate(parts, axis=2)

    def gains(self, hist: np.ndarray):
        """Per node: gain of every (feature, threshold) or -inf, and totals."""
        p = self.p
        l2 = float(p["lambda_l2"])
        G = hist[0, :, 0, :].sum(axis=1)
        H = hist[1, :, 0, :].sum(axis=1)
        C = hist[2, :, 0, :].sum(axis=1)
        GL, HL, CL = (np.cumsum(hist[i], axis=2) for i in range(3))
        GR, HR, CR = (G[:, None, None] - GL, H[:, None, None] - HL, C[:, None, None] - CL)
        ok = ((CL >= p["min_data_in_leaf"]) & (CR >= p["min_data_in_leaf"])
              & (HL >= p["min_child_weight"]) & (HR >= p["min_child_weight"]))
        with np.errstate(invalid="ignore", divide="ignore"):
            gain = 0.5 * (GL * GL / (HL + l2) + GR * GR / (HR + l2)
                          - (G * G / (H + l2))[:, None, None])
        return np.where(ok, gain, -np.inf), G, H, C

    def leaf_values(self, sums: np.ndarray) -> np.ndarray:
        with np.errstate(invalid="ignore", divide="ignore"):
            return -float(self.p["learning_rate"]) * sums[0] / (sums[1] + float(self.p["lambda_l2"]))

    # -- moving rows -------------------------------------------------------
    @staticmethod
    def tables(tree: Tree):
        return (jnp.asarray(tree.feature, jnp.int32), jnp.asarray(tree.threshold, jnp.float32),
                jnp.asarray(tree.left, jnp.int32), jnp.asarray(tree.right, jnp.int32))

    def route(self, rows: Rows, tree: Tree, levels: int):
        node = jnp.zeros((rows.outer, INNER, ROWS), jnp.int32)
        tabs = self.tables(tree)
        for _ in range(levels):
            node = _route_step(rows.q, node, *tabs)
        return node

    def add_tree(self, rows: Rows, score, tree: Tree, values: np.ndarray):
        node = self.route(rows, tree, int(self.p["max_depth"]))
        return _add_values(score, node, jnp.asarray(values, jnp.float32))

    def valid_metric(self, vscore) -> float:
        return metric_value(self.metric, self.valid.y_host, self.valid.host(vscore))

    # -- one tree: follow the job's, or grow one ---------------------------
    def one_tree(self, g, h, given: Tree | None, bf16: bool = False, levels: int | None = None):
        """Walks the levels.  With ``given`` it follows that tree's splits
        and returns ``(tree, facts)``: the gain it finds lost per level and
        its own value for every leaf.  Without, it grows the tree.  With
        ``levels`` it makes histograms of the first ``levels`` levels only
        and routes the rows down the rest of ``given`` by its thresholds."""
        p = self.p
        max_depth, max_leaves = int(p["max_depth"]), int(p["num_leaves"])
        levels = max_depth if levels is None else min(levels, max_depth)
        rows = self.train
        cap = 2 * max_leaves + 1
        tree = given or Tree(np.full(cap, -1, np.int32), np.zeros(cap, np.float32),
                             np.zeros(cap, np.int32), np.zeros(cap, np.int32),
                             np.zeros(cap, np.float64))
        M = tree.feature.shape[0]
        node = jnp.where(rows.real, 0, -1).astype(jnp.int32)
        frontier, n_nodes, n_leaves = [0], 1, 1
        level_gaps, flips = [], []
        for depth in range(levels):
            if not frontier:
                break
            slot_of = np.full(M + 1, -1, np.int32)      # last entry: padded rows
            slot_of[frontier] = np.arange(len(frontier))
            slot = jnp.asarray(slot_of)[node]
            hist = self.level_hist(slot, g, h, len(frontier), bf16)
            gain, _, _, C = self.gains(hist)
            flat = gain.reshape(len(frontier), -1)
            best = flat.max(axis=1)
            can = (C >= 2 * p["min_data_in_leaf"]) & np.isfinite(best) & (best > p["min_split_gain"])
            budget = max_leaves - n_leaves
            order = [i for i in np.argsort(-np.where(can, best, -np.inf), kind="stable") if can[i]]
            take = order[:budget]
            ref_total = float(best[take].sum()) if take else 0.0
            nxt = []
            if given is not None:
                chosen, flipped = 0.0, 0
                for i, n in enumerate(frontier):
                    f = int(tree.feature[n])
                    if f < 0:
                        continue
                    t = int(np.floor(tree.threshold[n]))
                    got = gain[i, f, t] if 0 <= t < BINS else -np.inf
                    got = float(got) if np.isfinite(got) and got > 0 else 0.0
                    chosen += got
                    flipped += bool(best[i] - got > 1e-9 * abs(best[i]))
                    nxt += [int(tree.left[n]), int(tree.right[n])]
                    n_leaves += 1
                gap = (ref_total - chosen) / ref_total if ref_total > 0 else float(chosen <= 0 and bool(nxt))
                level_gaps.append(gap)
                flips.append((flipped, len(nxt) // 2))
            else:
                for i in sorted(take):
                    n = frontier[i]
                    f, t = divmod(int(np.argmax(flat[i])), BINS)
                    tree.feature[n], tree.threshold[n] = f, t + 0.5
                    tree.left[n], tree.right[n] = n_nodes, n_nodes + 1
                    nxt += [n_nodes, n_nodes + 1]
                    n_nodes += 2
                    n_leaves += 1
            node = _route_step(rows.q, node, *self.tables(tree))
            frontier = nxt
        for _ in range(max_depth - levels):
            node = _route_step(rows.q, node, *self.tables(tree))
        sums = _merge(np.asarray(_node_sums(node, g, h, nodes=M, bf16=bf16)), bf16)
        values = np.where(tree.feature < 0, np.nan_to_num(self.leaf_values(sums)), 0.0)
        leaves = (tree.feature < 0) & (sums[2] > 0)
        if given is None:
            tree.value = np.where(leaves, values, 0.0)
            tree.cover = node_rows(tree, sums[2])
        facts = {"level_gain_gap": level_gaps, "flips": flips, "leaves": leaves,
                 "values": values, "count": sums[2]}
        return tree, facts

    # -- a whole job: follow it or stand in for it -------------------------
    def grow(self, iterations: int, bf16: bool = False) -> dict:
        """Train ``iterations`` trees: what a job would hand over, with the
        valid metric after each."""
        s0 = init_score(self.train.y_host, self.objective)
        score = self.train.start(s0)
        vscore = self.valid.start(s0) if self.valid else None
        trees, evals = [], {}
        for it in range(iterations):
            g, h = _grad_hess(score, self.train.y, objective=self.objective)
            tree, _ = self.one_tree(g, h, None, bf16)
            score = self.add_tree(self.train, score, tree, tree.value)
            if self.valid:
                vscore = self.add_tree(self.valid, vscore, tree, tree.value)
                evals[it] = self.valid_metric(vscore)
            trees.append(tree)
        return {"trees": trees, "init_score": s0, "evals": evals}

    def follow(self, job: dict, iterations: int) -> dict:
        """The numbers that decide ``correct`` for the first ``iterations``
        trees of ``job`` (``trees``, ``init_score``, ``evals``), and the
        valid metric of all its trees at its last iteration."""
        s0 = init_score(self.train.y_host, self.objective)
        out = {"init_score_gap": abs(float(job["init_score"]) - s0) / max(abs(s0), 1.0),
               "level_gain_gap": 0.0, "split_flip_share": 1.0, "leaf_value_gap": 0.0,
               "valid_metric_gap": 0.0, "per_tree": []}
        flipped = split = 0
        score = self.train.start(s0)
        vscore = self.valid.start(s0) if self.valid else None
        trees = job["trees"]
        for it in range(min(iterations, len(trees))):
            g, h = _grad_hess(score, self.train.y, objective=self.objective)
            tree, facts = self.one_tree(g, h, trees[it])
            lv = facts["leaves"]
            leaf_gap, worst = leaf_value_gap(tree, facts)
            level_gap = max(facts["level_gain_gap"], default=0.0)
            score = self.add_tree(self.train, score, tree, facts["values"])
            row = {"iteration": it, "level_gain_gap": level_gap, "leaf_value_gap": leaf_gap,
                   "leaves": int(lv.sum()), "level_gaps": facts["level_gain_gap"],
                   "flips": facts["flips"],
                   "worst_leaf_gap": float(worst.max()) if worst.size else None}
            if self.valid:
                vscore = self.add_tree(self.valid, vscore, tree, facts["values"])
                want = self.valid_metric(vscore)
                got = job["evals"].get(it, float("nan"))
                row["valid_metric"] = [got, want]
                out["valid_metric_gap"] = max(out["valid_metric_gap"],
                                              metric_gap(self.metric, got, want))
            flipped += sum(f for f, _ in facts["flips"])
            split += sum(n for _, n in facts["flips"])
            out["level_gain_gap"] = max(out["level_gain_gap"], level_gap)
            out["leaf_value_gap"] = max(out["leaf_value_gap"], leaf_gap)
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

    # -- the last trees of a job: the ones a full run grew in its window ----
    def window(self, job: dict, iterations: int, bf16: bool = False):
        """For each of the last ``iterations`` trees of ``job``: ``(index,
        tree, facts)`` with the reference's own gradients at that tree
        (scores from the seed, brought forward through the job's trees),
        the root's histogram, and every leaf's sums by the tree's routing."""
        rows, trees = self.train, job["trees"]
        score = rows.start(init_score(rows.y_host, self.objective))
        for it, tree in enumerate(trees):
            if it >= len(trees) - iterations:
                g, h = _grad_hess(score, rows.y, objective=self.objective)
                yield it, tree, self.one_tree(g, h, tree, bf16, levels=1)[1]
            score = self.add_tree(rows, score, tree, tree.value)

    def follow_window(self, job: dict, iterations: int) -> dict:
        """The numbers that decide ``correct`` for the last ``iterations``
        trees of ``job``, each the worst of those trees."""
        out = {"window_cover_gap": 0.0, "window_leaf_value_gap": 0.0,
               "window_root_gain_gap": 0.0, "window_trees": []}
        for it, tree, facts in self.window(job, iterations):
            leaf_gap, _ = leaf_value_gap(tree, facts)
            want = node_rows(tree, facts["count"])
            cover_gap = (float("inf") if tree.cover is None
                         else float(np.abs(tree.cover - want).max() / self.train.n))
            row = {"iteration": it, "cover_gap": cover_gap, "leaf_value_gap": leaf_gap,
                   "root_gain_gap": facts["level_gain_gap"][0], "leaves": int(facts["leaves"].sum())}
            for key in ("cover_gap", "leaf_value_gap", "root_gain_gap"):
                out["window_" + key] = max(out["window_" + key], row[key])
            out["window_trees"].append(row)
        return out

    def restate(self, job: dict, iterations: int, bf16: bool = False) -> dict:
        """``job`` with the leaf values and node rows of its last
        ``iterations`` trees as this reference makes them on its own rows, in
        float32 or from gradients rounded to bfloat16: a stand-in for the
        job, to read the control and the faults from."""
        trees = list(job["trees"])
        for it, tree, facts in self.window(job, iterations, bf16):
            trees[it] = dataclasses.replace(
                tree, value=np.where(facts["leaves"], facts["values"], 0.0),
                cover=node_rows(tree, facts["count"]))
        return {**job, "trees": trees}
