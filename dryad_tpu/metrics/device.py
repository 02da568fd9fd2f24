"""Device-side evaluation metrics (SURVEY.md §5 metrics/observability).

The round-1 trainer fetched the full validation score matrix to the host
every eval (a blocking fetch + O(N) transfer + host sort for AUC).  These jax implementations compute the metric where
the scores already live, so an eval costs one 4-byte scalar fetch — or no
fetch at all until training ends when nothing needs the value mid-run.

The numpy implementations in ``dryad_tpu.metrics`` remain the oracle:
``test_device_metrics.py`` pins each function against them to fp32
tolerance (device sums are f32 tree-reductions; at 1e6 rows the relative
error is ~1e-6, far below metric noise).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.engine import introspect
from dryad_tpu.metrics import DEFAULT_METRIC, HIGHER_BETTER, _METRIC_ALIASES

_EPS = 1e-15


def auc_device(y, s):
    """ROC-AUC via the midrank statistic — jax mirror of metrics.auc.

    Tie-group boundaries are computed in exact int32 (f32 indices would
    collapse above 2^24 rows); the rank sum is an f32 tree reduction,
    ~1e-6 relative error at 1M rows."""
    n = s.shape[0]
    order = jnp.argsort(s, stable=True)
    ss = s[order]
    pos_sorted = y[order] > 0.5
    i_arr = jnp.arange(n, dtype=jnp.int32)
    is_first = jnp.concatenate([jnp.ones((1,), bool), ss[1:] != ss[:-1]])
    is_last = jnp.concatenate([ss[1:] != ss[:-1], jnp.ones((1,), bool)])
    # group start: running max of first-of-group indices; group end: the
    # same trick on the reversed array
    gs = jax.lax.cummax(jnp.where(is_first, i_arr, -1))
    ge_rev = jax.lax.cummax(jnp.where(is_last[::-1], i_arr, -1))
    ge = (n - 1) - ge_rev[::-1]
    ranks = 0.5 * (gs + ge).astype(jnp.float32) + 1.0  # midranks, 1-based
    n_pos = jnp.sum(pos_sorted.astype(jnp.float32))
    n_neg = n - n_pos
    sum_pos_ranks = jnp.sum(jnp.where(pos_sorted, ranks, 0.0))
    value = (sum_pos_ranks - n_pos * (n_pos + 1.0) * 0.5) / (n_pos * n_neg)
    return jnp.where((n_pos == 0) | (n_neg == 0), jnp.float32(jnp.nan), value)


def binary_logloss_device(y, s):
    # stable form: softplus(s) - y*s == -(y log p + (1-y) log(1-p)); the
    # f32-naive clip(sigmoid, eps, 1-eps) rounds 1-1e-15 to 1.0 and NaNs on
    # saturated scores.  Per-row cap mirrors the numpy oracle's eps clip.
    loss = jax.nn.softplus(s) - y * s
    return jnp.mean(jnp.minimum(loss, jnp.float32(-np.log(_EPS))))


def multi_logloss_device(y, s):
    p = jax.nn.softmax(s, axis=1)
    p = jnp.clip(p, _EPS, 1.0)
    p = p / jnp.sum(p, axis=1, keepdims=True)
    py = jnp.take_along_axis(p, y.astype(jnp.int32)[:, None], axis=1)[:, 0]
    return -jnp.mean(jnp.log(py))


def error_device(y, s):
    if s.ndim == 1:  # binary raw scores: class 1 iff score > 0
        pred = (s > 0).astype(jnp.int32)
    else:
        pred = jnp.argmax(s, axis=1).astype(jnp.int32)
    return 1.0 - jnp.mean((pred == y.astype(jnp.int32)).astype(jnp.float32))


def rmse_device(y, s):
    d = y - s
    return jnp.sqrt(jnp.mean(d * d))


def mse_device(y, s):
    d = y - s
    return jnp.mean(d * d)


def mae_device(y, s):
    return jnp.mean(jnp.abs(y - s))


def poisson_deviance_device(y, s):
    """Mirror of metrics.poisson_deviance (raw log-rate scores); the 1e-30
    clamp epsilon matches the host mirror exactly (ADVICE r4)."""
    mu = jnp.exp(s)
    ylog = jnp.where(y > 0, y * jnp.log(jnp.maximum(y, 1e-30) / mu), 0.0)
    return jnp.mean(2.0 * (ylog - (y - mu)))


def _pad_queries(query_offsets: np.ndarray) -> tuple[np.ndarray, int]:
    """(Q, S) row-id scatter plan for per-query padded views; pad slots get
    row id N (out of range, gathered via mode='fill')."""
    qoff = np.asarray(query_offsets, np.int64)
    sizes = np.diff(qoff)
    Q, S = sizes.size, int(sizes.max(initial=1))
    ids = np.full((Q, S), qoff[-1], np.int64)
    for q in range(Q):
        ids[q, : sizes[q]] = np.arange(qoff[q], qoff[q + 1])
    return ids.astype(np.int32), int(qoff[-1])


def ndcg_device(y, s, qids, k):
    """Mean NDCG@k over padded (Q, S) query views — mirror of
    metrics.ndcg_at_k incl. the idcg==0 → 1.0 convention.

    ``qids`` is the (Q, S) row-id plan from ``_pad_queries``; padding slots
    hold an out-of-range id and are filled with rel=0 / score=-inf."""
    Q, S = qids.shape
    rel = y[jnp.minimum(qids, y.shape[0] - 1)]
    sc = s[jnp.minimum(qids, s.shape[0] - 1)]
    pad = qids >= y.shape[0]
    rel = jnp.where(pad, 0.0, rel)
    sc = jnp.where(pad, -jnp.inf, sc)

    pos = jnp.arange(S, dtype=jnp.float32)[None, :]
    # numpy sorts by -score with a stable mergesort; -inf padding lands last
    order = jnp.argsort(-sc, axis=1, stable=True)
    rel_by_score = jnp.take_along_axis(rel, order, axis=1)
    rel_ideal = -jnp.sort(-rel, axis=1)
    topk = (pos < k) & (pos < jnp.sum(~pad, axis=1)[:, None])
    disc = jnp.where(topk, 1.0 / jnp.log2(pos + 2.0), 0.0)
    dcg = jnp.sum((jnp.exp2(rel_by_score) - 1.0) * disc, axis=1)
    idcg = jnp.sum((jnp.exp2(rel_ideal) - 1.0) * disc, axis=1)
    ndcg = jnp.where(idcg == 0.0, 1.0, dcg / idcg)
    return jnp.mean(ndcg)


@jax.named_scope("dryad.eval")
def eval_value(name, ndcg_at, y, raw_score, qids=None):
    """Raw (traceable) metric value — shared by the standalone ``_eval_jit``
    and the chunked trainer, which evaluates INSIDE its device program."""
    s = raw_score
    if s.ndim == 2 and s.shape[1] == 1:
        s = s[:, 0]
    if name == "auc":
        return auc_device(y, s)
    if name == "binary_logloss":
        return binary_logloss_device(y, s)
    if name == "multi_logloss":
        return multi_logloss_device(y, s)
    if name == "accuracy":
        return 1.0 - error_device(y, s)
    if name == "error":
        return error_device(y, s)
    if name == "rmse":
        return rmse_device(y, s)
    if name == "mse":
        return mse_device(y, s)
    if name == "mae":
        return mae_device(y, s)
    if name == "poisson_deviance":
        return poisson_deviance_device(y, s)
    if name == "ndcg":
        return ndcg_device(y, s, qids, ndcg_at)
    raise ValueError(f"unknown metric {name!r}")


_eval_jit = introspect.whole_program("dryad.eval", partial(
    jax.jit, static_argnames=("name", "ndcg_at"))(eval_value))


def make_evaluator(objective: str, metric: str, valid_ds, ndcg_at: int = 10):
    """(name, higher_better, fn) — ``fn(vscore_device) -> f32 device scalar``.

    ``valid_ds``'s labels (and query plan for ndcg) upload once; the
    returned fn is a reusable jitted program keyed on (metric, shapes)."""
    name = metric or DEFAULT_METRIC[objective]
    name = _METRIC_ALIASES.get(name, name)
    if name not in HIGHER_BETTER:
        # same exception type as the CPU backend's evaluate_raw
        raise ValueError(f"unknown metric {name!r}")
    qids = None
    if name == "ndcg":
        if valid_ds.query_offsets is None:
            raise ValueError("ndcg requires query groups on the validation set")
        qoff = np.asarray(valid_ds.query_offsets, np.int64)
        sizes = np.diff(qoff)
        Q, S = sizes.size, int(sizes.max(initial=1))
        N = int(qoff[-1])
        # the dense (Q, S) plan explodes on skewed group sizes (100k tiny
        # queries + one 1M-row group -> Q*S ~ 1e11 ids): when the padded
        # view is much larger than the data, evaluate on the HOST instead —
        # one score fetch per eval (the deferred-fetch optimization is lost,
        # correctness is not)
        if Q * S > max(8 * N, 1 << 24):
            from dryad_tpu.metrics import ndcg_at_k

            y_np = np.asarray(valid_ds.y)
            qoff_np = qoff

            def fn_host(vscore):
                s = np.asarray(vscore)
                if s.ndim == 2 and s.shape[1] == 1:
                    s = s[:, 0]
                return np.float32(ndcg_at_k(y_np, s, qoff_np, ndcg_at))

            fn_host.host_only = True  # chunked trainer cannot inline this
            return name, HIGHER_BETTER[name], fn_host
        qids = jnp.asarray(_pad_queries(valid_ds.query_offsets)[0])

    # labels upload only when a device evaluator is actually returned
    y = jnp.asarray(np.asarray(valid_ds.y, np.float32))

    def fn(vscore):
        return _eval_jit(name, ndcg_at, y, vscore, qids)

    # the chunked trainer inlines the metric INSIDE its device program —
    # expose the pieces eval_value needs
    fn.host_only = False
    fn.metric_name = name
    fn.y_dev = y
    fn.qids = qids
    return name, HIGHER_BETTER[name], fn
