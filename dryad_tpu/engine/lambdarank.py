"""LambdaMART gradient/hessian on device (SURVEY.md §7 hard part d).

The ragged per-query pairwise λ computation is reshaped for a vector
machine: queries are padded to a fixed document budget ``S`` (the max query
length rounded up), giving a dense (Q, S) layout on which ranks, |ΔNDCG|
weights, and the full S×S pair grid vectorize — then vmapped over queries.
Padding docs carry relevance -1 and participate in no valid pair.

Semantics match ``objectives.LambdaRank.grad_hess_np`` (the canonical host
path): stable sort by -score for ranks, gain 2^rel - 1, log2 discounts,
truncation to pairs touching the top-k, sigmoid-weighted λ with σ scaling.
Host path remains available via ``use_device=False`` and is the parity
oracle in tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.obs.registry import default_registry


class PaddingPlan:
    """Loop-invariant scatter plan for ragged query groups — build it once
    per dataset (train.py hoists it out of the boosting loop) since it
    depends only on the query offsets."""

    def __init__(self, query_offsets: np.ndarray, pad_multiple: int = 8,
                 truncation: int | None = None):
        sizes = np.diff(query_offsets)
        self.Q = int(sizes.size)
        self.S = int(max(8, -(-int(sizes.max()) // pad_multiple) * pad_multiple))
        row_np = np.repeat(np.arange(self.Q, dtype=np.int32), sizes)
        col_np = np.concatenate([np.arange(int(s), dtype=np.int32) for s in sizes])
        self.row_ids = jnp.asarray(row_np)
        self.col_ids = jnp.asarray(col_np)
        self.pair_cells = pair_cells(sizes, self.S, truncation)
        _note_plan(self)


def pair_cells(sizes: np.ndarray, S: int, truncation: int | None) -> dict:
    """Pair cells a λ-pass over these queries has to do with, three ways:
    ``padded`` is what the one-width plan walks (Q x S^2), ``own`` the
    queries' own grids (sum L^2), ``kept`` the most pairs the truncation can
    keep (sum min(T, L) x L; with no truncation given, ``own``)."""
    sizes = np.asarray(sizes, np.int64)
    own = int((sizes * sizes).sum())
    kept = own if truncation is None else int(
        (np.minimum(int(truncation), sizes) * sizes).sum())
    return {"padded": int(sizes.size) * S * S, "own": own, "kept": kept}


def _note_plan(plan: PaddingPlan) -> None:
    """The plan's size as gauges, set once where it is built: what the next
    plan (length buckets, say) is sized from."""
    reg = default_registry()
    if not reg.enabled:
        return
    reg.gauge("dryad_rank_queries", "Query groups of the λ-plan").set(plan.Q)
    reg.gauge("dryad_rank_plan_width",
              "Documents a query is padded to in the λ-plan (S)").set(plan.S)
    cells = reg.gauge("dryad_rank_pair_cells",
                      "Pair cells of one λ-pass: padded (Q x S^2), own "
                      "(sum L^2), kept (sum min(truncation, L) x L)")
    for kind, value in plan.pair_cells.items():
        cells.labels(kind=kind).set(value)


@partial(jax.jit, static_argnames=("Q", "S", "sigma", "truncation"))
def _lambda_grad_padded(score, rel, row_ids, col_ids, Q, S, sigma, truncation):
    n = score.shape[0]
    big_neg = jnp.float32(-1e30)

    # scatter docs into the (Q, S) padded layout
    s_pad = jnp.full((Q, S), big_neg, jnp.float32).at[row_ids, col_ids].set(score)
    r_pad = jnp.full((Q, S), -1.0, jnp.float32).at[row_ids, col_ids].set(rel)
    present = jnp.zeros((Q, S), bool).at[row_ids, col_ids].set(True)

    def per_query(s, rel_q, pres):
        # ranks: stable descending sort (padding sinks to the bottom)
        order = jnp.argsort(-s, stable=True)
        rank_of = jnp.zeros((S,), jnp.int32).at[order].set(jnp.arange(S, dtype=jnp.int32))
        rel_clip = jnp.maximum(rel_q, 0.0)
        gains = jnp.power(2.0, rel_clip) - 1.0
        discounts = 1.0 / jnp.log2(rank_of.astype(jnp.float32) + 2.0)
        # ideal DCG over the query's own docs (descending relevance)
        rel_sorted = -jnp.sort(-rel_clip * pres)
        ideal_disc = 1.0 / jnp.log2(jnp.arange(S, dtype=jnp.float32) + 2.0)
        max_dcg = jnp.sum((jnp.power(2.0, rel_sorted) - 1.0) * ideal_disc * (rel_sorted >= 0))
        inv_max_dcg = jnp.where(max_dcg > 0, 1.0 / max_dcg, 0.0)

        topk = rank_of < truncation
        rel_diff = rel_q[:, None] - rel_q[None, :]
        valid = (rel_diff > 0) & pres[:, None] & pres[None, :] & (topk[:, None] | topk[None, :])
        sdiff = s[:, None] - s[None, :]
        rho = 1.0 / (1.0 + jnp.exp(sigma * sdiff))
        delta_ndcg = (
            jnp.abs(gains[:, None] - gains[None, :])
            * jnp.abs(discounts[:, None] - discounts[None, :])
            * inv_max_dcg
        )
        lam = jnp.where(valid, sigma * rho * delta_ndcg, 0.0)
        hes = jnp.where(valid, sigma * sigma * rho * (1.0 - rho) * delta_ndcg, 0.0)
        g = -lam.sum(axis=1) + lam.sum(axis=0)
        h = hes.sum(axis=1) + hes.sum(axis=0)
        return g, h

    # batched map: a full vmap would materialize O(Q*S^2) pair tensors
    # (MSLR-scale queries OOM instantly); bound live memory to ~batch*S^2
    batch = max(1, min(Q, (1 << 22) // (S * S)))
    g_pad, h_pad = jax.lax.map(
        lambda args: per_query(*args), (s_pad, r_pad, present), batch_size=batch
    )
    g = g_pad[row_ids, col_ids]
    h = h_pad[row_ids, col_ids]
    return g.astype(jnp.float32), h.astype(jnp.float32)


def grad_hess_ranking(obj, score, y, weight, query_offsets, use_device: bool = True,
                      plan: "PaddingPlan | None" = None):
    """λ-gradients for one boosting iteration; device path with host oracle."""
    if query_offsets is None:
        raise ValueError("lambdarank requires query groups (Dataset(group=...))")
    if use_device:
        if plan is None:
            plan = PaddingPlan(np.asarray(query_offsets), truncation=int(obj.truncation))
        g, h = _lambda_grad_padded(
            jnp.asarray(score, jnp.float32), jnp.asarray(y, jnp.float32),
            plan.row_ids, plan.col_ids,
            plan.Q, plan.S, float(obj.sigma), int(obj.truncation),
        )
        if weight is not None:
            w = jnp.asarray(weight)
            g, h = g * w, h * w
        return g, h
    g, h = obj.grad_hess_np(
        np.asarray(score), np.asarray(y),
        None if weight is None else np.asarray(weight),
        query_offsets=np.asarray(query_offsets),
    )
    return jnp.asarray(g), jnp.asarray(h)
