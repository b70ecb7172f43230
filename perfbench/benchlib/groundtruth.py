"""Exact top-k over the raw corpus, and recall@k against it.

The distances run at ``Precision.HIGHEST`` (a TPU otherwise rounds f32
matmul operands to bf16). A (queries x chunk) score block is cut into
groups of ``GROUP`` points; the k groups with the largest maxima hold every
member of the block's top-k (a member outside them would be beaten by k
group maxima), so one small ``top_k`` over those groups is exact and the
wide ``top_k`` over the whole block is never run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GROUP = 128
QUERY_BLOCK = 256


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _block_topk(q, pts, base, *, k, metric):
    """Top-k (higher is better) of one query block over one corpus chunk:
    (scores (Qb, k), global ids (Qb, k))."""
    dots = jnp.matmul(q, pts.T, precision=jax.lax.Precision.HIGHEST)
    if metric == "l2":   # -(|p|^2 - 2 q.p): |q|^2 does not change the order
        scores = 2.0 * dots - jnp.sum(pts * pts, axis=-1)[None, :]
    else:
        scores = dots
    n = pts.shape[0]
    pad = (-n) % GROUP
    scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    groups = scores.reshape(q.shape[0], -1, GROUP)
    _, gsel = jax.lax.top_k(jnp.max(groups, axis=-1), k)          # (Qb, k)
    cand = jnp.take_along_axis(groups, gsel[..., None], axis=1)   # (Qb,k,G)
    cand_ids = gsel[..., None] * GROUP + jnp.arange(GROUP)[None, None, :]
    top, sel = jax.lax.top_k(cand.reshape(q.shape[0], -1), k)
    ids = jnp.take_along_axis(cand_ids.reshape(q.shape[0], -1), sel, axis=1)
    return top, ids + base


@functools.partial(jax.jit, static_argnames=("k",))
def _merge(s_a, i_a, s_b, i_b, *, k):
    s = jnp.concatenate([s_a, s_b], axis=1)
    i = jnp.concatenate([i_a, i_b], axis=1)
    top, sel = jax.lax.top_k(s, k)
    return top, jnp.take_along_axis(i, sel, axis=1)


def exact_topk(queries: jax.Array, chunks: list[jax.Array], *, k: int,
               metric: str) -> np.ndarray:
    """(Q, k) int ids of the exact top-k of every query over the chunked
    corpus (ids count rows across the chunks in order)."""
    nq = queries.shape[0]
    pad = (-nq) % QUERY_BLOCK
    q_all = jnp.pad(queries, ((0, pad), (0, 0)), mode="edge")
    out = []
    for lo in range(0, nq + pad, QUERY_BLOCK):
        q = q_all[lo:lo + QUERY_BLOCK]
        best, base = None, 0
        for c in chunks:
            s, i = _block_topk(q, c, jnp.int32(base), k=k, metric=metric)
            best = (s, i) if best is None else _merge(*best, s, i, k=k)
            base += c.shape[0]
        out.append(best[1])
    return np.asarray(jnp.concatenate(out))[:nq]


def recall_per_query(ids: np.ndarray, gt: np.ndarray) -> np.ndarray:
    """(Q,) recall@k of each row of ``ids`` against ``gt`` (both (Q, k)):
    the share of true neighbours found; an id returned twice counts once."""
    k = gt.shape[1]
    return (gt[:, :, None] == ids[:, None, :]).any(axis=2).sum(axis=1) / k
