"""Exact brute-force nearest-neighbour oracle — ground truth for every test
and recall measurement. Chunked so the (Q, N) score matrix never exceeds
memory for benchmark-scale N."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("k", "metric", "chunk"))
def exact_topk(queries: jnp.ndarray, points: jnp.ndarray, *, k: int,
               metric: str = "l2", chunk: int = 65536):
    """Exact top-k ids+scores. queries (Q, D), points (N, D) -> (Q, k) each.

    The distance matmul runs at ``Precision.HIGHEST``: at default
    precision a TPU rounds f32 operands to bf16 on the MXU, and the
    ground truth would no longer be exact.

    Streaming top-k: scan over point chunks keeping the running best k, so
    memory is O(Q * (chunk + k)) regardless of N.
    """
    q = queries.astype(jnp.float32)
    n = points.shape[0]
    n_pad = ((n + chunk - 1) // chunk) * chunk
    pts = jnp.pad(points.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    pts = pts.reshape(-1, chunk, points.shape[-1])
    nq = q.shape[0]
    sign = -1.0 if metric == "l2" else 1.0  # internally higher-is-better

    def body(carry, xs):
        best_s, best_i = carry
        chunk_pts, base = xs
        dots = jnp.matmul(q, chunk_pts.T,                        # (Q, chunk)
                          precision=jax.lax.Precision.HIGHEST)
        if metric == "l2":
            p_sq = jnp.sum(chunk_pts * chunk_pts, axis=-1)
            scores = -(p_sq[None, :] - 2.0 * dots)               # -(|p|^2-2qp)
        else:
            scores = dots
        ids = base + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        pad_mask = ids < n
        scores = jnp.where(pad_mask, scores, -jnp.inf)
        cat_s = jnp.concatenate([best_s, scores], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, (nq, chunk))], 1)
        top_s, sel = jax.lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, sel, axis=1)
        return (top_s, top_i), None

    init = (jnp.full((nq, k), -jnp.inf), jnp.full((nq, k), -1, jnp.int32))
    bases = jnp.arange(pts.shape[0], dtype=jnp.int32) * chunk
    (best_s, best_i), _ = jax.lax.scan(body, init, (pts, bases))
    return sign * best_s, best_i
