"""Inverted file index: coarse k-means filtering + padded cluster storage.

Storage layout is TPU-native: instead of the CPU-style CSR inverted lists,
clusters are padded to a fixed capacity so that the online scan over the
``nprobs`` selected clusters is a static-shape gather — the structural
equivalent of the paper's per-cluster inverted indices (Alg. 1 line 12-14),
laid out for regular vector access instead of pointer chasing.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .kmeans import KMeansState, assign, kmeans_subsampled


class IVFIndex(NamedTuple):
    centroids: jnp.ndarray     # (C, D) f32
    centroid_sq: jnp.ndarray   # (C,)   f32
    point_ids: jnp.ndarray     # (C, P) int32 — padded per-cluster point ids; -1 = pad
    valid: jnp.ndarray         # (C, P) bool
    labels: jnp.ndarray        # (N,)   int32 — cluster of each point

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def capacity(self) -> int:
        return self.point_ids.shape[1]


def cluster_capacity(n: int, n_clusters: int, capacity_mult: float) -> int:
    """Padded per-cluster slot count: ``capacity_mult * N/C``, min 8, mult of 8.

    Parameters
    ----------
    n : int
        Number of points.
    n_clusters : int
        Number of IVF clusters.
    capacity_mult : float
        Padding headroom over the perfectly balanced fill ``N / C``.

    Returns
    -------
    int
        The slot count P shared by every padded cluster row.
    """
    cap = int(max(8, capacity_mult * n / n_clusters))
    return ((cap + 7) // 8) * 8


def padded_layout(labels: np.ndarray, n_clusters: int, cap: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Pack point ids into the padded (C, P) cluster layout, spilling overflow.

    Overflowing points (rare with reasonable k-means balance) spill to the
    emptiest non-full clusters via a host-side pass, and their ``labels``
    entry is rewritten to the adoptive cluster so storage and labels agree.

    Parameters
    ----------
    labels : np.ndarray
        (N,) int — owning cluster per point. Modified in place on spill.
    n_clusters : int
        Number of clusters C.
    cap : int
        Padded capacity P per cluster (:func:`cluster_capacity`).

    Returns
    -------
    tuple of np.ndarray
        ``(point_ids (C, P) int32 with -1 padding, labels (N,))``.
    """
    point_ids = np.full((n_clusters, cap), -1, dtype=np.int32)
    # each cluster takes its first ``cap`` points in id order (a stable sort
    # by label gives every point its rank within its cluster); the rest
    # overflow, in id order
    order = np.argsort(labels, kind="stable")
    by_label = np.asarray(labels)[order]
    rank = np.arange(order.size) - np.searchsorted(by_label, by_label)
    keep = rank < cap
    point_ids[by_label[keep], rank[keep]] = order[keep]
    fill = np.minimum(np.bincount(by_label, minlength=n_clusters),
                      cap).astype(np.int64)
    overflow = np.sort(order[~keep]).tolist()
    if overflow:  # spill to emptiest clusters (keeps every point searchable)
        order = np.argsort(fill)
        oi = 0
        for c in order:
            while fill[c] < cap and oi < len(overflow):
                pid = overflow[oi]
                point_ids[c, fill[c]] = pid
                labels[pid] = c
                fill[c] += 1
                oi += 1
            if oi >= len(overflow):
                break
    return point_ids, labels


def build_ivf(points: jnp.ndarray, *, n_clusters: int, n_iters: int = 10,
              key: jax.Array | None = None, capacity_mult: float = 4.0,
              max_train_points: int = 200_000) -> IVFIndex:
    """Train IVF centroids and build the padded cluster layout.

    ``capacity_mult`` pads each cluster to ``capacity_mult * N/C`` slots;
    overflowing points (rare with reasonable k-means balance) spill to the
    emptiest non-full clusters via a host-side pass
    (:func:`padded_layout`). Lloyd training runs on a
    ``max_train_points``-row subsample (FAISS-style); the full set is
    only ever streamed through chunked assignment.
    """
    st: KMeansState = kmeans_subsampled(points, n_clusters=n_clusters,
                                        n_iters=n_iters, key=key,
                                        max_train_points=max_train_points)
    labels = np.array(assign(points.astype(jnp.float32), st.centroids))
    n = points.shape[0]
    cap = cluster_capacity(n, n_clusters, capacity_mult)
    point_ids, labels = padded_layout(labels, n_clusters, cap)
    point_ids = jnp.asarray(point_ids)
    return IVFIndex(
        centroids=st.centroids,
        centroid_sq=jnp.sum(st.centroids * st.centroids, axis=-1),
        point_ids=point_ids,
        valid=point_ids >= 0,
        labels=jnp.asarray(labels),
    )


@functools.partial(jax.jit, static_argnames=("nprobe", "metric"))
def filter_clusters(queries: jnp.ndarray, index: IVFIndex, *, nprobe: int,
                    metric: str = "l2") -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stage A (paper Fig. 1): pick the nprobe closest/most-similar centroids.

    Mapped to the MXU exactly as the paper maps it to Tensor cores (§5.3):
    ``|x-q|^2 = x^2 - 2 x.q^T + q^2`` — a single GEMM plus rank-1 terms.
    Returns (scores, cluster_ids), each (Q, nprobe). Scores are
    lower-is-better for L2 and higher-is-better for IP.
    """
    qc = queries.astype(jnp.float32) @ index.centroids.T        # (Q, C)
    if metric == "l2":
        d = index.centroid_sq[None, :] - 2.0 * qc               # |q|^2 omitted (rank-only)
        neg_scores, ids = jax.lax.top_k(-d, nprobe)
        return -neg_scores, ids
    elif metric == "ip":
        scores, ids = jax.lax.top_k(qc, nprobe)
        return scores, ids
    raise ValueError(f"unknown metric {metric!r}")
