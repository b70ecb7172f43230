"""Synthetic ANN datasets statistically matched to the paper's benchmarks.

SIFT1M / DEEP1M / TTI1M are not redistributable offline, so we synthesize
anisotropic Gaussian-mixture stand-ins whose two properties JUNO exploits are
present by construction: (i) IVF-cluster imbalance (power-law cluster sizes)
and (ii) PQ-entry sparsity/locality (points concentrate near their cluster
centre, so top-k entries are spatially local in each subspace).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    dim: int
    metric: str          # "l2" | "ip"
    n_modes: int = 256   # latent mixture components
    anisotropy: float = 4.0
    power: float = 1.5   # cluster-size power law exponent


SIFT_LIKE = DatasetSpec("sift-like", 128, "l2")
DEEP_LIKE = DatasetSpec("deep-like", 96, "l2")
TTI_LIKE = DatasetSpec("tti-like", 200, "ip", n_modes=128)


def _mixture(spec: DatasetSpec, key: jax.Array):
    """The latent mixture (means, per-mode scales, weights) and the keys
    the samplers draw from — fixed by ``key`` alone."""
    k_mu, k_scale, k_assign, k_pts, k_q, k_rot = jax.random.split(key, 6)
    d, g = spec.dim, spec.n_modes
    mu = jax.random.normal(k_mu, (g, d)) * 4.0
    # anisotropic per-mode scales: a few directions dominate (like real
    # descriptor data after PCA) — drives the entry-locality the paper sees.
    scales = jnp.exp(jax.random.normal(k_scale, (g, d)) *
                     jnp.log(spec.anisotropy) / 2.0)
    # power-law mode weights -> imbalanced IVF clusters
    w = jnp.arange(1, g + 1, dtype=jnp.float32) ** (-spec.power)
    w = w / jnp.sum(w)
    return (mu, scales, w), (k_assign, k_pts, k_q, k_rot)


def _points(spec, mix, n, k_assign, k_pts, k_rot):
    """``n`` corpus points from the mixture (ip: norms spread in [1, 1.3))."""
    mu, scales, w = mix
    assign = jax.random.choice(k_assign, spec.n_modes, shape=(n,), p=w)
    eps = jax.random.normal(k_pts, (n, spec.dim))
    points = mu[assign] + eps * scales[assign]
    if spec.metric == "ip":  # normalise magnitude spread for MIPS realism
        norm = jnp.linalg.norm(points, axis=-1, keepdims=True)
        points = points / jnp.maximum(norm, 1e-6) * (
            1.0 + 0.3 * jax.random.uniform(k_rot, (n, 1)))
    return points.astype(jnp.float32)


def _queries(spec, mix, n, k_q):
    """``n`` queries: mixture draws at 1.1× the spread (ip: unit norm)."""
    mu, scales, w = mix
    qassign = jax.random.choice(k_q, spec.n_modes, shape=(n,), p=w)
    qeps = jax.random.normal(jax.random.fold_in(k_q, 1), (n, spec.dim))
    queries = mu[qassign] + qeps * scales[qassign] * 1.1
    if spec.metric == "ip":
        queries = queries / jnp.maximum(
            jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-6)
    return queries.astype(jnp.float32)


def make_dataset(spec: DatasetSpec, n_points: int, n_queries: int,
                 key: jax.Array | None = None):
    """Returns (points (N, D) f32, queries (Q, D) f32)."""
    if key is None:
        key = jax.random.PRNGKey(42)
    mix, (k_assign, k_pts, k_q, k_rot) = _mixture(spec, key)
    return (_points(spec, mix, n_points, k_assign, k_pts, k_rot),
            _queries(spec, mix, n_queries, k_q))


def make_dataset_chunked(spec: DatasetSpec, n_points: int, n_queries: int,
                         key: jax.Array, *, chunk: int):
    """The :func:`make_dataset` distribution at corpus sizes one array
    cannot hold: points come as a list of device arrays of ``chunk`` rows
    (the last may be shorter), each drawn under its own key folded from
    ``key``, so peak memory is one chunk's temporaries.

    Returns (list of (≤chunk, D) f32 point chunks, queries (Q, D) f32).
    """
    mix, (k_assign, k_pts, k_q, k_rot) = _mixture(spec, key)
    sample = jax.jit(lambda i, n: _points(
        spec, mix, n, jax.random.fold_in(k_assign, i),
        jax.random.fold_in(k_pts, i), jax.random.fold_in(k_rot, i)),
        static_argnums=1)
    chunks = [sample(i, min(chunk, n_points - lo))
              for i, lo in enumerate(range(0, n_points, chunk))]
    return chunks, _queries(spec, mix, n_queries, k_q)
