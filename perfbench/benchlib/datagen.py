"""Seeded synthetic ANN corpora and query pools, made on the device.

A copy of the program's generator (``src/repro/data/synthetic.py``), kept
here so that the benchmark's inputs cannot change with the program: an
anisotropic Gaussian mixture with power-law mode weights (imbalanced IVF
clusters) whose points lie near their mode (local PQ entries). For
``ip`` corpora point norms spread over [1, 1.3) and queries are unit norm.

The mixture itself (its modes' means and scales) is part of the
configuration: it comes from the configuration's ``mixture_seed``, so every
run serves data of one shape. The points and queries drawn from it come
from one key derived from ``--seed``; chunk ``i`` of the corpus is drawn
under its own key folded from it, so a corpus of any size is made one
chunk at a time by one compiled program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (both 32-bit halves are folded in,
    so seeds above 2**31 are distinct and valid)."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _mixture(gen: dict, dim: int, key: jax.Array):
    """The latent mixture (means, per-mode scales, weights), fixed by
    ``gen["mixture_seed"]``, and the keys the samplers draw from ``key``."""
    k_mu, k_scale = jax.random.split(jax.random.PRNGKey(gen["mixture_seed"]))
    k_assign, k_pts, k_q, k_rot = jax.random.split(key, 4)
    g = gen["n_modes"]
    mu = jax.random.normal(k_mu, (g, dim)) * 4.0
    scales = jnp.exp(jax.random.normal(k_scale, (g, dim)) *
                     jnp.log(gen["anisotropy"]) / 2.0)
    w = jnp.arange(1, g + 1, dtype=jnp.float32) ** (-gen["power"])
    w = w / jnp.sum(w)
    return (mu, scales, w), (k_assign, k_pts, k_q, k_rot)


@functools.partial(jax.jit, static_argnames=("n", "dim", "metric", "n_modes",
                                             "anisotropy", "power",
                                             "mixture_seed"))
def _chunk(key, i, *, n, dim, metric, n_modes, anisotropy, power,
           mixture_seed):
    gen = dict(n_modes=n_modes, anisotropy=anisotropy, power=power,
               mixture_seed=mixture_seed)
    (mu, scales, w), (k_assign, k_pts, _, k_rot) = _mixture(gen, dim, key)
    k_assign, k_pts, k_rot = (jax.random.fold_in(k, i)
                              for k in (k_assign, k_pts, k_rot))
    assign = jax.random.choice(k_assign, n_modes, shape=(n,), p=w)
    eps = jax.random.normal(k_pts, (n, dim))
    points = mu[assign] + eps * scales[assign]
    if metric == "ip":
        norm = jnp.linalg.norm(points, axis=-1, keepdims=True)
        points = points / jnp.maximum(norm, 1e-6) * (
            1.0 + 0.3 * jax.random.uniform(k_rot, (n, 1)))
    return points.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "dim", "metric", "n_modes",
                                             "anisotropy", "power",
                                             "mixture_seed"))
def _queries(key, *, n, dim, metric, n_modes, anisotropy, power,
             mixture_seed):
    gen = dict(n_modes=n_modes, anisotropy=anisotropy, power=power,
               mixture_seed=mixture_seed)
    (mu, scales, w), (_, _, k_q, _) = _mixture(gen, dim, key)
    qassign = jax.random.choice(k_q, n_modes, shape=(n,), p=w)
    qeps = jax.random.normal(jax.random.fold_in(k_q, 1), (n, dim))
    queries = mu[qassign] + qeps * scales[qassign] * 1.1
    if metric == "ip":
        queries = queries / jnp.maximum(
            jnp.linalg.norm(queries, axis=-1, keepdims=True), 1e-6)
    return queries.astype(jnp.float32)


def _static(data: dict) -> dict:
    gen = data["generator"]
    return dict(dim=data["dim"], metric=data["metric"],
                n_modes=gen["n_modes"], anisotropy=gen["anisotropy"],
                power=gen["power"], mixture_seed=gen["mixture_seed"])


def corpus_chunks(data: dict, key: jax.Array) -> list[jax.Array]:
    """The corpus as device chunks of ``data["chunk_rows"]`` rows (the last
    may be shorter); ``data`` is a configuration's ``dataset`` entry. The
    queries of :func:`query_pool` under the same key come from the same
    mixture."""
    n, rows = data["n_points"], data["chunk_rows"]
    st = _static(data)
    return [_chunk(key, i, n=min(rows, n - lo), **st)
            for i, lo in enumerate(range(0, n, rows))]


def query_pool(data: dict, key: jax.Array, n: int) -> jax.Array:
    """``n`` queries (n, D) f32 on the device; the pool of one run."""
    return _queries(key, n=n, **_static(data))
