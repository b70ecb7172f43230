"""Selective L2/IP-LUT construction (paper §4) — reference JAX path.

The Pallas kernel in ``repro.kernels.lut_build`` fuses the same computation;
this module is the semantics of record. For each selected cluster residual,
computes the (S, E) table of sub-distances plus the selection mask
``dist <= tau[s]`` — the TPU analogue of the RT-core in/out check, where the
dense E-wide MXU row replaces the BVH traversal and the dynamic threshold
vector replaces ``t_max`` (DESIGN.md §2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .pq import PQCodebook

BIG = jnp.float32(1e9)


def build_lut(residual_sub: jnp.ndarray, codebook: PQCodebook, tau: jnp.ndarray,
              *, metric: str = "l2") -> tuple[jnp.ndarray, jnp.ndarray]:
    """residual_sub: (..., S, M) query-minus-centroid projections.
    tau: (..., S) per-subspace dynamic thresholds.

    Returns (lut, mask), each (..., S, E):
      l2: lut[s,e] = |r_s - e|^2,         mask = lut <= tau^2
      ip: lut[s,e] = <r_s, e>,            mask = (|e|^2 - 2<r_s,e>) <= tau^2
          (the paper's radius-folding trick: threshold on the transformed L2
          so selection still means "spatially close", while the LUT stores the
          similarity that will be accumulated — higher-is-better.)
    """
    # HIGHEST: at default precision a TPU rounds the f32 operands of this
    # contraction to bf16, and the reference would drift from the kernel
    r_dot_e = jnp.einsum("...sm,sem->...se", residual_sub,
                         codebook.entries,
                         precision=jax.lax.Precision.HIGHEST)  # (..., S, E)
    e_sq = codebook.entry_sq                                    # (S, E)
    tau_sq = (tau * tau)[..., None]
    if metric == "l2":
        r_sq = jnp.sum(residual_sub * residual_sub, -1)[..., None]
        lut = r_sq - 2.0 * r_dot_e + e_sq
        mask = lut <= tau_sq
        return lut, mask
    elif metric == "ip":
        lut = r_dot_e
        mask = (e_sq - 2.0 * r_dot_e) <= tau_sq                 # |e-r|^2 - |r|^2 <= tau^2
        return lut, mask
    raise ValueError(f"unknown metric {metric!r}")


def masked_lut(lut: jnp.ndarray, mask: jnp.ndarray, tau: jnp.ndarray,
               *, metric: str = "l2") -> jnp.ndarray:
    """Substitute pruned entries with their information-preserving bound.

    Paper Alg. 2 drops pruned entries entirely and gives never-hit points a
    large constant. We use the tighter per-subspace substitution: a pruned
    entry's sub-distance is *at least* tau[s] (L2) / at most the threshold
    bound (IP), so substituting the bound keeps ranking sound while exactly
    reproducing the paper's "large constant" behaviour for points pruned in
    every subspace (sum of bounds ≈ BIG ordering-wise).
    """
    if metric == "l2":
        fill = (tau * tau)[..., None]
        return jnp.where(mask, lut, fill)
    return ip_pruned_fill(lut, mask)


def ip_pruned_fill(lut: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """IP-metric pruned-entry substitution: each pruned entry contributes the
    worst KEPT similarity of its row (0.0 when a row keeps nothing).

    This is THE definition of ip pruning semantics — also applied by
    ``kernels.ops.build_selective_lut`` (post-pass over the kernel's
    placeholder) and mirrored by ``kernels.ref.selective_lut_ref``, so the
    ref/pallas/core paths cannot silently diverge again
    (tests/test_impl_parity.py)."""
    fill = jnp.min(jnp.where(mask, lut, jnp.inf), axis=-1, keepdims=True)
    fill = jnp.where(jnp.isfinite(fill), fill, 0.0)
    return jnp.where(mask, lut, fill)


def hit_tables(lut: jnp.ndarray, mask: jnp.ndarray, tau: jnp.ndarray,
               *, mode: str = "reward_penalty", metric: str = "l2") -> jnp.ndarray:
    """Hit-count tables (paper §5.4) as int8 (..., S, E).

    mode="count"          : JUNO-L — outer-sphere hit = +1, miss = 0
    mode="reward_penalty" : JUNO-M — inner sphere (tau/2) = +1, outer only = 0,
                            miss both = -1
    For IP the inner test uses the same transformed-L2 geometry as the mask.
    """
    if metric != "l2":
        raise ValueError("use hit_tables_ip for the IP metric")
    inner = lut <= (0.5 * tau[..., None]) ** 2
    if mode == "count":
        return mask.astype(jnp.int8)
    elif mode == "reward_penalty":
        return (inner.astype(jnp.int8) - (~mask).astype(jnp.int8))
    raise ValueError(f"unknown hit-count mode {mode!r}")


def hit_tables_ip(r_dot_e: jnp.ndarray, entry_sq: jnp.ndarray, tau: jnp.ndarray,
                  *, mode: str = "reward_penalty") -> jnp.ndarray:
    """IP-metric hit tables from raw dot products (transformed-L2 geometry)."""
    t = entry_sq - 2.0 * r_dot_e            # |e-r|^2 - |r|^2, monotone in L2
    tau_sq = (tau * tau)[..., None]
    outer = t <= tau_sq
    if mode == "count":
        return outer.astype(jnp.int8)
    inner = t <= 0.25 * tau_sq
    return inner.astype(jnp.int8) - (~outer).astype(jnp.int8)
