"""Pallas TPU kernel: fused selective LUT construction (paper §4.1/§4.2).

One pass over the codebook produces, per (query-residual, subspace):
  * the masked L2/IP LUT row (pruned entries pre-substituted with tau^2 /
    the IP floor) and
  * the int8 hit table (+1 inner sphere, 0 outer ring, -1 miss — paper §5.4),
so the RT-core's "membership test + free distance from t_hit" collapses into
a single VMEM-resident fused kernel (DESIGN.md §2): codebook coordinates are
read from HBM once per block and never touched again downstream.

Layout: 2-D subspaces (M=2, as in JUNO) are carried as separate planes: the
query side q0/q1/tau as (B, S, 1) columns that broadcast along lanes, the
codebook side e0/e1/esq as (S, E) planes, so every operand is lane-aligned
on E (=256) and no (…, 2) trailing dim comes near the VPU.

Grid: (B/bB,); each program computes a (bB, S, E) tile of both outputs, the
whole subspace axis at once (a block on the last two dims must span them
or be (8, 128)-aligned). VMEM per program ≈ bB·S·E·(4+1) + 3·S·E·4 ≈
0.6 MB at (bB, S, E) = (8, 48, 256), 1.3 MB at S = 100.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .onehot import sublane_tile

DEFAULT_BB = 8   # query-residual rows per program


def _hit_code(inner, outer):
    """+1 inner sphere, 0 outer ring, -1 miss, as int8 (int32 arithmetic:
    the VPU has no int8 subtract)."""
    return (inner.astype(jnp.int32) - (~outer).astype(jnp.int32)
            ).astype(jnp.int8)


def _kernel_l2(q0_ref, q1_ref, e0_ref, e1_ref, esq_ref, tau_ref,
               lut_ref, hit_ref):
    q0 = q0_ref[...]                       # (bB, S, 1)
    q1 = q1_ref[...]
    e0 = e0_ref[...][None]                 # (1, S, E)
    e1 = e1_ref[...][None]
    esq = esq_ref[...][None]
    tau = tau_ref[...]                     # (bB, S, 1)

    # |r - e|^2 = |r|^2 - 2 r.e + |e|^2 — rank-1 expansion, fused per tile
    r_sq = q0 * q0 + q1 * q1                                     # (bB, S, 1)
    dot = q0 * e0 + q1 * e1                                      # (bB, S, E)
    dist = r_sq - 2.0 * dot + esq

    tau_sq = tau * tau
    outer = dist <= tau_sq
    inner = dist <= 0.25 * tau_sq
    # masked LUT: pruned entries substituted with their tau^2 lower bound
    lut_ref[...] = jnp.where(outer, dist, tau_sq)
    hit_ref[...] = _hit_code(inner, outer)


def _kernel_ip(q0_ref, q1_ref, e0_ref, e1_ref, esq_ref, tau_ref,
               lut_ref, hit_ref):
    q0 = q0_ref[...]                       # (bB, S, 1)
    q1 = q1_ref[...]
    tau = tau_ref[...]

    dot = q0 * e0_ref[...][None] + q1 * e1_ref[...][None]       # (bB, S, E)
    # transformed-L2 selection geometry (the paper's radius-folding trick):
    t = esq_ref[...][None] - 2.0 * dot
    tau_sq = tau * tau
    outer = t <= tau_sq
    inner = t <= 0.25 * tau_sq
    # pruned entries get a -tau^2/2 placeholder: the row-min of kept entries
    # (the reference substitution) needs a reduction over the whole E axis,
    # which this tiled kernel cannot do in one pass. ops.build_selective_lut
    # replaces the placeholder with the exact kept-row min afterwards so the
    # pallas and ref paths rank identically (tests/test_impl_parity.py).
    lut_ref[...] = jnp.where(outer, dot, -0.5 * tau_sq)
    hit_ref[...] = _hit_code(inner, outer)


@functools.partial(jax.jit, static_argnames=("metric", "bb", "interpret"))
def selective_lut(q0: jnp.ndarray, q1: jnp.ndarray, e0: jnp.ndarray,
                  e1: jnp.ndarray, esq: jnp.ndarray, tau: jnp.ndarray, *,
                  metric: str = "l2", bb: int = DEFAULT_BB,
                  interpret: bool = False):
    """q0/q1 (B, S) f32; e0/e1/esq (S, E) f32; tau (B, S) f32.
    Returns (masked_lut (B, S, E) f32, hit_table (B, S, E) int8). B is
    padded to the block size and sliced back."""
    b, s = q0.shape
    e = e0.shape[1]
    bb = sublane_tile(b, bb)
    pad = (-b) % bb
    q0, q1, tau = (jnp.pad(x, ((0, pad), (0, 0)))[..., None]
                   for x in (q0, q1, tau))
    bp = b + pad

    q_spec = pl.BlockSpec((bb, s, 1), lambda i: (i, 0, 0))
    e_spec = pl.BlockSpec((s, e), lambda i: (0, 0))
    out_spec = pl.BlockSpec((bb, s, e), lambda i: (i, 0, 0))
    kernel = _kernel_l2 if metric == "l2" else _kernel_ip

    lut, hit = pl.pallas_call(
        kernel,
        grid=(bp // bb,),
        in_specs=[q_spec, q_spec, e_spec, e_spec, e_spec, q_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((bp, s, e), jnp.float32),
                   jax.ShapeDtypeStruct((bp, s, e), jnp.int8)],
        interpret=interpret,
    )(q0, q1, e0, e1, esq, tau)
    return lut[:b], hit[:b]
