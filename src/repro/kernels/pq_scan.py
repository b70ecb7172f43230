"""Pallas TPU kernel: masked ADC scan (paper Fig. 1 stage D, JUNO-H).

Accumulates per-point total distance from the masked LUT:
    total[p] = sum_s lut[s, codes[p, s]]

TPU mapping: the per-(point, subspace) gather is expressed as a one-hot
contraction  lut (1, S·E) · one_hot(codes) (bP, S·E)^T → (1, bP)  on the MXU
(``onehot.slab_onehot_dot``) — the direct TPU analogue of the paper's
Tensor-core "A × B(=ones)" accumulation trick (§5.3): the quantized codes
choose MXU operand columns instead of driving scalar lookups.

Grid: (P/bP,). LUT stays VMEM-resident across all point blocks (constant
index map), codes stream through; points run on lanes, so ``valid`` and
the output are (1, P) rows. VMEM ≈ bP·SLAB·E·4 for the one-hot, which is
formed per 8-subspace slab (1 MB at bP = 128, E = 256).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .onehot import lane_tile, slab_onehot_dot

DEFAULT_BP = 128   # points per program
SLAB = 8           # subspaces one-hot-expanded at a time (VMEM control)


def _scan_kernel(lut_ref, codes_ref, valid_ref, out_ref, *, n_entries,
                 bad_value):
    acc = slab_onehot_dot(codes_ref[...].astype(jnp.int32), lut_ref[...],
                          n_entries=n_entries, out_dtype=jnp.float32,
                          slab=SLAB)                  # (1, bP)
    out_ref[...] = jnp.where(valid_ref[...] != 0, acc, bad_value)


@functools.partial(jax.jit, static_argnames=("metric", "bp", "interpret"))
def pq_scan(lut: jnp.ndarray, codes: jnp.ndarray, valid: jnp.ndarray, *,
            metric: str = "l2", bp: int = DEFAULT_BP,
            interpret: bool = False) -> jnp.ndarray:
    """lut (S, E) f32 (pre-masked), codes (P, S) uint8, valid (P,) bool.
    Returns (P,) f32 total scores; invalid slots get ±inf. ``bp`` is
    rounded up to a lane multiple (see ``hit_count``)."""
    p, s = codes.shape
    e = lut.shape[1]
    bp = lane_tile(bp)
    pad = (-p) % bp
    codes = jnp.pad(codes, ((0, pad), (0, 0)))
    valid = jnp.pad(valid, (0, pad)).astype(jnp.int32)[None]
    bad = float("inf") if metric == "l2" else float("-inf")

    out = pl.pallas_call(
        functools.partial(_scan_kernel, n_entries=e, bad_value=bad),
        grid=((p + pad) // bp,),
        in_specs=[
            pl.BlockSpec((s, e), lambda i: (0, 0)),   # LUT resident
            pl.BlockSpec((bp, s), lambda i: (i, 0)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, bp), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p + pad), jnp.float32),
        interpret=interpret,
    )(lut, codes, valid)
    return out[0, :p]
