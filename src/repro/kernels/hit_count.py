"""Pallas TPU kernel: hit-count scan (paper §5.4, JUNO-L/M).

score[p] = sum_s table[s, codes[p, s]]  with table in {+1, 0, -1} int8.

This is the aggressive approximation: the f32 LUT is never touched — an int8
reward/penalty table is contracted against one-hot codes with int32
accumulation (VPU/MXU int8 path), 4× denser than the exact scan. The TPU
stand-in for "count ray hits instead of computing distances".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .onehot import lane_tile, slab_onehot_dot

DEFAULT_BP = 128
SLAB = 8

_NEG = -(2 ** 30)  # python int → baked literal (pallas rejects traced consts)


def _hit_kernel(table_ref, codes_ref, valid_ref, out_ref, *, n_entries):
    acc = slab_onehot_dot(codes_ref[...].astype(jnp.int32), table_ref[...],
                          n_entries=n_entries, out_dtype=jnp.int32,
                          slab=SLAB)                  # (1, bP)
    out_ref[...] = jnp.where(valid_ref[...] != 0, acc, _NEG)


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def hit_count(table: jnp.ndarray, codes: jnp.ndarray, valid: jnp.ndarray, *,
              bp: int = DEFAULT_BP, interpret: bool = False) -> jnp.ndarray:
    """table (S, E) int8, codes (P, S) uint8, valid (P,) bool → (P,) int32.

    ``bp`` is rounded up to a lane multiple and P padded to it; the point
    axis runs on lanes, so ``valid`` and the output are (1, P) rows."""
    p, s = codes.shape
    e = table.shape[1]
    bp = lane_tile(bp)
    pad = (-p) % bp
    codes = jnp.pad(codes, ((0, pad), (0, 0)))
    valid = jnp.pad(valid, (0, pad)).astype(jnp.int32)[None]

    out = pl.pallas_call(
        functools.partial(_hit_kernel, n_entries=e),
        grid=((p + pad) // bp,),
        in_specs=[
            pl.BlockSpec((s, e), lambda i: (0, 0)),
            pl.BlockSpec((bp, s), lambda i: (i, 0)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, bp), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p + pad), jnp.int32),
        interpret=interpret,
    )(table, codes, valid)
    return out[0, :p]
