"""Shared pieces of the scan kernels: the one-hot MXU contraction and the
TPU tile rules.

Every block a kernel hands to Mosaic obeys the (8, 128) rule: its last two
dimensions are multiples of (8, 128) or span the whole array dimension.
:func:`sublane_tile` and :func:`lane_tile` pick such tiles, so the
interpret-mode tests on the CPU run the same tiling the chip compiles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DEFAULT_SLAB = 8
SUBLANE = 8
LANE = 128


def sublane_tile(n: int, want: int) -> int:
    """Rows per block on a second-to-last axis of length ``n``: the whole
    axis when it fits in one block, else ``want`` rounded up to 8."""
    want = max(SUBLANE, -(-want // SUBLANE) * SUBLANE)
    return n if n <= want else want


def lane_tile(want: int | None) -> int:
    """Lanes per block on a last axis: ``want`` rounded up to 128."""
    return max(LANE, -(-(want or LANE) // LANE) * LANE)


def slab_onehot_dot(codes: jnp.ndarray, tab: jnp.ndarray, *, n_entries: int,
                    out_dtype=jnp.float32,
                    slab: int = DEFAULT_SLAB) -> jnp.ndarray:
    """``out[0, p] = sum_s tab[s, codes[p, s]]`` on the MXU.

    codes (bP, S) int32, tab (S, E) → (1, bP) in ``out_dtype``.

    The per-(point, subspace) LUT gather is expressed as a one-hot
    contraction, the TPU analogue of the paper's Tensor-core "A × B(=ones)"
    accumulation trick (§5.3): quantized codes choose MXU operand columns
    instead of driving scalar lookups. For ``slab`` subspaces at a time the
    one-hots ``codes[:, s] == e`` (bP, E) are laid side by side on the lane
    axis into (bP, slab·E), the matching LUT rows into (1, slab·E), and one
    ``dot_general`` contracts the lane axes of both. Only lane-aligned
    concatenations and a transposed-operand matmul are used, so Mosaic
    lowers it with no shape cast; the slab bounds the one-hot's VMEM at
    bP·slab·E·itemsize. Accumulation dtype is pinned by ``out_dtype`` via
    ``preferred_element_type``: int32 for the int8 hit path, f32 (at
    ``Precision.HIGHEST``, so LUT values are not rounded to bf16 on the
    MXU) for the ADC path (tests/test_kernels.py pins both).

    Shared by the kernel bodies of ``hit_count``, ``pq_scan`` and the fused
    two- and three-stage scans, one query row at a time; callable both
    inside Pallas kernels and as plain jnp.
    """
    bp, n_sub = codes.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (bp, n_entries), 1)
    op_dtype = tab.dtype
    precision = (jax.lax.Precision.HIGHEST if op_dtype == jnp.float32
                 else None)
    acc = jnp.zeros((1, bp), out_dtype)
    for s0 in range(0, n_sub, slab):
        subs = range(s0, min(s0 + slab, n_sub))
        oh = jnp.concatenate(
            [(codes[:, s:s + 1] == lane).astype(op_dtype) for s in subs],
            axis=1)                                   # (bP, sl·E)
        row = jnp.concatenate([tab[s:s + 1, :] for s in subs], axis=1)
        acc = acc + jax.lax.dot_general(
            row, oh, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=out_dtype)         # (1, bP)
    return acc
