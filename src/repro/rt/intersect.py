"""Pallas TPU kernel: RT-core-style sphere-intersection filter stage.

The paper's stage-1 filter is an RT-core BVH traversal: the query is cast
as a ray origin and the hardware reports which centroid spheres it lands
in, skipping whole BVH subtrees that cannot intersect. The TPU has no
traversal unit, but the grid built by ``repro.rt.grid`` gives the same
two-level structure in a regular layout — and this kernel walks it:

* **grid axis = cells.** One program per (query-block, cell). The cell's
  AABB is tested against the block's query discs first; a cell no disc
  touches writes zeros and **skips the per-centroid work entirely**
  (``pl.when``) — the TPU-shaped analogue of the BVH skipping subtrees.
* **slot test.** For live cells, the (bQ, cap) disc-vs-disc test
  ``||qp - cp|| <= R + reach`` runs on lane-aligned coordinate planes
  (c0/c1 — the selective_lut idiom), emitting int8 hits.

Both tests compare *squared* distances guarded by ``thr >= 0`` so the
``-inf`` pad/empty sentinels from the grid build can never hit. The cell
test is conservative by construction (centroids lie inside their cell's
AABB and ``cell_reach >= reach``, with float monotonicity preserving both
inequalities), so kernel output is bit-identical to the dense oracle
``kernels.ref.rt_sphere_hits_ref`` — the skip changes work, never results.

``sphere_hits_host`` is the dense jnp path used for off-TPU serving
(dispatched by ``kernels.ops.rt_sphere_hits``): at host scale the whole
slot table is a few thousand lanes, so the dense test beats paying
interpret-mode overhead per cell.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.onehot import sublane_tile

DEFAULT_BQ = 8   # query rows per program


def _sphere_kernel(q0_ref, q1_ref, r_ref, box_ref, creach_ref,
                   c0_ref, c1_ref, reach_ref, out_ref):
    """One (query-block, cell) program: AABB pre-test, then disc tests.

    Queries arrive as (bQ, 1) columns and the cell's data as (1, ·) rows,
    so every test is a lane/sublane broadcast."""
    q0 = q0_ref[...]                                  # (bQ, 1)
    q1 = q1_ref[...]
    r = r_ref[...]
    box = box_ref[...]                                # (1, 4) lo0 lo1 hi0 hi1
    dx = jnp.clip(q0, box[:, 0:1], box[:, 2:3]) - q0  # query → AABB offset
    dy = jnp.clip(q1, box[:, 1:2], box[:, 3:4]) - q1
    d2_cell = dx * dx + dy * dy
    thr_cell = r + creach_ref[...]                    # (bQ, 1)
    live = (thr_cell >= 0.0) & (d2_cell <= thr_cell * thr_cell)
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(jnp.max(live.astype(jnp.int32)) > 0)
    def _slot_tests():
        sx = q0 - c0_ref[...]                         # (bQ, cap)
        sy = q1 - c1_ref[...]
        d2 = sx * sx + sy * sy
        thr = r + reach_ref[...]
        hit = (thr >= 0.0) & (d2 <= thr * thr)
        out_ref[...] = hit.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def sphere_hits(q0: jnp.ndarray, q1: jnp.ndarray, radius: jnp.ndarray,
                boxes: jnp.ndarray, cell_reach: jnp.ndarray,
                c0: jnp.ndarray, c1: jnp.ndarray, slot_reach: jnp.ndarray,
                *, bq: int = DEFAULT_BQ,
                interpret: bool = False) -> jnp.ndarray:
    """Cell-walk sphere-intersection filter (see module docstring).

    Parameters
    ----------
    q0, q1 : jnp.ndarray
        (Q,) f32 — ray-plane query coordinates.
    radius : jnp.ndarray
        (Q,) f32 — ray-plane query-sphere radii.
    boxes : jnp.ndarray
        (n_cells, 4) f32 — per-cell AABBs ``[lo0, lo1, hi0, hi1]``.
    cell_reach : jnp.ndarray
        (n_cells,) f32 — per-cell max centroid reach (``-inf`` = empty).
    c0, c1 : jnp.ndarray
        (n_cells, cap) f32 — projected centroid coordinate planes.
    slot_reach : jnp.ndarray
        (n_cells, cap) f32 — per-slot reach (``-inf`` = pad slot).
    bq : int
        Query rows per program (rounded to a tile the chip accepts).
    interpret : bool
        Run the Pallas interpreter (CPU validation) instead of compiling.

    Returns
    -------
    jnp.ndarray
        (Q, n_cells · cap) int8 flat hit table, cell-major — index it with
        ``CentroidGrid.slot_of`` to recover cluster order.

    Notes
    -----
    Every per-cell operand gets a unit middle axis — (n_cells, 1, ·) with
    the cell axis squeezed out of the block — so each block spans its
    array's last two dimensions, as the chip requires. The kernel writes a
    cell-major (n_cells, Q, cap) table, transposed here.
    """
    q = q0.shape[0]
    n_cells, cap = c0.shape
    bq = sublane_tile(q, bq)
    pad_q = (-q) % bq
    q0, q1, radius = (jnp.pad(x, (0, pad_q))[:, None]
                      for x in (q0, q1, radius))
    qp = q + pad_q
    col = pl.BlockSpec((bq, 1), lambda i, c: (i, 0))

    def cell(width):
        return pl.BlockSpec((None, 1, width), lambda i, c: (c, 0, 0))

    out = pl.pallas_call(
        _sphere_kernel,
        grid=(qp // bq, n_cells),
        in_specs=[col, col, col, cell(4), cell(1), cell(cap), cell(cap),
                  cell(cap)],
        out_specs=pl.BlockSpec((None, bq, cap), lambda i, c: (c, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_cells, qp, cap), jnp.int32),
        interpret=interpret,
    )(q0, q1, radius, boxes[:, None], cell_reach[:, None, None],
      c0[:, None], c1[:, None], slot_reach[:, None])
    return jnp.transpose(out[:, :q], (1, 0, 2)).reshape(
        q, n_cells * cap).astype(jnp.int8)


@jax.jit
def sphere_hits_host(q0: jnp.ndarray, q1: jnp.ndarray, radius: jnp.ndarray,
                     c0: jnp.ndarray, c1: jnp.ndarray,
                     slot_reach: jnp.ndarray) -> jnp.ndarray:
    """Dense jnp sphere-intersection path for off-TPU serving.

    Identical results to the kernel (the cell pre-test is conservative, so
    skipping it changes nothing); at host scale the dense (Q, n_cells·cap)
    test is a handful of fused vector ops and beats per-cell interpreter
    dispatch. The body IS the dense oracle
    (``kernels.ref.rt_sphere_hits_ref``) under one jit — a single source
    of truth, so host path and semantics of record cannot drift.

    Parameters
    ----------
    q0, q1, radius : jnp.ndarray
        (Q,) f32 ray-plane query coordinates and radii.
    c0, c1, slot_reach : jnp.ndarray
        (n_cells, cap) f32 centroid planes and per-slot reaches
        (``-inf`` = pad).

    Returns
    -------
    jnp.ndarray
        (Q, n_cells · cap) int8 flat hit table (cell-major).
    """
    from repro.kernels.ref import rt_sphere_hits_ref
    return rt_sphere_hits_ref(q0, q1, radius, c0, c1, slot_reach)
