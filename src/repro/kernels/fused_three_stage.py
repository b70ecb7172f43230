"""Pallas TPU kernels: three-stage search — RT sphere test, int8 hit-count
prefilter and survivor-masked ADC (paper §5.5 + §6; DESIGN.md §2-§3).

The paper's full hardware pipeline maps the RT-core sphere test *into* the
tensor-core distance stage: survivors of the BVH traversal stream straight
to the distance stage without a host-visible round trip. The composed path
pays that round trip — ``rt_sphere_hits`` produces an HBM-resident
(Q, n_cells·cap) table, the host gathers it into a probe mask, and the mask
re-enters ``fused_two_stage`` as a pre-masked ``valid``. Here the sphere
test is stage 0 of the two-stage counts kernel (``fused_two_stage.
two_stage``): each (query-block, point-block) tile belongs to one probe,
and the kernel tests the block's query discs against that probed cluster's
projected centroid and reach (gathered from the ``CentroidGrid`` layout at
``slot_idx``) before counting. The hit table never materializes: only the
probes a query actually scans are tested, so the cell walk and its AABB
skip have nothing left to prune.

Outputs are the two-stage kernel's four (equal to composing
``rt_sphere_hits`` → probe-mask gather → ``fused_two_stage``; pinned by
tests/test_fused3_kernel.py) plus ``probe_ok`` (Q, np) bool — the stage-0
verdict per probed cluster, identical to ``core.juno._rt_probe_mask`` —
so the side-buffer/minor-tier path downstream applies the SAME verdict to
out-of-cluster points as the kernel applied to their in-cluster siblings.
Probe 0 is the nearest-probe backstop: its reach is set to +inf, so its
disc test always passes, like ``_rt_probe_mask``'s ``.at[:, 0].set(True)``.

``fused_three_stage_host`` is the schedule-equivalent host path for
off-TPU serving; ``kernels.ref.fused_three_stage_ref`` is the dense jnp
oracle. Tile/accumulation knobs (``bq``/``bp``/``acc``/``topc_impl``) are
supplied by ``kernels.autotune`` and are result-invariant by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .fused_two_stage import (DEFAULT_BP, DEFAULT_BQ, fused_two_stage_host,
                              two_stage)
from .ref import rt_sphere_hits_ref


@functools.partial(jax.jit,
                   static_argnames=("cap_c", "metric", "bq", "bp", "acc",
                                    "interpret"))
def fused_three_stage(lut: jnp.ndarray, table: jnp.ndarray,
                      codes: jnp.ndarray, valid: jnp.ndarray,
                      q0: jnp.ndarray, q1: jnp.ndarray, radius: jnp.ndarray,
                      cell_c0: jnp.ndarray, cell_c1: jnp.ndarray,
                      slot_reach: jnp.ndarray, slot_idx: jnp.ndarray, *,
                      cap_c: int, metric: str = "l2", bq: int = DEFAULT_BQ,
                      bp: int | None = DEFAULT_BP, acc: str = "f32",
                      interpret: bool = False):
    """lut/table (Q, np, S, E) f32/int8, codes (Q, np, P, S) uint8,
    valid (Q, np, P) bool; q0/q1/radius (Q,) f32 ray-plane queries;
    cell_c0/cell_c1/slot_reach (n_cells, cap) — the ``CentroidGrid``
    layout; slot_idx (Q, np) int32 = ``grid.slot_of[probed cluster ids]``
    → (counts (Q, np, P) i32, dist (Q, np, P) f32, cand (Q, C) i32,
    cand_dist (Q, C) f32, probe_ok (Q, np) bool). See module docstring.
    ``bq``/``bp``/``acc`` are the autotuner's knobs — all
    result-invariant."""
    pc0 = jnp.take(cell_c0.reshape(-1), slot_idx)           # (Q, np)
    pc1 = jnp.take(cell_c1.reshape(-1), slot_idx)
    preach = jnp.take(slot_reach.reshape(-1), slot_idx)
    preach = preach.at[:, 0].set(jnp.inf)                   # probe-0 backstop
    return two_stage(lut, table, codes, valid, cap_c=cap_c, metric=metric,
                     bq=bq, bp=bp, acc=acc, interpret=interpret,
                     rt=(q0, q1, radius, pc0, pc1, preach))


@functools.partial(jax.jit, static_argnames=("cap_c", "metric", "topc_impl"))
def fused_three_stage_host(lut: jnp.ndarray, table: jnp.ndarray,
                           codes: jnp.ndarray, valid: jnp.ndarray,
                           q0: jnp.ndarray, q1: jnp.ndarray,
                           radius: jnp.ndarray, cell_c0: jnp.ndarray,
                           cell_c1: jnp.ndarray, slot_reach: jnp.ndarray,
                           slot_idx: jnp.ndarray, *, cap_c: int,
                           metric: str = "l2", topc_impl: str = "sort"):
    """Schedule-equivalent host path for off-TPU serving: the dense sphere
    test (``rt_sphere_hits_ref`` — no cell skip needed at host scale)
    gathered at ``slot_idx`` plays phase 0, masks ``valid``, and the result
    flows through ``fused_two_stage_host`` (same contract/deviations as
    documented there; ``topc_impl`` is its autotuner θ-selection knob).
    Returns the kernel's 5-tuple."""
    hits = rt_sphere_hits_ref(q0, q1, radius, cell_c0, cell_c1, slot_reach)
    pok = jnp.take_along_axis(hits, slot_idx, axis=1) > 0
    pok = pok.at[:, 0].set(True)
    valid = valid & pok[:, :, None]
    counts, dist, cand, cdist = fused_two_stage_host(
        lut, table, codes, valid, cap_c=cap_c, metric=metric,
        topc_impl=topc_impl)
    return counts, dist, cand, cdist, pok
