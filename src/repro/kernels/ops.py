"""Jit'd public wrappers over the Pallas kernels, chosen by platform.

On a TPU every wrapper runs its compiled Pallas kernel. Off-TPU the plain
scans (``selective_lut``, ``hit_count``, ``pq_scan``) run the same kernels
in interpret mode — the kernel body executes in Python over real blocks,
validating BlockSpec tiling and semantics — while the fused scans and the
RT walk dispatch to schedule-equivalent host paths. ``_on_tpu()`` decides.

:func:`slab_onehot_dot`, the one-hot MXU contraction the kernel bodies
share, lives in ``kernels.onehot`` and is re-exported here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.rt import intersect as _rt

from . import fused_three_stage as _fused3
from . import fused_two_stage as _fused
from . import hit_count as _hit
from . import ivf_filter as _filt
from . import pq_scan as _scan
from . import selective_lut as _lut
from .onehot import slab_onehot_dot  # noqa: F401  (public re-export)


@functools.cache
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_impl() -> str:
    """The search path a caller gets when it names none: ``"pallas"`` (the
    compiled kernels) on a TPU, ``"ref"`` (the jnp reference) elsewhere,
    where the kernels would only run in the interpreter."""
    return "pallas" if _on_tpu() else "ref"


def _kernels(impl: str | None) -> bool:
    """Whether a dispatcher runs its compiled kernel: on a TPU, unless the
    caller asked for the jnp reference (``impl="ref"``)."""
    return impl != "ref" and _on_tpu()


def _interpret() -> bool:
    return not _on_tpu()


def build_selective_lut(qsub: jnp.ndarray, entries: jnp.ndarray,
                        entry_sq: jnp.ndarray, tau: jnp.ndarray, *,
                        metric: str = "l2"):
    """qsub (..., S, 2) f32, entries (S, E, 2), entry_sq (S, E), tau (..., S).
    Returns (masked_lut (..., S, E) f32, hit_table (..., S, E) int8).
    Leading dims are flattened into the kernel's batch axis."""
    lead = qsub.shape[:-2]
    s = qsub.shape[-2]
    b = 1
    for d in lead:
        b *= d
    q0 = qsub[..., 0].reshape(b, s)
    q1 = qsub[..., 1].reshape(b, s)
    lut, hit = _lut.selective_lut(q0, q1, entries[..., 0], entries[..., 1],
                                  entry_sq, tau.reshape(b, s), metric=metric,
                                  interpret=_interpret())
    e = entries.shape[1]
    lut = lut.reshape(*lead, s, e)
    hit = hit.reshape(*lead, s, e)
    if metric == "ip":
        # The kernel substitutes pruned entries with -tau^2/2 (the exact
        # floor needs a row reduction over kept entries, which would cost a
        # second kernel pass). Recover the reference semantics here with one
        # cheap vectorized pass so impl="pallas" and impl="ref" rank
        # identically.
        from repro.core.lut import ip_pruned_fill
        lut = ip_pruned_fill(lut, hit >= 0)
    return lut, hit


def masked_adc_scan(lut: jnp.ndarray, codes: jnp.ndarray, valid: jnp.ndarray,
                    *, metric: str = "l2") -> jnp.ndarray:
    """lut (..., S, E), codes (..., P, S), valid (..., P) → (..., P) f32."""
    lead = codes.shape[:-2]
    if not lead:
        return _scan.pq_scan(lut, codes, valid, metric=metric,
                             interpret=_interpret())
    fn = functools.partial(_scan.pq_scan, metric=metric,
                           interpret=_interpret())
    for _ in lead:
        fn = jax.vmap(fn)
    return fn(lut, codes, valid)


def hit_count_scan(table: jnp.ndarray, codes: jnp.ndarray,
                   valid: jnp.ndarray) -> jnp.ndarray:
    """table (..., S, E) int8, codes (..., P, S), valid (..., P) → int32."""
    lead = codes.shape[:-2]
    if not lead:
        return _hit.hit_count(table, codes, valid, interpret=_interpret())
    fn = functools.partial(_hit.hit_count, interpret=_interpret())
    for _ in lead:
        fn = jax.vmap(fn)
    return fn(table, codes, valid)


def fused_two_stage_scan(mlut: jnp.ndarray, table: jnp.ndarray,
                         codes: jnp.ndarray, valid: jnp.ndarray, *,
                         cap_c: int, metric: str = "l2",
                         impl: str | None = None):
    """Fused two-stage scan: hit-count prefilter → survivor threshold →
    masked ADC + top-candidate compaction.

    mlut/table (Q, np, S, E), codes (Q, np, P, S), valid (Q, np, P) →
    (counts (Q, np, P) i32, dist (Q, np, P) f32, cand (Q, C) i32,
     cand_dist (Q, C) f32); ``cand`` is the top-cap_c-by-count candidate
    set, ``cand_dist`` their masked-LUT totals — the two-stage search
    consumes these directly, with no separate rerank gather.

    On TPU these are the two-stage Pallas kernels (counts, ``lax.top_k``,
    survivor-masked ADC), unless ``impl="ref"`` asks for the jnp
    reference. Otherwise it dispatches to the schedule-equivalent
    host path rather than interpret mode: interpreting the kernels would
    serialize the serving hot path, and the host path's histogram
    selection is the same survivor-threshold idea expressed CPU-natively.
    The interpret-mode kernels are validated against the composed kernels
    by tests/test_fused_kernel.py.

    The result-invariant tile/θ-selection knobs come from the process
    active :class:`repro.kernels.autotune.KernelConfig` (read at trace
    time — install tuned configs before the first dispatch).
    """
    from . import autotune
    cfg = autotune.active_config("fused_two_stage")
    if _kernels(impl):
        return _fused.fused_two_stage(mlut, table, codes, valid,
                                      cap_c=cap_c, metric=metric,
                                      bq=cfg.bq, bp=cfg.bp,
                                      acc=cfg.acc_dtype)
    return _fused.fused_two_stage_host(mlut, table, codes, valid,
                                       cap_c=cap_c, metric=metric,
                                       topc_impl=cfg.topc_impl)


def fused_three_stage_scan(mlut: jnp.ndarray, table: jnp.ndarray,
                           codes: jnp.ndarray, valid: jnp.ndarray,
                           q0: jnp.ndarray, q1: jnp.ndarray,
                           radius: jnp.ndarray, cell_c0: jnp.ndarray,
                           cell_c1: jnp.ndarray, slot_reach: jnp.ndarray,
                           slot_idx: jnp.ndarray, *, cap_c: int,
                           metric: str = "l2", impl: str | None = None):
    """Single-residency three-stage scan: RT sphere test → hit-count
    prefilter → masked ADC + top-candidate compaction, in one pass.

    The :func:`fused_two_stage_scan` contract with the RT probe filter
    folded in as stage 0: ``q0``/``q1``/``radius`` are the ray-plane
    queries, ``cell_c0``/``cell_c1``/``slot_reach`` the ``CentroidGrid``
    layout, and ``slot_idx`` (Q, np)
    int32 the probed clusters' flat slot indices
    (``grid.slot_of[cids]``). Returns the two-stage 4-tuple plus
    ``probe_ok`` (Q, np) bool — identical to the host-side
    ``_rt_probe_mask`` gather (probe 0 always True), so downstream
    side-buffer scoring applies the same verdict the kernel applied to
    in-cluster points.

    Dispatch/knob rules are those of :func:`fused_two_stage_scan`: the
    Pallas kernel on TPU, the schedule-equivalent host path off-TPU, with
    the active ``autotune`` config (``fused_three_stage`` entry) applied
    at trace time. Bit-identical to composing :func:`rt_sphere_hits` →
    probe-mask gather → :func:`fused_two_stage_scan`
    (tests/test_fused3_kernel.py).
    """
    from . import autotune
    cfg = autotune.active_config("fused_three_stage")
    if _kernels(impl):
        return _fused3.fused_three_stage(
            mlut, table, codes, valid, q0, q1, radius, cell_c0, cell_c1,
            slot_reach, slot_idx, cap_c=cap_c,
            metric=metric, bq=cfg.bq, bp=cfg.bp, acc=cfg.acc_dtype)
    return _fused3.fused_three_stage_host(
        mlut, table, codes, valid, q0, q1, radius, cell_c0, cell_c1,
        slot_reach, slot_idx, cap_c=cap_c, metric=metric,
        topc_impl=cfg.topc_impl)


def rt_sphere_hits(q0: jnp.ndarray, q1: jnp.ndarray, radius: jnp.ndarray,
                   boxes: jnp.ndarray, cell_reach: jnp.ndarray,
                   c0: jnp.ndarray, c1: jnp.ndarray,
                   slot_reach: jnp.ndarray, *,
                   impl: str | None = None) -> jnp.ndarray:
    """RT-core-style sphere-intersection filter (stage-1 spatial pruning).

    Parameters
    ----------
    q0, q1, radius : jnp.ndarray
        (Q,) f32 ray-plane query coordinates and query-sphere radii.
    boxes : jnp.ndarray
        (n_cells, 4) f32 per-cell AABBs (kernel path's cell-skip input).
    cell_reach : jnp.ndarray
        (n_cells,) f32 per-cell max reach (``-inf`` = empty cell).
    c0, c1, slot_reach : jnp.ndarray
        (n_cells, cap) f32 projected centroid planes and per-slot reaches
        (``-inf`` = pad slot).
    impl : str, optional
        ``"ref"`` forces the dense jnp path on a TPU too.

    Returns
    -------
    jnp.ndarray
        (Q, n_cells·cap) int8 flat hit table, cell-major.

    Notes
    -----
    On TPU this runs the cell-walk Pallas kernel (``rt.intersect``); the
    AABB pre-test skips a cell's disc tests when no query disc touches
    it. Off-TPU it dispatches to the dense host path rather than
    interpret mode — same dispatch rule (and rationale) as
    :func:`fused_two_stage_scan`; results are identical either way
    because the cell skip is conservative.
    """
    if _kernels(impl):
        return _rt.sphere_hits(q0, q1, radius, boxes, cell_reach,
                               c0, c1, slot_reach)
    return _rt.sphere_hits_host(q0, q1, radius, c0, c1, slot_reach)


def filter_scores(queries, centroids, centroid_sq, *, metric="l2"):
    """Fused IVF filtering distance matrix (paper stage A on the MXU)."""
    return _filt.ivf_filter(queries, centroids, centroid_sq, metric=metric,
                            interpret=_interpret())
