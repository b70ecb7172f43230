"""Build-time spatial index over IVF cluster centroids (the "BVH build").

The paper's stage-1 filter runs on RT cores: cluster centroids become
spheres, the query becomes a ray origin, and BVH traversal answers "which
clusters might contain near neighbours" without touching the ones that
cannot. This module is the build-time half of the TPU re-mapping
(docs/kernels.md §RT): a **uniform cell grid** over a 2-D orthonormal
projection of the centroids (the "ray plane") with per-cell centroid lists
padded to static shapes, so the online walk (`repro.rt.intersect`) is a
regular grid-shaped kernel instead of pointer chasing.

Geometry
--------
Every cluster ``c`` carries a *projected reach* ``r_c`` — the radius of the
smallest disc around its projected centroid containing every member point's
projection — computed exactly at build time (projection first, then max).
A query with sphere radius ``R`` intersects cluster ``c`` iff::

    ||P q - P c||_2 <= R + r_c        (P = the (D, 2) orthonormal projection)

which is exactly "query disc touches cluster disc" in the ray plane and a
superset of the members the full-space sphere can contain *in that plane*.
The per-cell bound ``cell_reach = max_c r_c`` lets the online kernel skip
whole cells (the traversal analogue). ``R`` itself comes from the density
model's calibrated per-subspace thresholds — see :func:`query_radius`.

The grid is a plain NamedTuple of arrays: it shards/replicates like any
other index component, serializes alongside the index
(:func:`save_grid`/:func:`load_grid`), and updates in place on online
inserts touching only the affected cells (:func:`update_radii`).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# analytic fallback (calib_queries=0): a full-space distance R contracts to
# ~R*sqrt(m/D) under an orthonormal (D, m) projection; SIGMA standard
# deviations of the (Rayleigh-ish) projected length keep essentially every
# in-sphere point inside the projected query disc. The calibrated build
# replaces this with a measured quantile (see _radius_calibration).
DEFAULT_SIGMA = 3.0


class CentroidGrid(NamedTuple):
    """Static-shape uniform cell grid over projected cluster centroids.

    Attributes
    ----------
    proj : jnp.ndarray
        (D, 2) f32 — orthonormal projection onto the ray plane.
    lo, hi : jnp.ndarray
        (2,) f32 — grid bounding box in the ray plane.
    boxes : jnp.ndarray
        (n_cells, 4) f32 — per-cell AABB as ``[lo0, lo1, hi0, hi1]``.
    cell_ids : jnp.ndarray
        (n_cells, cap) int32 — padded per-cell cluster-id lists; -1 = pad.
    cell_c0, cell_c1 : jnp.ndarray
        (n_cells, cap) f32 — projected centroid coordinates per slot,
        carried as separate lane-aligned planes (selective_lut idiom).
    slot_reach : jnp.ndarray
        (n_cells, cap) f32 — projected cluster reach per slot; ``-inf`` at
        pad slots, so the signed intersection test can never hit them.
    cell_reach : jnp.ndarray
        (n_cells,) f32 — ``max`` of slot_reach per cell (``-inf`` when the
        cell is empty); the kernel's cell-skip bound.
    slot_of : jnp.ndarray
        (C,) int32 — flat slot index (``cell * cap + slot``) of each
        cluster; inverts the cell layout back to cluster order.
    radius_scale : jnp.ndarray
        () f32 — full-space → ray-plane radius contraction
        (``sqrt(2 / D)``; the analytic fallback folds in DEFAULT_SIGMA).
    radius_bias : jnp.ndarray
        () f32 — calibrated additive radius term (ray-plane units); the
        ``coverage`` quantile of ``needed - contraction * ||τ||`` over
        calibration queries, so ``rt_scale=1`` hits the coverage target
        while the knob stays monotone (larger scale ⇒ more survivors).
    """

    proj: jnp.ndarray
    lo: jnp.ndarray
    hi: jnp.ndarray
    boxes: jnp.ndarray
    cell_ids: jnp.ndarray
    cell_c0: jnp.ndarray
    cell_c1: jnp.ndarray
    slot_reach: jnp.ndarray
    cell_reach: jnp.ndarray
    slot_of: jnp.ndarray
    radius_scale: jnp.ndarray
    radius_bias: jnp.ndarray

    @property
    def n_cells(self) -> int:
        """Number of grid cells (G²)."""
        return self.cell_ids.shape[0]

    @property
    def capacity(self) -> int:
        """Padded per-cell centroid-list capacity."""
        return self.cell_ids.shape[1]

    @property
    def grid_size(self) -> int:
        """Cells per axis G (the grid is square)."""
        return int(round(self.n_cells ** 0.5))


def _projection(dim: int, seed: int) -> np.ndarray:
    """Deterministic (D, 2) orthonormal projection via QR of a Gaussian."""
    g = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (dim, 2)),
                   np.float64)
    q, _ = np.linalg.qr(g)
    return q.astype(np.float32)


def _decoded_residuals(data, rows: int = 8192) -> np.ndarray:
    """(N, D) host array of every point's PQ-reconstructed residual,
    decoded ``rows`` points at a time: decoding all N at once materializes
    an (N·S, 2) array, which a TPU pads to 128 lanes (30 GB at N = 600K,
    S = 100)."""
    from repro.core.pq import decode
    codes = data.codes
    return np.concatenate([
        np.asarray(decode(codes[i:i + rows], data.codebook))
        for i in range(0, codes.shape[0], rows)])


def _radius_calibration(data, proj: np.ndarray, reach: np.ndarray, *,
                        metric: str, coverage: float, n_queries: int,
                        k: int = 10, seed: int = 0,
                        points: np.ndarray | None = None) -> float:
    """Measure the τ → ray-plane-radius scale on reconstruction queries.

    Same recipe as ``_calibrate_density``: perturbed database points act
    as calibration queries, their exact top-``k`` give ground truth. For
    each query the smallest radius whose survivor set covers *every*
    owner cluster of its top-k is ``max_owner(||qp - cp|| - reach_c)``;
    subtracting the query's contracted ``sqrt(Σ_s τ_s²)`` (the same
    density-model thresholds search time will have) leaves the additive
    correction the analytic radius misses, and the ``coverage`` quantile
    of those corrections becomes ``radius_bias`` — so at ``rt_scale=1.0``
    roughly a ``coverage`` fraction of queries keep all their
    true-neighbour clusters as survivors.
    """
    from repro.core import density as density_lib
    from repro.core.ref import exact_topk

    cent = np.asarray(data.ivf.centroids, np.float32)
    labels = np.asarray(data.ivf.labels)
    if points is not None:
        pts = np.asarray(points, np.float32)
    else:
        pts = cent[labels] + _decoded_residuals(data)
    n = pts.shape[0]
    nq = min(n_queries, n)
    rng = np.random.default_rng(seed)
    qidx = rng.choice(n, size=nq, replace=False)
    noise = 0.01 * rng.standard_normal((nq, pts.shape[1])) * pts.std()
    queries = (pts[qidx] + noise).astype(np.float32)

    _, gt = exact_topk(jnp.asarray(queries), jnp.asarray(pts), k=k,
                       metric=metric, chunk=min(65536, n))
    owners = labels[np.asarray(gt)]                            # (nq, k)
    qp = queries @ proj
    cp = cent @ proj
    dproj = np.linalg.norm(qp[:, None, :] - cp[owners], axis=-1)
    needed = (dproj - reach[owners]).max(axis=1)               # (nq,)

    if metric == "l2":   # probe-0 residual geometry, as at search time
        d = np.sum(cent * cent, -1)[None, :] - 2.0 * queries @ cent.T
        res = queries - cent[np.argmin(d, axis=1)]
    else:
        res = queries
    m = data.codebook.sub_dim
    tau = np.asarray(density_lib.predict_threshold(
        data.density, jnp.asarray(res.reshape(nq, -1, m)), 1.0))
    tau_norm = np.sqrt(np.sum(tau * tau, axis=-1))
    contract = (2.0 / cent.shape[1]) ** 0.5
    return float(np.quantile(needed - contract * tau_norm, coverage))


def build_grid(data, *, metric: str = "l2", grid_size: int | None = None,
               proj_seed: int = 0, coverage: float = 0.9,
               calib_queries: int = 64,
               points: np.ndarray | None = None) -> CentroidGrid:
    """Build the centroid cell grid for a built index.

    Parameters
    ----------
    data : JunoIndexData
        A built index (``repro.core.build``); centroids, labels and PQ
        codes are read from it.
    metric : str
        "l2" | "ip" — the metric the index serves (drives calibration).
    grid_size : int, optional
        Cells per axis. Default: ``max(2, round(sqrt(C / 4)))`` — about
        four centroids per cell.
    proj_seed : int
        PRNG seed for the orthonormal ray-plane projection.
    coverage : float
        Radius-calibration target: at ``rt_scale=1.0`` about this
        fraction of calibration queries keep every owner cluster of
        their exact top-10 in the survivor set.
    calib_queries : int
        Calibration sample size; 0 skips calibration and falls back to
        the analytic ``DEFAULT_SIGMA * sqrt(2/D)`` contraction.
    points : np.ndarray, optional
        (N, D) f32 raw database points. When given, per-cluster reaches
        and calibration use exact residuals; otherwise positions are
        reconstructed from the PQ codes (``pq.decode``), which
        under-measures reach by at most the quantization error.

    Returns
    -------
    CentroidGrid
        The static-shape grid, ready for :func:`survivor_mask`.
    """
    cent = np.asarray(data.ivf.centroids, np.float32)          # (C, D)
    labels = np.asarray(data.ivf.labels)
    c, d = cent.shape
    proj = _projection(d, proj_seed)
    cp = cent @ proj                                           # (C, 2)

    if points is not None:
        res = np.asarray(points, np.float32) - cent[labels]
    else:
        res = _decoded_residuals(data)
    rp = res @ proj                                            # (N, 2)
    rnorm = np.sqrt(np.sum(rp * rp, axis=-1))
    reach = np.zeros((c,), np.float32)
    np.maximum.at(reach, labels, rnorm)

    if calib_queries > 0:
        radius_scale = (2.0 / d) ** 0.5
        radius_bias = _radius_calibration(
            data, proj, reach, metric=metric, coverage=coverage,
            n_queries=calib_queries, seed=proj_seed, points=points)
    else:
        radius_scale = DEFAULT_SIGMA * (2.0 / d) ** 0.5
        radius_bias = 0.0

    g = grid_size or max(2, int(round((c / 4.0) ** 0.5)))
    lo = cp.min(axis=0)
    hi = cp.max(axis=0)
    span = np.maximum(hi - lo, 1e-6)
    ij = np.clip(((cp - lo) / span * g).astype(np.int64), 0, g - 1)
    flat_cell = ij[:, 0] * g + ij[:, 1]

    counts = np.bincount(flat_cell, minlength=g * g)
    cap = max(8, int(-(-counts.max() // 8) * 8))               # pad to 8
    cell_ids = np.full((g * g, cap), -1, np.int32)
    slot_reach = np.full((g * g, cap), -np.inf, np.float32)
    cell_c0 = np.zeros((g * g, cap), np.float32)
    cell_c1 = np.zeros((g * g, cap), np.float32)
    slot_of = np.zeros((c,), np.int32)
    fill = np.zeros((g * g,), np.int64)
    for cid in range(c):
        cell = flat_cell[cid]
        s = fill[cell]
        cell_ids[cell, s] = cid
        cell_c0[cell, s] = cp[cid, 0]
        cell_c1[cell, s] = cp[cid, 1]
        slot_reach[cell, s] = reach[cid]
        slot_of[cid] = cell * cap + s
        fill[cell] += 1

    cell_lo = lo[None, :] + np.stack(
        np.meshgrid(np.arange(g), np.arange(g), indexing="ij"),
        axis=-1).reshape(-1, 2) * (span / g)[None, :]
    boxes = np.concatenate([cell_lo, cell_lo + (span / g)[None, :]],
                           axis=1).astype(np.float32)

    return CentroidGrid(
        proj=jnp.asarray(proj), lo=jnp.asarray(lo.astype(np.float32)),
        hi=jnp.asarray(hi.astype(np.float32)), boxes=jnp.asarray(boxes),
        cell_ids=jnp.asarray(cell_ids), cell_c0=jnp.asarray(cell_c0),
        cell_c1=jnp.asarray(cell_c1), slot_reach=jnp.asarray(slot_reach),
        cell_reach=jnp.asarray(slot_reach.max(axis=1)),
        slot_of=jnp.asarray(slot_of),
        radius_scale=jnp.float32(radius_scale),
        radius_bias=jnp.float32(radius_bias))


def query_radius(grid: CentroidGrid, tau: jnp.ndarray,
                 scale: jnp.ndarray | float = 1.0) -> jnp.ndarray:
    """Ray-plane query-sphere radius from the calibrated thresholds.

    The density model's per-subspace thresholds τ_s are calibrated so the
    top-k's entries fall within τ_s of the query's subspace projection
    (paper §4.1); since full-space distances add over subspaces,
    ``sqrt(Σ_s τ_s²)`` is the matching full-space radius, and
    ``grid.radius_scale`` contracts it into the ray plane.

    Parameters
    ----------
    grid : CentroidGrid
        The built grid (supplies ``radius_scale``).
    tau : jnp.ndarray
        (Q, S) f32 per-subspace thresholds for each query — e.g. the
        probe-0 row of the thresholds ``_search_batch`` already computes.
    scale : float or jnp.ndarray
        User knob (the rt analogue of ``thres_scale``): > 1 trades
        throughput for coverage — the radius is monotone in it — and
        very large values cover every cell (the full-coverage limit the
        parity tests pin).

    Returns
    -------
    jnp.ndarray
        (Q,) f32 ray-plane radii,
        ``scale · radius_scale · sqrt(Σ_s τ_s²) + radius_bias``.
    """
    return (jnp.asarray(scale, jnp.float32) * grid.radius_scale
            * jnp.sqrt(jnp.sum(tau * tau, axis=-1)) + grid.radius_bias)


def survivor_mask(grid: CentroidGrid, queries: jnp.ndarray,
                  radius: jnp.ndarray, *, impl: str | None = None
                  ) -> jnp.ndarray:
    """Per-(query, cluster) sphere-intersection hits, in cluster order.

    Projects the queries onto the ray plane, runs the cell-walk
    intersection stage (``kernels.ops.rt_sphere_hits`` — Pallas on TPU,
    host path off-TPU) and inverts the cell layout back to cluster order.

    Parameters
    ----------
    grid : CentroidGrid
        The built grid.
    queries : jnp.ndarray
        (Q, D) f32 full-space queries.
    radius : jnp.ndarray
        (Q,) f32 ray-plane radii (:func:`query_radius`).
    impl : str, optional
        Forwarded to ``rt_sphere_hits`` (``"ref"``: dense jnp path).

    Returns
    -------
    jnp.ndarray
        (Q, C) int8 — 1 where the query sphere intersects the cluster's
        disc, 0 elsewhere; the stage-1 survivor mask consumed ahead of the
        hit-count / masked-ADC scans.
    """
    from repro.kernels import ops as kops
    qp = queries.astype(jnp.float32) @ grid.proj
    hits = kops.rt_sphere_hits(qp[:, 0], qp[:, 1], radius, grid.boxes,
                               grid.cell_reach, grid.cell_c0, grid.cell_c1,
                               grid.slot_reach, impl=impl)
    return jnp.take(hits, grid.slot_of, axis=1)


def update_radii(grid: CentroidGrid, clusters, reaches) -> CentroidGrid:
    """Grow per-cluster reaches after online inserts (touched cells only).

    Inserts never move centroids, so cell membership is stable — the only
    grid state an insert can invalidate is the reach of the owning
    cluster (a new point may project farther from its centroid than any
    existing member). This recomputes ``slot_reach``/``cell_reach`` for
    exactly the touched slots/cells; deletes are left alone (a stale
    larger reach only over-covers, never drops a survivor).

    Parameters
    ----------
    grid : CentroidGrid
        Current grid.
    clusters : array-like
        (B,) int — owning cluster of each inserted point.
    reaches : array-like
        (B,) f32 — projected residual length of each inserted point
        (``||(p - centroid) @ proj||``).

    Returns
    -------
    CentroidGrid
        Updated grid (shares every untouched array with the input).
    """
    clusters = np.atleast_1d(np.asarray(clusters, np.int64))
    reaches = np.atleast_1d(np.asarray(reaches, np.float32))
    cap = grid.capacity
    slots = np.asarray(grid.slot_of)[clusters]
    slot_reach = np.asarray(grid.slot_reach).copy()
    np.maximum.at(slot_reach.reshape(-1), slots, reaches)
    cells = np.unique(slots // cap)
    cell_reach = np.asarray(grid.cell_reach).copy()
    cell_reach[cells] = slot_reach[cells].max(axis=1)
    return grid._replace(slot_reach=jnp.asarray(slot_reach),
                         cell_reach=jnp.asarray(cell_reach))


def routing_state(grid: CentroidGrid, data) -> dict:
    """Host-side (numpy) snapshot of everything :func:`probe_budget` reads.

    The serving engine routes every request through ``probe_budget``;
    pulling the density grid and centroid planes off-device per request
    would dominate the (microseconds-scale) numpy math, so the engine
    caches this snapshot and refreshes it only when the grid object
    changes (online inserts grow reaches via :func:`update_radii`, which
    builds a new grid).

    Parameters
    ----------
    grid : CentroidGrid
        The built grid.
    data : JunoIndexData
        The served index (centroids + density model).

    Returns
    -------
    dict
        Plain numpy arrays/scalars keyed by name; pass as the ``state``
        argument of :func:`probe_budget`.
    """
    dens = data.density
    return {
        "cent": np.asarray(data.ivf.centroids, np.float32),
        "dens_grid": np.asarray(dens.grid),
        "dens_lo": np.asarray(dens.lo), "dens_hi": np.asarray(dens.hi),
        "coeffs": np.asarray(dens.coeffs),
        "tau_min": float(dens.tau_min), "tau_max": float(dens.tau_max),
        "sub_dim": int(data.codebook.sub_dim),
        "proj": np.asarray(grid.proj),
        "slot_of": np.asarray(grid.slot_of),
        "c0": np.asarray(grid.cell_c0).reshape(-1),
        "c1": np.asarray(grid.cell_c1).reshape(-1),
        "reach": np.asarray(grid.slot_reach).reshape(-1),
        "radius_scale": float(grid.radius_scale),
        "radius_bias": float(grid.radius_bias),
    }


def probe_budget(grid: CentroidGrid, data, queries, *, metric: str = "l2",
                 scale: float = 1.0, thres_scale: float = 1.0,
                 max_probes: int = 16,
                 state: dict | None = None) -> np.ndarray:
    """Host-side (numpy) per-query probe budget — the router's rt input.

    For each query, ranks the ``max_probes`` best clusters by the same
    stage-A score ``filter_clusters`` uses and returns the rank of the
    LAST one surviving the sphere-intersection test. Probing that many
    clusters (plus the probe-0 backstop) reaches every cluster the rt
    mask would keep at the full budget — ranks beyond it are pruned
    probes that contribute only sentinels — so shrinking a request's
    nprobe to the next bucket ≥ this value loses nothing the mask would
    have kept.

    Parameters
    ----------
    grid : CentroidGrid
        The built grid.
    data : JunoIndexData
        The served index (centroids + density model).
    queries : np.ndarray
        (Q, D) f32 queries.
    metric : str
        "l2" | "ip".
    scale : float
        Same radius knob as :func:`query_radius`.
    thres_scale : float
        The search-time selectivity-threshold multiplier — MUST match
        the ``thres_scale`` of the searches being routed, because the
        in-search mask derives its radius from the scaled τ.
    max_probes : int
        The unshrunk probe budget to rank within.
    state : dict, optional
        Cached :func:`routing_state` snapshot (avoids per-call
        device→host copies on the serving hot path).

    Returns
    -------
    np.ndarray
        (Q,) int64 in ``[1, max_probes]``.
    """
    st = state if state is not None else routing_state(grid, data)
    q = np.atleast_2d(np.asarray(queries, np.float32))
    cent = st["cent"]
    max_probes = min(max_probes, cent.shape[0])
    qc = q @ cent.T                                            # (Q, C), once
    if metric == "l2":
        score = np.sum(cent * cent, -1)[None, :] - 2.0 * qc
        res = q - cent[np.argmin(score, axis=1)]
    else:
        score = -qc
        res = q
    order = np.argsort(score, axis=1)[:, :max_probes]          # (Q, np)

    qsub = res.reshape(q.shape[0], -1, st["sub_dim"])
    g = st["dens_grid"]
    gsz = g.shape[-1]
    span = np.maximum(st["dens_hi"] - st["dens_lo"], 1e-6)
    ij = np.clip(((qsub - st["dens_lo"]) / span * gsz).astype(np.int64),
                 0, gsz - 1)
    dval = g[np.arange(g.shape[0])[None, :], ij[..., 0], ij[..., 1]]
    tau = np.clip(np.polyval(st["coeffs"], dval),
                  st["tau_min"], st["tau_max"]) * thres_scale
    radius = (scale * st["radius_scale"]
              * np.sqrt(np.sum(tau * tau, axis=-1)) + st["radius_bias"])

    qp = q @ st["proj"]
    flat = st["slot_of"][order]                                # (Q, np)
    dx = qp[:, 0, None] - st["c0"][flat]
    dy = qp[:, 1, None] - st["c1"][flat]
    thr = radius[:, None] + st["reach"][flat]
    hit = (thr >= 0) & (dx * dx + dy * dy <= thr * thr)
    hit[:, 0] = True                                           # backstop
    return max_probes - np.argmax(hit[:, ::-1], axis=1)


def save_grid(path: str, grid: CentroidGrid) -> None:
    """Serialize a grid to ``path`` (.npz) alongside the index it indexes.

    Parameters
    ----------
    path : str
        Target file path (np.savez format).
    grid : CentroidGrid
        The grid to persist.
    """
    np.savez(path, **{k: np.asarray(v) for k, v in grid._asdict().items()})


def load_grid(path: str) -> CentroidGrid:
    """Load a grid serialized by :func:`save_grid`.

    Parameters
    ----------
    path : str
        File written by :func:`save_grid`.

    Returns
    -------
    CentroidGrid
        The deserialized grid (device arrays).
    """
    with np.load(path) as z:
        return CentroidGrid(**{k: jnp.asarray(z[k])
                               for k in CentroidGrid._fields})
