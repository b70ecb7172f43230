"""JunoIndex — the end-to-end JUNO system (paper Alg. 1 + Alg. 2).

Offline (``build``): IVF k-means → residual PQ codebooks → padded per-cluster
code storage (the TPU layout of the paper's entry→points inverted index) →
density grid + polynomial threshold regressor calibration.

Online (``search``): MXU filtering → selective LUT construction with dynamic
per-subspace thresholds (the RT-core stage, re-mapped per DESIGN.md §2) →
masked ADC scan (JUNO-H) or int8 hit-count scan (JUNO-L/M) → top-k.

Modes map 1:1 to the paper's operating points:
  "H" — exact selective distances            (high quality)
  "M" — reward/penalty hit count, r & r/2    (medium)
  "L" — plain hit count                      (low quality, max throughput)
plus one beyond-paper mode exploiting the same sparsity TPU-natively:
  "H2" — two-stage: int8 hit-count prefilter selects a static top-C
         candidate set, exact ADC reranks only those. The paper skips
         far points dynamically on the RT core; H2 gets the same skip as
         a static-shape top-k — ~(nprobe·P)/C less f32 gather work at
         JUNO-H-level recall (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import density as density_lib
from . import lut as lut_lib
from . import scan as scan_lib
from .ivf import IVFIndex, build_ivf, filter_clusters
from .pq import PQCodebook, encode, split_subspaces, train_codebook
from .ref import exact_topk


@dataclasses.dataclass(frozen=True)
class JunoConfig:
    """Build-time knobs of the JUNO index (paper defaults in comments)."""

    n_clusters: int = 1024          # C
    n_entries: int = 256            # E
    sub_dim: int = 2                # M (JUNO uses 2-D subspaces)
    metric: str = "l2"              # "l2" | "ip"
    kmeans_iters: int = 10
    capacity_mult: float = 4.0
    # Lloyd training (IVF and PQ) runs on at most this many points
    # (FAISS-style subsampled training); the full set is only streamed
    # through chunked assignment/encoding. <= 0 disables subsampling.
    max_train_points: int = 200_000
    grid_size: int = 64             # density grid G (paper: 100)
    calib_queries: int = 128        # queries used to fit the threshold poly
    calib_topk: int = 100           # "top-100" of the paper
    poly_degree: int = 2


class JunoIndexData(NamedTuple):
    """A built index: IVF + PQ codebooks + padded codes + density model."""

    ivf: IVFIndex
    codebook: PQCodebook
    codes: jnp.ndarray           # (N, S) uint8
    cluster_codes: jnp.ndarray   # (C, P, S) uint8 — padded per-cluster codes
    density: density_lib.DensityModel
    points_sq: jnp.ndarray       # (N,) f32 (kept for oracles/rerank)


class SideBuffer(NamedTuple):
    """Fixed-capacity exact-membership overflow store for online inserts.

    When an insert's owning cluster has no free padded slot left, the point
    spills here instead of forcing a rebuild. Side points are scored during
    search with the SAME masked-LUT / hit-table gather an in-cluster point
    would receive (and only when their owning cluster is probed), so
    ``compact()`` — which moves them back into freed cluster slots — is a
    search no-op.
    """
    codes: jnp.ndarray     # (B, S) uint8 — PQ codes of spilled points
    cluster: jnp.ndarray   # (B,) int32 — owning cluster (-1 = empty slot)
    ids: jnp.ndarray       # (B,) int32 — global point id
    valid: jnp.ndarray     # (B,) bool

    @property
    def capacity(self) -> int:
        """Fixed slot count B of the buffer."""
        return self.ids.shape[0]


def empty_side_buffer(capacity: int, n_subspaces: int) -> SideBuffer:
    """Allocate an all-empty :class:`SideBuffer`.

    Parameters
    ----------
    capacity : int
        Fixed slot count B (part of the jitted search signature).
    n_subspaces : int
        PQ subspace count S of the index the buffer will ride along with.

    Returns
    -------
    SideBuffer
        codes (B, S) uint8 zeros, cluster/ids (B,) int32 = -1,
        valid (B,) bool = False.
    """
    return SideBuffer(
        codes=jnp.zeros((capacity, n_subspaces), jnp.uint8),
        cluster=jnp.full((capacity,), -1, jnp.int32),
        ids=jnp.full((capacity,), -1, jnp.int32),
        valid=jnp.zeros((capacity,), bool))


def _side_gather(table: jnp.ndarray, cids: jnp.ndarray, side: SideBuffer
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Score side-buffer points against a per-probe LUT/hit table.

    table (Q, np, S, E), cids (Q, np). A side point participates only when
    its owning cluster is among the probed clusters — exactly the condition
    under which it would have been scanned had it lived in its cluster's
    padded slots — and its score is the same gather+sum the in-cluster scan
    performs, so folding it back via ``compact()`` changes nothing.

    Returns (totals (Q, B), probe (Q, B), ok (Q, B)).
    """
    nq = cids.shape[0]
    match = cids[:, :, None] == side.cluster[None, None, :]      # (Q, np, B)
    ok = jnp.any(match, axis=1) & side.valid[None, :]            # (Q, B)
    probe = jnp.argmax(match, axis=1)                            # (Q, B)
    qi = jnp.arange(nq)[:, None, None]
    si = jnp.arange(table.shape[2])[None, None, :]
    codes = side.codes.astype(jnp.int32)[None, :, :]             # (1, B, S)
    vals = table[qi, probe[:, :, None], si, codes]               # (Q, B, S)
    return jnp.sum(vals, axis=-1), probe, ok


def build(points: jnp.ndarray, config: JunoConfig,
          key: jax.Array | None = None) -> JunoIndexData:
    """Offline phase (paper Alg. 1 adapted to the TPU layout)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    k_ivf, k_pq, k_cal = jax.random.split(key, 3)
    pts = jnp.asarray(points, jnp.float32)
    n, d = pts.shape
    s = d // config.sub_dim

    t_max = config.max_train_points if config.max_train_points > 0 else n
    ivf = build_ivf(pts, n_clusters=config.n_clusters,
                    n_iters=config.kmeans_iters, key=k_ivf,
                    capacity_mult=config.capacity_mult,
                    max_train_points=t_max)
    residuals = pts - ivf.centroids[ivf.labels]
    if n > t_max:  # subsampled PQ training: full-set Lloyd is O(N·E) per iter
        sub_idx = jax.random.choice(jax.random.fold_in(k_pq, 1), n,
                                    shape=(t_max,), replace=False)
        train_res = residuals[sub_idx]
    else:
        train_res = residuals
    codebook = train_codebook(train_res, n_entries=config.n_entries,
                              m=config.sub_dim, n_iters=config.kmeans_iters,
                              key=k_pq)
    codes = encode(residuals, codebook)                          # (N, S)
    # Padded per-cluster codes: pad slots read code 0 but are masked by valid.
    safe_ids = jnp.maximum(ivf.point_ids, 0)
    cluster_codes = codes[safe_ids]                              # (C, P, S)

    dens_model = _calibrate_density(pts, residuals, codebook, codes, ivf,
                                    config, k_cal)
    return JunoIndexData(ivf=ivf, codebook=codebook, codes=codes,
                         cluster_codes=cluster_codes, density=dens_model,
                         points_sq=jnp.sum(pts * pts, axis=-1))


def _calib_query_subspaces(queries, ivf, config):
    """Query-side subspace projections in the geometry the mask uses.

    For l2 the projection is the probe-0 residual (DESIGN.md §2); for ip
    the raw query. Returns (Qs, S, M) f32. Shared by the in-memory
    calibration below and the streaming pipeline (``repro.build``).
    """
    _, c1 = filter_clusters(queries, ivf, nprobe=1, metric=config.metric)
    if config.metric == "l2":
        qres = queries - ivf.centroids[c1[:, 0]]
        return split_subspaces(qres, config.sub_dim)             # (Qs, S, M)
    return split_subspaces(queries, config.sub_dim)


def _calib_tau_needed(qsub, gt_codes, codebook, metric):
    """Per-subspace threshold containing every top-k entry (paper §4.1).

    qsub (Qs, S, M), gt_codes (Qs, K, S) int32 — the PQ codes of each
    calibration query's exact top-k. Returns (Qs, S) f32: the transformed
    distance from the query's subspace projection that covers all K
    ground-truth entries. Shared by :func:`build` and ``repro.build``.
    """
    ent = codebook.entries                                       # (S, E, M)
    s_idx = jnp.arange(ent.shape[0])[None, None, :]
    gt_entries = ent[s_idx, gt_codes]                            # (Qs, K, S, M)
    if metric == "l2":
        diff = gt_entries - qsub[:, None, :, :]
        t = jnp.sum(diff * diff, axis=-1)                        # (Qs, K, S)
        return jnp.sqrt(jnp.max(t, axis=1))                      # (Qs, S)
    e_sq = jnp.sum(gt_entries * gt_entries, -1)
    dot = jnp.sum(gt_entries * qsub[:, None], -1)
    t = e_sq - 2.0 * dot
    return jnp.sqrt(jnp.maximum(jnp.max(t, axis=1), 0.0))


def _calibrate_density(pts, residuals, codebook, codes, ivf, config, key):
    """Fit density → threshold polynomial from ground-truth top-k (paper §4.1)."""
    n = pts.shape[0]
    nq = min(config.calib_queries, n)
    k_choice, k_noise = jax.random.split(key)
    qidx = jax.random.choice(k_choice, n, shape=(nq,), replace=False)
    # perturb so calibration queries are not exact database points
    noise = 0.01 * jax.random.normal(k_noise, (nq, pts.shape[1])) * jnp.std(pts)
    queries = pts[qidx] + noise.astype(jnp.float32)

    _, gt_ids = exact_topk(queries, pts, k=config.calib_topk,
                           metric=config.metric, chunk=min(65536, n))
    qsub = _calib_query_subspaces(queries, ivf, config)
    gt_codes = codes[gt_ids].astype(jnp.int32)                   # (Qs, K, S)
    tau_needed = _calib_tau_needed(qsub, gt_codes, codebook, config.metric)

    sub_pts = jnp.swapaxes(split_subspaces(residuals, config.sub_dim), 0, 1)
    return density_lib.calibrate(sub_pts, codebook.entries, qsub, tau_needed,
                                 grid_size=config.grid_size,
                                 degree=config.poly_degree)


def _rt_probe_mask(rt_grid, q, tau, cids, rt_scale, rt_offset, impl):
    """Stage-1 spatial pruning: which probed clusters survive the RT test.

    Runs the sphere-intersection filter (``repro.rt``) with the radius
    derived from the probe-0 row of the thresholds ``tau`` the caller
    already computed, and gathers the (Q, C) survivor mask at the probed
    cluster ids (offset by ``rt_offset`` on a shard — the grid is global).
    Probe 0 is always kept (nearest-probe backstop), so a query whose
    sphere misses everything still degrades to a nprobe=1 search instead
    of returning sentinels. ``impl`` picks the sphere-test path
    (``kernels.ops.rt_sphere_hits``). Returns (Q, nprobe) bool.
    """
    from repro import rt as rt_lib
    radius = rt_lib.query_radius(rt_grid, tau[:, 0, :], rt_scale)
    hits = rt_lib.survivor_mask(rt_grid, q, radius,          # (Q, C_global)
                                impl=impl)
    gcids = cids if rt_offset is None else cids + rt_offset
    probe_ok = jnp.take_along_axis(hits, gcids, axis=1) > 0
    return probe_ok.at[:, 0].set(True)


def _resolve_impl(impl: str | None) -> str:
    """The search path for ``impl``; ``None`` follows the platform
    (``kernels.ops.default_impl``: "pallas" on a TPU, "ref" elsewhere).
    Called once, by the shared scoring tails every entry point reaches."""
    if impl is None:
        from repro.kernels.ops import default_impl
        return default_impl()
    return impl


def _score_probed(index: JunoIndexData, q: jnp.ndarray, base: jnp.ndarray,
                  cids: jnp.ndarray, codes: jnp.ndarray, valid: jnp.ndarray,
                  ids: jnp.ndarray, *, k: int, mode: str, metric: str,
                  thres_scale: float, impl: str | None = None,
                  side: SideBuffer | None = None, prefilter: str = "scan",
                  rt_grid=None, rt_scale: float = 1.0, rt_offset=None):
    """Stages B+C over an explicitly gathered probe set.

    The tail of :func:`_search_batch` with the stage-A cluster filter and
    the per-probe gathers hoisted out: ``base``/``cids`` (Q, np) come from
    :func:`~repro.core.ivf.filter_clusters`, and ``codes`` (Q, np, P, S) /
    ``valid`` (Q, np, P) / ``ids`` (Q, np, P) are the probed rows of
    ``cluster_codes`` / ``ivf.valid`` / ``ivf.point_ids`` — however the
    caller obtained them. The resident path gathers them on device;
    the paged backend (``repro.serve.paged``) gathers codes on the host
    through its cluster cache and feeds them in, so both paths share this
    scoring math verbatim. Only ``index.codebook`` and ``index.density``
    are read from ``index``. ``impl=None`` follows the platform
    (:func:`_resolve_impl`). Returns (scores (Q, k), ids (Q, k)).
    """
    impl = _resolve_impl(impl)
    nq = q.shape[0]
    nprobe = cids.shape[1]
    m = index.codebook.sub_dim

    # --- stage B: selective LUT construction (the RT-core stage) ---------
    if metric == "l2":
        res = q[:, None, :] - index.ivf.centroids[cids]          # (Q, np, D)
        qsub = res.reshape(nq, nprobe, -1, m)                    # (Q, np, S, M)
        probe_base = jnp.zeros((nq, nprobe), jnp.float32)
    else:
        qsub = jnp.broadcast_to(
            q.reshape(nq, 1, -1, m), (nq, nprobe, q.shape[1] // m, m))
        probe_base = base                                        # <q, c_probe>
    tau = density_lib.predict_threshold(index.density, qsub, thres_scale)

    # --- stage C: distance calculation over the selected clusters --------
    if prefilter == "rt":
        probe_ok = _rt_probe_mask(rt_grid, q, tau, cids, rt_scale,
                                  rt_offset, impl)
        valid = valid & probe_ok[..., None]

    if impl == "pallas":
        from repro.kernels import ops as kops
        mlut, table = kops.build_selective_lut(
            qsub, index.codebook.entries, index.codebook.entry_sq, tau,
            metric=metric)
        if mode == "H":
            pt_scores = kops.masked_adc_scan(mlut, codes, valid,
                                             metric=metric)
            if metric == "ip":
                pt_scores = pt_scores + probe_base[..., None]
            higher_better = metric == "ip"
        else:
            if mode == "L":  # plain count: clip penalty/inner to {0, 1}
                table = (table >= 0).astype(jnp.int8)
            pt_scores = kops.hit_count_scan(table, codes, valid
                                            ).astype(jnp.float32)
            higher_better = True
    elif mode == "H":
        lut, mask = lut_lib.build_lut(qsub, index.codebook, tau, metric=metric)
        mlut = lut_lib.masked_lut(lut, mask, tau, metric=metric)
        scan = jax.vmap(jax.vmap(
            lambda l, c, v: scan_lib.adc_scan(l, c, v, metric=metric)))
        pt_scores = scan(mlut, codes, valid)                     # (Q, np, P)
        if metric == "ip":
            pt_scores = pt_scores + probe_base[..., None]
        higher_better = metric == "ip"
    else:
        lut, mask = lut_lib.build_lut(qsub, index.codebook, tau, metric=metric)
        hc_mode = "count" if mode == "L" else "reward_penalty"
        if metric == "l2":
            table = lut_lib.hit_tables(lut, mask, tau, mode=hc_mode,
                                       metric="l2")
        else:
            table = lut_lib.hit_tables_ip(lut, index.codebook.entry_sq, tau,
                                          mode=hc_mode)
        scan = jax.vmap(jax.vmap(scan_lib.hit_count_scan))
        pt_scores = scan(table, codes, valid).astype(jnp.float32)
        higher_better = True

    flat_scores = pt_scores.reshape(nq, -1)
    flat_ids = ids.reshape(nq, -1)
    if side is not None:
        # overflow inserts: same per-probe table, same gather+sum, same
        # invalid sentinel — only reachable when the owning cluster is probed
        # AND (under prefilter="rt") only when that probe survives the
        # sphere test, exactly like its in-cluster siblings
        if mode == "H":
            tot, probe, ok = _side_gather(mlut, cids, side)
            if prefilter == "rt":
                ok = ok & jnp.take_along_axis(probe_ok, probe, axis=1)
            if metric == "ip":
                tot = tot + jnp.take_along_axis(probe_base, probe, axis=1)
            side_scores = jnp.where(ok, tot,
                                    -jnp.inf if higher_better else jnp.inf)
        else:
            tot, probe, ok = _side_gather(table.astype(jnp.int32), cids, side)
            if prefilter == "rt":
                ok = ok & jnp.take_along_axis(probe_ok, probe, axis=1)
            side_scores = jnp.where(ok, tot, jnp.int32(-(2 ** 30))
                                    ).astype(jnp.float32)
        flat_scores = jnp.concatenate([flat_scores, side_scores], axis=1)
        flat_ids = jnp.concatenate(
            [flat_ids, jnp.broadcast_to(side.ids[None], (nq, side.capacity))],
            axis=1)
    sel_scores, sel = jax.lax.top_k(
        flat_scores if higher_better else -flat_scores, k)
    out_ids = jnp.take_along_axis(flat_ids, sel, axis=1)
    out_scores = sel_scores if higher_better else -sel_scores
    return out_scores, out_ids


@functools.partial(jax.jit,
                   static_argnames=("nprobe", "k", "mode", "metric", "impl",
                                    "prefilter"))
def _search_batch(index: JunoIndexData, queries: jnp.ndarray, *, nprobe: int,
                  k: int, mode: str, metric: str, thres_scale: float,
                  impl: str | None = None, side: SideBuffer | None = None,
                  prefilter: str = "scan", rt_grid=None,
                  rt_scale: float = 1.0, rt_offset=None):
    """One jitted query batch. Returns (scores (Q,k), ids (Q,k)).

    impl="ref"    — pure-jnp reference path (semantics of record)
    impl="pallas" — fused Pallas kernels (TPU path; interpret=True on CPU)
    impl=None     — follows the platform (:func:`_resolve_impl`)
    side          — optional overflow buffer of online inserts, merged into
                    the final top-k with in-cluster-identical scoring.
    prefilter     — "scan" (dense, every probed cluster scanned) or "rt"
                    (RT-core-style sphere-intersection pruning: probes
                    whose cluster disc the query sphere misses are masked
                    out of the scans; needs ``rt_grid``, see ``repro.rt``).
    """
    q = queries.astype(jnp.float32)

    # --- stage A: filtering (MXU GEMM + top-k), paper Fig. 1 bottom-left ---
    base, cids = filter_clusters(q, index.ivf, nprobe=nprobe, metric=metric)
    codes = index.cluster_codes[cids]                            # (Q, np, P, S)
    valid = index.ivf.valid[cids]                                # (Q, np, P)
    ids = index.ivf.point_ids[cids]                              # (Q, np, P)
    return _score_probed(index, q, base, cids, codes, valid, ids, k=k,
                         mode=mode, metric=metric, thres_scale=thres_scale,
                         impl=impl, side=side, prefilter=prefilter,
                         rt_grid=rt_grid, rt_scale=rt_scale,
                         rt_offset=rt_offset)


def _score_probed_two_stage(index: JunoIndexData, q: jnp.ndarray,
                            base: jnp.ndarray, cids: jnp.ndarray,
                            codes: jnp.ndarray, valid: jnp.ndarray,
                            ids: jnp.ndarray, *, k: int, metric: str,
                            thres_scale: float, rerank: int = 0,
                            impl: str | None = None, fused: bool = False,
                            fused3: bool | None = None,
                            side: SideBuffer | None = None,
                            prefilter: str = "scan", rt_grid=None,
                            rt_scale: float = 1.0, rt_offset=None):
    """Mode "H2": int8 hit-count prefilter → exact ADC on top-C survivors.

    Beyond-paper: converts JUNO's dynamic skip into a static-shape candidate
    set so the expensive f32 gather/accumulate runs on C = rerank points
    instead of nprobe·P (see module docstring).

    ``fused=True`` routes both stages through
    ``kernels.ops.fused_two_stage_scan`` — one kernel computes the hit
    counts, applies the survivor threshold in-kernel and emits the compacted
    top-C candidates WITH their masked-ADC distances, so this function does
    no wide top-k and no separate rerank gather. Candidate selection is the
    same top-C-by-count rule, so fused and composed return identical ids
    (tests/test_impl_parity.py). ``impl`` picks who builds the LUT/hit
    tables and, on a TPU, whether the fused scan runs its Pallas kernels
    ("pallas") or the jnp host path ("ref"); ``None`` follows the platform
    (:func:`_resolve_impl`).

    When ``fused=True`` meets ``prefilter="rt"``, the RT sphere test ALSO
    folds in — ``kernels.ops.fused_three_stage_scan`` runs the sphere
    walk, the hit-count prefilter and the masked ADC in one residency, and
    its ``probe_ok`` output replaces the separate :func:`_rt_probe_mask`
    round trip (bit-identical by construction; the kernel gathers the same
    ``slot_of`` verdicts in-register). ``fused3=False`` forces the
    composed rt+fused path (parity baseline); ``None``/``True`` take the
    three-stage kernel whenever it applies.

    Like :func:`_score_probed`, this is the post-gather tail of
    :func:`_search_batch_two_stage`: ``base``/``cids``/``codes``/``valid``/
    ``ids`` arrive pre-gathered so the resident and paged
    (``repro.serve.paged``) backends share the scoring math verbatim.
    """
    impl = _resolve_impl(impl)
    nq = q.shape[0]
    nprobe = cids.shape[1]
    m = index.codebook.sub_dim
    c_budget = rerank or 4 * k

    if metric == "l2":
        res = q[:, None, :] - index.ivf.centroids[cids]
        qsub = res.reshape(nq, nprobe, -1, m)
        probe_base = jnp.zeros((nq, nprobe), jnp.float32)
    else:
        qsub = jnp.broadcast_to(
            q.reshape(nq, 1, -1, m), (nq, nprobe, q.shape[1] // m, m))
        probe_base = base
    tau = density_lib.predict_threshold(index.density, qsub, thres_scale)

    use_fused3 = fused and prefilter == "rt" and fused3 is not False
    if prefilter == "rt" and not use_fused3:
        probe_ok = _rt_probe_mask(rt_grid, q, tau, cids, rt_scale,
                                  rt_offset, impl)
        valid = valid & probe_ok[..., None]

    from repro.kernels import ops as kops
    if impl == "pallas":
        mlut, table = kops.build_selective_lut(
            qsub, index.codebook.entries, index.codebook.entry_sq, tau,
            metric=metric)
    else:
        lut, mask = lut_lib.build_lut(qsub, index.codebook, tau,
                                      metric=metric)
        mlut = lut_lib.masked_lut(lut, mask, tau, metric=metric)
        if metric == "l2":
            table = lut_lib.hit_tables(lut, mask, tau, mode="reward_penalty",
                                       metric="l2")
        else:
            table = lut_lib.hit_tables_ip(lut, index.codebook.entry_sq, tau,
                                          mode="reward_penalty")

    p = codes.shape[2]
    cap = min(c_budget, nprobe * p)
    if use_fused3:
        # all three stages in the fused scan: the counts kernel runs the
        # sphere test of each probed cluster, masks the probes in-register
        # (same slot_of verdicts _rt_probe_mask would gather, probe 0
        # backstopped), then counts, thresholds and compacts as the fused
        # two-stage scan does — no HBM hit table, no host mask round trip
        from repro import rt as rt_lib
        radius = rt_lib.query_radius(rt_grid, tau[:, 0, :], rt_scale)
        qp2 = q @ rt_grid.proj                                   # (Q, 2)
        gcids = cids if rt_offset is None else cids + rt_offset
        slot_idx = jnp.take(rt_grid.slot_of, gcids)              # (Q, np)
        _, _, cand, exact, probe_ok = kops.fused_three_stage_scan(
            mlut, table, codes, valid, qp2[:, 0], qp2[:, 1], radius,
            rt_grid.cell_c0, rt_grid.cell_c1, rt_grid.slot_reach, slot_idx,
            cap_c=cap, metric=metric, impl=impl)
        valid = valid & probe_ok[..., None]
        cand_probe = cand // p                                   # (Q, C)
        cand_valid = jnp.take_along_axis(valid.reshape(nq, -1), cand, axis=1)
        cand_ids = jnp.take_along_axis(ids.reshape(nq, -1), cand, axis=1)
    elif fused:
        # both stages in one fused scan: counts, survivor threshold,
        # compacted top-C candidates + their ADC totals
        _, _, cand, exact = kops.fused_two_stage_scan(
            mlut, table, codes, valid, cap_c=cap, metric=metric, impl=impl)
        cand_probe = cand // p                                   # (Q, C)
        cand_valid = jnp.take_along_axis(valid.reshape(nq, -1), cand, axis=1)
        cand_ids = jnp.take_along_axis(ids.reshape(nq, -1), cand, axis=1)
    else:
        if impl == "pallas":
            counts = kops.hit_count_scan(table, codes, valid)
        else:
            counts = jax.vmap(jax.vmap(scan_lib.hit_count_scan))(table, codes,
                                                                 valid)

        # stage 1: top-C candidates by hit count (int32, cheap)
        flat_counts = counts.reshape(nq, -1)
        _, cand = jax.lax.top_k(flat_counts, cap)
        cand_probe = cand // p                                   # (Q, C)

        # stage 2: exact ADC only on survivors
        cand_codes = jnp.take_along_axis(
            codes.reshape(nq, -1, codes.shape[-1]), cand[..., None], axis=1)
        s_idx = jnp.arange(mlut.shape[2])[None, None, :]
        vals = mlut[jnp.arange(nq)[:, None, None], cand_probe[..., None],
                    s_idx, cand_codes.astype(jnp.int32)]         # (Q, C, S)
        exact = jnp.sum(vals, axis=-1)
        cand_valid = jnp.take_along_axis(valid.reshape(nq, -1), cand, axis=1)
        cand_ids = jnp.take_along_axis(ids.reshape(nq, -1), cand, axis=1)
    if metric == "ip":
        exact = exact + jnp.take_along_axis(probe_base, cand_probe, axis=1)
    if side is not None:
        # side points bypass stage 1 (the buffer is tiny) and join the exact
        # rerank pool directly, scored identically to in-cluster survivors —
        # including (under prefilter="rt") the probe's sphere-test verdict
        tot, probe, ok = _side_gather(mlut, cids, side)
        if prefilter == "rt":
            ok = ok & jnp.take_along_axis(probe_ok, probe, axis=1)
        if metric == "ip":
            tot = tot + jnp.take_along_axis(probe_base, probe, axis=1)
        exact = jnp.concatenate(
            [exact, jnp.where(ok, tot, -jnp.inf if metric == "ip" else jnp.inf)],
            axis=1)
        cand_valid = jnp.concatenate([cand_valid, ok], axis=1)
        cand_ids = jnp.concatenate(
            [cand_ids, jnp.broadcast_to(side.ids[None], (nq, side.capacity))],
            axis=1)
    if metric == "ip":
        exact = jnp.where(cand_valid, exact, -jnp.inf)
        sel_s, sel = jax.lax.top_k(exact, k)
        out_scores = sel_s
    else:
        exact = jnp.where(cand_valid, exact, jnp.inf)
        sel_s, sel = jax.lax.top_k(-exact, k)
        out_scores = -sel_s
    out_ids = jnp.take_along_axis(cand_ids, sel, axis=1)
    return out_scores, out_ids


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "metric", "impl",
                                             "rerank", "fused", "fused3",
                                             "prefilter"))
def _search_batch_two_stage(index: JunoIndexData, queries: jnp.ndarray, *,
                            nprobe: int, k: int, metric: str,
                            thres_scale: float, rerank: int = 0,
                            impl: str | None = None, fused: bool = False,
                            fused3: bool | None = None,
                            side: SideBuffer | None = None,
                            prefilter: str = "scan", rt_grid=None,
                            rt_scale: float = 1.0, rt_offset=None):
    """Mode "H2" entry point: stage-A filter + gathers, then the shared
    two-stage scoring tail (:func:`_score_probed_two_stage`). Returns
    (scores (Q, k), ids (Q, k)); see the tail's docstring for the fused
    and composed candidate-selection semantics.
    """
    q = queries.astype(jnp.float32)
    base, cids = filter_clusters(q, index.ivf, nprobe=nprobe, metric=metric)
    codes = index.cluster_codes[cids]                            # (Q,np,P,S)
    valid = index.ivf.valid[cids]
    ids = index.ivf.point_ids[cids]
    return _score_probed_two_stage(
        index, q, base, cids, codes, valid, ids, k=k, metric=metric,
        thres_scale=thres_scale, rerank=rerank, impl=impl, fused=fused,
        fused3=fused3, side=side, prefilter=prefilter, rt_grid=rt_grid,
        rt_scale=rt_scale, rt_offset=rt_offset)


def search(index: JunoIndexData, queries: jnp.ndarray, *, nprobe: int = 16,
           k: int = 100, mode: str = "H", metric: str = "l2",
           thres_scale: float = 1.0, batch: int = 64,
           impl: str | None = None,
           rerank: int = 0, fused: bool = False, fused3: bool | None = None,
           side: SideBuffer | None = None, prefilter: str = "scan",
           rt_grid=None, rt_scale: float = 1.0):
    """Search the index — the public online API (paper Alg. 2).

    Chunks queries through the jitted batch kernels, padding the last
    chunk with edge-replicated rows (in-distribution work whose results
    are sliced off).

    Parameters
    ----------
    index : JunoIndexData
        A built index (:func:`build`).
    queries : jnp.ndarray
        (Q, D) f32 query vectors.
    nprobe : int
        Clusters probed per query (stage-A budget).
    k : int
        Results per query.
    mode : str
        Operating point — "H" (exact selective distances), "M"
        (reward/penalty hit count), "L" (plain hit count) or "H2"
        (two-stage hit-count prefilter → exact rerank).
    metric : str
        "l2" | "ip".
    thres_scale : float
        Multiplier on the calibrated selectivity thresholds τ.
    batch : int
        Queries per jitted call (one compiled program per distinct batch).
    impl : str, optional
        "ref" (pure-jnp semantics of record) or "pallas" (TPU kernels;
        interpret mode off-TPU). ``None`` follows the platform
        (``kernels.ops.default_impl``): "pallas" on a TPU, "ref" elsewhere.
    rerank : int
        Mode "H2" stage-2 candidate budget C (0 → ``4 * k``).
    fused : bool
        Mode "H2" only: serve both stages through the fused
        hit-count→masked-ADC kernel path; top-k ids are identical to the
        composed path (see ``_search_batch_two_stage``). Combined with
        ``prefilter="rt"`` this dispatches the three-stage kernels
        (the probes' RT sphere test folded into the counts kernel) unless
        ``fused3=False``.
    fused3 : bool, optional
        Three-stage dispatch override. ``None`` (default) auto-selects it
        whenever ``fused=True`` and ``prefilter="rt"``; ``False`` forces
        the composed rt-mask + two-stage path (bit-identical results —
        this is the parity baseline); ``True`` additionally validates
        that the combination actually applies.
    side : SideBuffer, optional
        Overflow buffer of online inserts, merged into the final top-k
        with in-cluster-identical scoring.
    prefilter : str
        "scan" (default — every probed cluster is scanned) or "rt"
        (RT-core-style sphere-intersection pruning, ``repro.rt``: probes
        whose cluster disc the query sphere misses are masked out ahead
        of the hit-count / masked-ADC scans; at full-coverage radii the
        results are identical to "scan").
    rt_grid : repro.rt.CentroidGrid, optional
        The spatial index required by ``prefilter="rt"``
        (``rt.build_grid``).
    rt_scale : float
        Query-sphere radius knob for "rt" (monotone: larger ⇒ more
        survivors; very large values reproduce "scan" exactly).

    Returns
    -------
    tuple of jnp.ndarray
        ``(scores (Q, k) f32, ids (Q, k) int32)``; scores are distances
        (lower better) for l2 H/H2, similarities/counts (higher better)
        otherwise.
    """
    if fused and mode != "H2":
        raise ValueError(f"fused=True requires mode='H2', got mode={mode!r}")
    if prefilter not in ("scan", "rt"):
        raise ValueError(f"unknown prefilter {prefilter!r}")
    if prefilter == "rt" and rt_grid is None:
        raise ValueError("prefilter='rt' requires rt_grid (rt.build_grid)")
    if fused3 and not (fused and prefilter == "rt"):
        raise ValueError("fused3=True requires fused=True and "
                         "prefilter='rt' (the three-stage kernel folds the "
                         "RT walk into the fused scan)")
    rt_kw = dict(prefilter=prefilter, rt_grid=rt_grid, rt_scale=rt_scale)
    nq = queries.shape[0]
    out_s, out_i = [], []
    for i in range(0, nq, batch):
        qb = queries[i:i + batch]
        pad = batch - qb.shape[0]
        if pad:
            # replicate the last real query instead of zero-padding: a zero
            # row is out-of-distribution garbage work and, under metric="ip",
            # degenerate (every score 0) — edge rows are real queries whose
            # results we slice off anyway.
            qb = jnp.pad(qb, ((0, pad), (0, 0)), mode="edge")
        if mode == "H2":
            s, ids = _search_batch_two_stage(
                index, qb, nprobe=nprobe, k=k, metric=metric,
                thres_scale=thres_scale, rerank=rerank, impl=impl,
                fused=fused, fused3=fused3, side=side, **rt_kw)
        else:
            s, ids = _search_batch(index, qb, nprobe=nprobe, k=k, mode=mode,
                                   metric=metric, thres_scale=thres_scale,
                                   impl=impl, side=side, **rt_kw)
        out_s.append(s[:batch - pad])
        out_i.append(ids[:batch - pad])
    return jnp.concatenate(out_s), jnp.concatenate(out_i)


@jax.jit
def _label_encode(pts: jnp.ndarray, centroids: jnp.ndarray,
                  codebook: PQCodebook) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Insert-time (labels, codes) for a small batch, fully under one jit.

    ``kmeans.assign`` is an eager ``lax.map`` pipeline tuned for N≫chunk
    offline builds; per-insert it would pay ~50ms of retracing for a
    microseconds-sized matmul. Insert batches are small, so the dense
    distance matrix is the right shape here.
    """
    d = (jnp.sum(centroids * centroids, -1)[None, :]
         - 2.0 * pts @ centroids.T)
    labels = jnp.argmin(d, axis=-1).astype(jnp.int32)
    return labels, encode(pts - centroids[labels], codebook)




class MutableIndexBase:
    """Host-side slot bookkeeping shared by the single-device and sharded
    mutable indices (`MutableJunoIndex`, `dist.DistributedMutableIndex`).

    The control plane is identical in both: per-cluster free-slot lists, an
    id → (cluster, slot) map (cluster −1 = side-buffer position), and a
    plan-then-commit discipline so a failing ``insert``/``delete`` raises
    BEFORE any state — host or device — has been touched. Subclasses supply
    the data plane via ``_labels_codes`` (insert-time encoding) and
    ``_apply_insert``/``_apply_delete`` (device scatters).
    """

    side: SideBuffer

    def _init_bookkeeping(self, ivf_valid, point_ids, *, side_capacity: int,
                          first_new_id: int, n_subspaces: int) -> None:
        valid = np.asarray(ivf_valid)
        pids = np.asarray(point_ids)
        n_clusters = valid.shape[0]
        self.side = empty_side_buffer(side_capacity, n_subspaces)
        self._free = [list(np.where(~valid[c])[0][::-1])
                      for c in range(n_clusters)]
        #: id -> (cluster, slot); cluster == -1 means side-buffer position
        self._loc: dict[int, tuple[int, int]] = {}
        for c in range(n_clusters):
            for slot in np.where(valid[c])[0]:
                self._loc[int(pids[c, slot])] = (c, int(slot))
        self._side_free = list(range(side_capacity))[::-1]
        self._next_id = first_new_id
        # LSM delta tiers (repro.core.freshness). A swap_data rederives the
        # base bookkeeping but keeps the tier CONFIGURATION (a rebuild
        # already folded the old tiers' points into the new base); the
        # generation counter and rt mutation counter are monotone across
        # swaps so stale cached views/budgets can never alias a new
        # generation's state.
        self._minors: list = []
        self._max_minors: int = getattr(self, "_max_minors", 0)
        self._minor_gen: int = getattr(self, "_minor_gen", 0)
        self._delta_epoch: int = getattr(self, "_delta_epoch", 0) + 1
        self._delta_cache: tuple[int, SideBuffer] | None = None
        self._rt_muts: int = getattr(self, "_rt_muts", -1) + 1

    # ---- data-plane hooks (subclass responsibility) ----------------------
    def _labels_codes(self, pts: jnp.ndarray):
        raise NotImplementedError

    def _rt_centroids(self) -> jnp.ndarray:
        """(C, D) replicated centroids for rt-grid maintenance."""
        raise NotImplementedError

    def _rt_on_insert(self, pts: jnp.ndarray, labels: np.ndarray) -> None:
        """Post-insert spatial-index maintenance (shared by subclasses).

        Called once per committed insert batch with the raw points and
        their owning clusters; when an ``repro.rt`` grid is attached
        (``self.rt_grid``), grows the touched clusters' projected reaches
        so the sphere filter never drops a cluster holding a fresh point.
        Always bumps :attr:`rt_mutations` (even gridless) so engine-side
        routing caches keyed on it can never serve a pre-insert probe
        budget to a post-insert index state.
        """
        self._rt_muts += 1
        if getattr(self, "rt_grid", None) is None:
            return
        from repro import rt as rt_lib
        res = (np.asarray(pts, np.float32)
               - np.asarray(self._rt_centroids())[labels])
        rp = res @ np.asarray(self.rt_grid.proj)
        self.rt_grid = rt_lib.update_radii(
            self.rt_grid, labels, np.sqrt(np.sum(rp * rp, axis=-1)))

    def _apply_insert(self, cl: list[int], sl: list[int], ids: np.ndarray,
                      codes: jnp.ndarray) -> None:
        raise NotImplementedError

    def _apply_delete(self, cl: list[int], sl: list[int]) -> None:
        raise NotImplementedError

    # ---- introspection ---------------------------------------------------
    @property
    def n_live(self) -> int:
        """Number of live (non-tombstoned) points in the index."""
        return len(self._loc)

    @property
    def side_fill(self) -> int:
        """Number of occupied side-buffer slots."""
        return self.side.capacity - len(self._side_free)

    def free_slots(self, cluster: int) -> int:
        """Free padded slots remaining in ``cluster``."""
        return len(self._free[cluster])

    @property
    def rt_mutations(self) -> int:
        """Monotone counter of rt-relevant mutations (insert batches and
        generation swaps); engine routing caches key on it to invalidate
        stale probe budgets."""
        return self._rt_muts

    # ---- LSM delta tiers (repro.core.freshness) --------------------------
    def enable_tiers(self, max_minors: int, *, minor_store=None,
                     minor_name: str = "minors") -> None:
        """Turn on the LSM freshness tiers (see ``repro.core.freshness``).

        With ``max_minors > 0`` a full L0 side buffer no longer makes
        ``insert`` raise: it is promoted into a sealed, PQ-encoded minor
        generation (up to ``max_minors`` of them) that a
        ``MergeScheduler`` folds back into the base incrementally.

        Parameters
        ----------
        max_minors : int
            Maximum concurrent minor generations (0 disables tiering —
            the legacy single-SideBuffer behavior).
        minor_store : repro.build.store.ArtifactStore, optional
            When given, promoted generations are committed through the
            store and demand-paged back on first search touch with
            per-row sha256 verification (the paged tier's contract).
        minor_name : str
            Artifact name minors are committed under.
        """
        self._max_minors = int(max_minors)
        if minor_store is not None:
            self._minor_sink = (minor_store, minor_name)
        self._delta_cache = None
        self._delta_epoch += 1

    @property
    def delta_fill(self) -> int:
        """Live points across all delta tiers (L0 + minor generations)."""
        return self.side_fill + sum(m.live for m in self._minors)

    def delta_view(self, *, elide_empty: bool = True):
        """The delta tiers as ONE fixed-capacity :class:`SideBuffer`.

        With tiering disabled this is exactly the legacy side buffer
        (None when empty, so the no-spill hot path keeps its
        side-elided jit signature). With tiering enabled, L0 and every
        minor generation are concatenated — padded to the constant
        capacity ``B * (1 + max_minors)`` so merge cycles never change
        the jitted search signature — and cached until the next tier
        mutation.

        Parameters
        ----------
        elide_empty : bool
            Return None when every tier is empty (default). The sharded
            serve path passes False: its compiled dispatch always takes
            a side argument.

        Returns
        -------
        SideBuffer or None
            The combined delta view.
        """
        if self._max_minors <= 0:
            if elide_empty and self.side_fill == 0:
                return None
            return self.side
        if elide_empty and self.side_fill == 0 and not self._minors:
            return None
        if (self._delta_cache is None
                or self._delta_cache[0] != self._delta_epoch):
            from .freshness import combined_delta
            self._delta_cache = (
                self._delta_epoch,
                combined_delta(self.side, self._minors, self._max_minors))
        return self._delta_cache[1]

    def delta_snapshot(self) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                      np.ndarray]:
        """Host-side ``(valid, cluster, ids, codes)`` over L0 + minors.

        The unpadded concatenation of every delta tier's slots, used by
        ``build.rebuild.live_points`` so an offline rebuild folds minor
        generations in exactly like side-buffer points.
        """
        valid = [np.asarray(self.side.valid)]
        cluster = [np.asarray(self.side.cluster)]
        ids = [np.asarray(self.side.ids)]
        codes = [np.asarray(self.side.codes)]
        for m in self._minors:
            valid.append(m.valid)
            cluster.append(m.cluster)
            ids.append(m.ids)
            codes.append(np.asarray(m.materialize()))
        return (np.concatenate(valid), np.concatenate(cluster),
                np.concatenate(ids), np.concatenate(codes))

    # ---- mutation --------------------------------------------------------
    def _placement_fits(self, labels: np.ndarray, side_slots: int) -> bool:
        """Whether a batch with these owning clusters is placeable, given
        ``side_slots`` free L0 positions. Pure feasibility check — reads
        the free lists, mutates nothing."""
        cs, counts = np.unique(labels, return_counts=True)
        spill = sum(max(0, int(n) - len(self._free[int(c)]))
                    for c, n in zip(cs, counts))
        return spill <= side_slots

    def insert(self, points) -> list[int]:
        """Insert a (B, D) batch; returns the assigned global ids.

        Raises RuntimeError (before mutating anything) if the batch cannot
        be placed — i.e. some owning cluster is full AND the delta tier
        cannot absorb the remainder; call ``compact()`` or build with a
        larger ``side_capacity``. With the LSM tiers enabled
        (:meth:`enable_tiers`) a full L0 is first promoted into a minor
        generation — but only when the batch provably fits afterwards, so
        a failing insert still mutates nothing. The commit itself is
        device-plane-first: every device update is a functional replace,
        so a failing subclass scatter (device OOM, a sealed paged shard)
        also leaves ALL state untouched; the infallible host bookkeeping
        runs last.
        """
        pts = jnp.atleast_2d(jnp.asarray(points, jnp.float32))
        labels, codes = self._labels_codes(pts)                  # (B,), (B, S)
        labels = np.asarray(labels)

        # feasibility first (no mutation). When the batch overflows, an
        # L0→minor promotion may free the whole side buffer — taken only
        # when the retry provably fits, keeping insert all-or-nothing.
        if not self._placement_fits(labels, len(self._side_free)):
            if (self._max_minors > 0 and self.side_fill > 0
                    and len(self._minors) < self._max_minors
                    and self._placement_fits(labels, self.side.capacity)):
                from .freshness import promote_l0
                promote_l0(self)
            else:
                raise RuntimeError(
                    "insert batch does not fit: cluster padding and side "
                    "buffer exhausted — call compact() or raise side_capacity")

        # plan (no mutation yet) — per-cluster free slots, then side buffer
        taken: dict[int, int] = {}
        side_need = 0
        placements: list[tuple[int, int]] = []   # (cluster, slot) | (-1, pos)
        for c in labels:
            c = int(c)
            used = taken.get(c, 0)
            if used < len(self._free[c]):
                # plan reads slots from the free lists' tails in order, so
                # the commit below can drop the tails in O(1)
                placements.append((c, self._free[c][-1 - used]))
                taken[c] = used + 1
            elif side_need < len(self._side_free):
                placements.append((-1, self._side_free[-1 - side_need]))
                side_need += 1
            else:                                # unreachable after _fits
                raise RuntimeError(
                    "insert batch does not fit: cluster padding and side "
                    "buffer exhausted — call compact() or raise side_capacity")

        new_ids = list(range(self._next_id, self._next_id + pts.shape[0]))
        ids_np = np.asarray(new_ids, np.int32)
        cl, sl, sel, s_pos, s_sel = [], [], [], [], []
        for i, (c, slot) in enumerate(placements):
            if c >= 0:
                cl.append(c)
                sl.append(slot)
                sel.append(i)
            else:
                s_pos.append(slot)
                s_sel.append(i)

        # commit: fallible device planes first, as functional replaces …
        new_side = None
        if s_pos:
            pos_j, sel_j = jnp.asarray(s_pos), jnp.asarray(s_sel)
            new_side = self.side._replace(
                codes=self.side.codes.at[pos_j].set(codes[sel_j]),
                cluster=self.side.cluster.at[pos_j].set(
                    jnp.asarray(labels[s_sel], jnp.int32)),
                ids=self.side.ids.at[pos_j].set(jnp.asarray(ids_np[s_sel])),
                valid=self.side.valid.at[pos_j].set(True))
        if cl:
            self._apply_insert(cl, sl, ids_np[sel], codes[jnp.asarray(sel)])
        # … then the infallible host bookkeeping
        if new_side is not None:
            self.side = new_side
        for c, cnt in taken.items():
            del self._free[c][-cnt:]
        if side_need:
            del self._side_free[-side_need:]
        for i, (c, slot) in enumerate(placements):
            self._loc[new_ids[i]] = (c, slot) if c >= 0 else (-1, slot)
        self._next_id += pts.shape[0]
        if s_pos:
            self._delta_epoch += 1
        self._rt_on_insert(pts, labels)
        return new_ids

    def delete(self, ids) -> int:
        """Tombstone points by global id. Freed cluster slots become insert
        targets; no data movement. An unknown/already-deleted/duplicated id
        raises KeyError BEFORE any state is touched (all-or-nothing).
        Points living in a minor generation (cluster code ≤ −2 in the
        location map) are tombstoned in that generation's host valid mask;
        a generation emptied this way is dropped."""
        pids = [int(p) for p in np.atleast_1d(np.asarray(ids, np.int64))]
        if len(set(pids)) != len(pids):
            raise KeyError(f"duplicate ids in delete batch: {pids}")
        locs = [self._loc[p] for p in pids]      # KeyError = unknown id
        cl, sl, s_pos = [], [], []
        m_pos: dict[int, list[int]] = {}         # minor gen -> positions
        for c, slot in locs:
            if c >= 0:
                cl.append(c)
                sl.append(slot)
            elif c == -1:
                s_pos.append(slot)
            else:
                m_pos.setdefault(-2 - c, []).append(slot)
        # fallible device planes first (functional replaces) …
        if cl:
            self._apply_delete(cl, sl)
        if s_pos:
            self.side = self.side._replace(
                valid=self.side.valid.at[jnp.asarray(s_pos)].set(False))
        # … then the infallible host bookkeeping
        if m_pos:
            by_gen = {m.gen: m for m in self._minors}
            for g, poss in m_pos.items():
                by_gen[g].valid[np.asarray(poss)] = False
            self._minors = [m for m in self._minors if m.live]
        for pid in pids:
            del self._loc[pid]
        for c, slot in locs:
            if c >= 0:
                self._free[c].append(slot)
            elif c == -1:
                self._side_free.append(slot)
        if s_pos or m_pos:
            self._delta_epoch += 1
        return len(pids)

    def compact(self) -> int:
        """Fold side-buffer points into freed slots of their owning cluster.
        Returns how many points moved; points whose cluster is still full
        stay in the buffer. Search results are unchanged (same scoring).

        The plan is built vectorized (one stable argsort groups side
        positions by owning cluster; each cluster donates its free-list
        tail) and validated BEFORE anything mutates: a duplicated free
        slot (double-free corruption) or a fold targeting a position
        already back on ``_side_free`` (reused-slot aliasing) raises
        RuntimeError with all state — host and device — untouched,
        instead of silently overwriting a live slot. Commit ordering is
        device-first / host-last, like ``insert``.
        """
        side_valid = np.asarray(self.side.valid)
        side_cluster = np.asarray(self.side.cluster)
        side_ids = np.asarray(self.side.ids)
        pos_all = np.where(side_valid)[0]
        if pos_all.size == 0:
            return 0
        order = np.argsort(side_cluster[pos_all], kind="stable")
        pos_sorted = pos_all[order]
        cs, starts, counts = np.unique(side_cluster[pos_sorted],
                                       return_index=True, return_counts=True)
        cl: list[int] = []
        sl: list[int] = []
        pos_l: list[int] = []
        plan: list[tuple[int, int]] = []         # (cluster, take)
        for c, st, n in zip(cs, starts, counts):
            c = int(c)
            take = min(int(n), len(self._free[c]))
            if not take:
                continue
            slots = self._free[c][-take:][::-1]
            cl += [c] * take
            sl += [int(s) for s in slots]
            pos_l += [int(p) for p in pos_sorted[st:st + take]]
            plan.append((c, take))
        if not pos_l:
            return 0
        # fail-closed plan validation (before ANY mutation)
        if len(set(zip(cl, sl))) != len(sl):
            raise RuntimeError(
                "compact plan references a cluster slot twice (corrupted "
                "free list / double-free); refusing to fold")
        if set(pos_l) & set(self._side_free):
            raise RuntimeError(
                "compact plan folds a side position already on the free "
                "list (reused-slot aliasing); refusing to fold")
        # fallible device planes first …
        pos_j = jnp.asarray(pos_l)
        self._apply_insert(cl, sl, side_ids[pos_l].astype(np.int32),
                           self.side.codes[pos_j])
        self.side = self.side._replace(
            valid=self.side.valid.at[pos_j].set(False))
        # … then the infallible host bookkeeping
        for c, take in plan:
            del self._free[c][-take:]
        for c, slot, pos in zip(cl, sl, pos_l):
            self._loc[int(side_ids[pos])] = (c, slot)
        self._side_free.extend(pos_l)
        self._delta_epoch += 1
        return len(pos_l)


class MutableJunoIndex(MutableIndexBase):
    """Online-mutable wrapper over a built :class:`JunoIndexData`.

    ``insert`` encodes new points with the EXISTING codebooks (no
    retraining) and appends them into free padded slots of their owning
    cluster; when a cluster's padding is exhausted the point spills into a
    fixed-capacity :class:`SideBuffer`. ``delete`` tombstones points via the
    ``valid`` mask. Neither touches the search hot path's shapes, so all
    jitted search signatures stay warm. ``compact()`` folds side-buffer
    points back into cluster slots freed by deletes — a search no-op by
    construction (side points are scored with the identical gather an
    in-cluster point gets).

    An optional :class:`repro.rt.CentroidGrid` rides along for
    ``search(prefilter="rt")`` (attach one, or let ``ensure_rt_grid``
    build it lazily); inserts keep it valid by growing the touched
    clusters' reaches — cell membership never changes because centroids
    never move — and deletes leave it alone (a stale larger reach only
    over-covers).
    """

    def __init__(self, data: JunoIndexData, *, side_capacity: int = 256,
                 rt_grid=None):
        self.data = data
        self.rt_grid = rt_grid
        self._init_bookkeeping(data.ivf.valid, data.ivf.point_ids,
                               side_capacity=side_capacity,
                               first_new_id=int(data.codes.shape[0]),
                               n_subspaces=int(data.codes.shape[1]))

    def _labels_codes(self, pts):
        return _label_encode(pts, self.data.ivf.centroids, self.data.codebook)

    # ---- hot swap --------------------------------------------------------
    def swap_data(self, new_data: JunoIndexData, *,
                  side_capacity: int | None = None) -> None:
        """Atomically install a rebuilt :class:`JunoIndexData`.

        The new index replaces the served one in a single assignment, the
        slot bookkeeping (free lists, id → location map) is rederived from
        its ``point_ids``/``valid`` arrays, the side buffer is reset to
        empty (a rebuild drains it into proper cluster slots — see
        ``repro.build.rebuild``), and the id counter is preserved so ids
        never repeat across generations. Any attached rt grid is dropped;
        it is rebuilt lazily on the next ``prefilter="rt"`` search
        (:meth:`ensure_rt_grid`).

        Parameters
        ----------
        new_data : JunoIndexData
            The replacement index. Point ids must already be global (a
            rebuild keeps them; see ``repro.build.rebuild.rebuild_index``).
        side_capacity : int, optional
            Capacity of the fresh side buffer (default: keep the current
            buffer's capacity).
        """
        first_new = max(
            self._next_id,
            int(np.asarray(new_data.ivf.point_ids).max(initial=-1)) + 1)
        self.data = new_data
        self.rt_grid = None
        self._init_bookkeeping(
            new_data.ivf.valid, new_data.ivf.point_ids,
            side_capacity=(self.side.capacity if side_capacity is None
                           else side_capacity),
            first_new_id=first_new,
            n_subspaces=int(new_data.codes.shape[1]))

    # ---- RT prefilter grid ----------------------------------------------
    def ensure_rt_grid(self, *, metric: str = "l2", **kw):
        """Build and attach the ``repro.rt`` centroid grid if absent.

        Parameters
        ----------
        metric : str
            "l2" | "ip" — forwarded to ``rt.build_grid`` calibration.
        **kw
            Remaining ``rt.build_grid`` keyword arguments.

        Returns
        -------
        repro.rt.CentroidGrid
            The attached grid.
        """
        if self.rt_grid is None:
            from repro import rt as rt_lib
            self.rt_grid = rt_lib.build_grid(self.data, metric=metric, **kw)
        return self.rt_grid

    def _rt_centroids(self):
        """Centroids for rt-grid maintenance (the index's own)."""
        return self.data.ivf.centroids

    def _apply_insert(self, cl, sl, ids, codes):
        cl_j, sl_j = jnp.asarray(cl), jnp.asarray(sl)
        ivf = self.data.ivf._replace(
            point_ids=self.data.ivf.point_ids.at[cl_j, sl_j].set(
                jnp.asarray(ids)),
            valid=self.data.ivf.valid.at[cl_j, sl_j].set(True))
        self.data = self.data._replace(
            ivf=ivf,
            cluster_codes=self.data.cluster_codes.at[cl_j, sl_j].set(codes))

    def _apply_delete(self, cl, sl):
        ivf = self.data.ivf._replace(
            valid=self.data.ivf.valid.at[jnp.asarray(cl),
                                         jnp.asarray(sl)].set(False))
        self.data = self.data._replace(ivf=ivf)

    # ---- query -----------------------------------------------------------
    def search(self, queries, *, prefilter: str = "scan", **kw):
        """Side-buffer-aware :func:`search` over the current index state.

        An empty side buffer is elided so the no-spill hot path compiles
        and runs exactly as the immutable index's. ``prefilter="rt"``
        routes stage 1 through the sphere-intersection filter, lazily
        building the grid on first use (``ensure_rt_grid``).

        Parameters
        ----------
        queries : jnp.ndarray
            (Q, D) f32 query vectors.
        prefilter : str
            "scan" | "rt" — see :func:`search`.
        **kw
            Remaining :func:`search` keyword arguments.

        Returns
        -------
        tuple of jnp.ndarray
            ``(scores (Q, k), ids (Q, k))`` as :func:`search`.
        """
        side = self.delta_view()
        if prefilter == "rt" and kw.get("rt_grid") is None:
            kw["rt_grid"] = self.ensure_rt_grid(metric=kw.get("metric", "l2"))
        return search(self.data, queries, side=side, prefilter=prefilter,
                      **kw)
