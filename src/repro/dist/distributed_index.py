"""Cluster-partitioned distributed JUNO search.

Scale-out shape (FusionANNS-style): the IVF CLUSTER dimension — centroids,
padded point-id lists and per-cluster PQ codes — is sharded over every mesh
axis, while queries, the PQ codebook and the density model are replicated.
Each shard runs the existing single-device masked-ADC / hit-count scan
(core/juno.py) over its ``local_nprobe`` nearest LOCAL clusters, then the
per-shard top-k candidate lists are all-gathered and merged with one global
static-shape ``lax.top_k`` — global point ids travel with the candidates, so
the merge is exact.

On a 1-device mesh this degenerates to plain ``search`` bit-for-bit: the
local stage IS ``_search_batch`` and the merge is a stable top-k over an
already-sorted list.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.density import DensityModel
from repro.core.ivf import IVFIndex
from repro.core.juno import (JunoIndexData, MutableIndexBase, SideBuffer,
                             _label_encode, _search_batch,
                             _search_batch_two_stage)
from repro.core.pq import PQCodebook


def _cluster_entry(mesh: Mesh):
    """Shard the cluster dim over ALL mesh axes (pure scale-out)."""
    axes = tuple(mesh.axis_names)
    return axes if len(axes) > 1 else axes[0]


def _shard_lin(mesh: Mesh) -> jnp.ndarray:
    """Linear index of the calling shard over all mesh axes (row-major),
    inside a ``shard_map`` body."""
    lin = jnp.int32(0)
    for ax in mesh.axis_names:
        lin = lin * mesh.shape[ax] + jax.lax.axis_index(ax)
    return lin


def index_pspecs(mesh: Mesh) -> JunoIndexData:
    """JunoIndexData-shaped tree of PartitionSpecs for the sharded index."""
    c = _cluster_entry(mesh)
    return JunoIndexData(
        ivf=IVFIndex(
            centroids=P(c, None),
            centroid_sq=P(c),
            point_ids=P(c, None),
            valid=P(c, None),
            labels=P(None)),
        codebook=PQCodebook(entries=P(None, None, None),
                            entry_sq=P(None, None)),
        codes=P(None, None),
        cluster_codes=P(c, None, None),
        density=DensityModel(grid=P(None, None, None), lo=P(None, None),
                             hi=P(None, None), coeffs=P(None),
                             tau_min=P(), tau_max=P()),
        points_sq=P(None))


def shard_index(idx: JunoIndexData, mesh: Mesh) -> JunoIndexData:
    """Place a built index on the mesh: cluster-partitioned arrays sharded,
    everything else replicated. Point ids stay GLOBAL, so shard-local results
    need no re-indexing at merge time."""
    specs = index_pspecs(mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), idx, specs)


def side_pspecs() -> SideBuffer:
    """SideBuffer-shaped tree of PartitionSpecs: fully replicated (the buffer
    is tiny; every shard scores the slice owned by its probed clusters)."""
    return SideBuffer(codes=P(None, None), cluster=P(None), ids=P(None),
                      valid=P(None))


def grid_pspecs():
    """CentroidGrid-shaped tree of PartitionSpecs: fully replicated.

    The rt grid indexes GLOBAL cluster ids and is a few KB of cell
    tables, so every shard carries the whole thing and localizes its
    probe lookups with a cluster-id offset (see
    ``make_distributed_search(prefilter="rt")``).
    """
    from repro.rt import CentroidGrid
    return CentroidGrid(
        proj=P(None, None), lo=P(None), hi=P(None), boxes=P(None, None),
        cell_ids=P(None, None), cell_c0=P(None, None), cell_c1=P(None, None),
        slot_reach=P(None, None), cell_reach=P(None), slot_of=P(None),
        radius_scale=P(), radius_bias=P())


def make_distributed_search(mesh: Mesh, local_nprobe: int, k: int, *,
                            mode: str = "H", metric: str = "l2",
                            thres_scale: float = 1.0,
                            impl: str | None = None,
                            rerank: int = 0, fused: bool = False,
                            fused3: bool | None = None,
                            with_side: bool = False,
                            prefilter: str = "scan", rt_scale: float = 1.0):
    """Build ``dsearch(sharded_index, queries[, side][, rt_grid])``.

    ``local_nprobe`` is the probe budget PER SHARD (global work scales with
    the mesh, matching the paper's fixed per-chip scan cost). The returned
    callable is jitted, so ``dsearch.lower(...)`` works for the dry-run.
    ``impl=None`` follows the platform, as in :func:`repro.core.juno.search`.

    ``fused=True`` (mode "H2" only) runs each shard's two-stage scan
    through the fused hit-count→masked-ADC kernel path — per-shard results,
    and therefore the exact global merge, are id-identical to the composed
    path (core/juno.py). Combined with ``prefilter="rt"`` each shard
    serves the three-stage kernels (the shard's probes
    look up the replicated grid at ``local_cid + shard_offset`` inside
    the kernel, same offset rule as the composed path); ``fused3=False``
    forces the composed rt+fused baseline, bit-identically.

    With ``with_side=True`` the callable takes a replicated
    :class:`SideBuffer` of online-insert overflow as a third argument: each
    shard localizes the buffer's GLOBAL owning-cluster ids into its own
    cluster range (ids owned by other shards localize out of [0, C_local)
    and can never match a probed local cluster), so every side point is
    scored by exactly the shard that owns its cluster — the same routing
    rule inserts follow.

    With ``prefilter="rt"`` the callable takes a replicated
    :class:`repro.rt.CentroidGrid` as its LAST argument: the grid indexes
    global cluster ids, so each shard runs the identical
    sphere-intersection filter and looks its local probes up at
    ``local_cid + shard_offset`` — the pruning decision for any cluster
    is the same on every shard, and the exact global merge is unchanged
    up to which probes each shard masked out (at full-coverage radii the
    results match ``prefilter="scan"`` exactly).
    """
    if fused and mode != "H2":
        raise ValueError(f"fused=True requires mode='H2', got mode={mode!r}")
    if prefilter not in ("scan", "rt"):
        raise ValueError(f"unknown prefilter {prefilter!r}")
    axes = tuple(mesh.axis_names)
    gather_axes = axes if len(axes) > 1 else axes[0]
    specs = index_pspecs(mesh)
    # sign convention of core/juno.py: H/H2 report real distances (lower is
    # better for l2); hit-count modes report counts (higher is better).
    higher_better = metric == "ip" if mode in ("H", "H2") else True

    def local_search(idx: JunoIndexData, queries: jnp.ndarray, *rest):
        """Per-shard scan over local clusters + exact all-gather merge."""
        rest = list(rest)
        side = rest.pop(0) if with_side else None
        rt_grid = rest.pop(0) if prefilter == "rt" else None
        n_local = idx.ivf.centroids.shape[0]
        lin = _shard_lin(mesh)
        if side is not None:
            side = side._replace(cluster=side.cluster - lin * n_local)
        rt_kw = {}
        if prefilter == "rt":
            rt_kw = dict(prefilter="rt", rt_grid=rt_grid, rt_scale=rt_scale,
                         rt_offset=lin * n_local)
        if mode == "H2":
            s, ids = _search_batch_two_stage(
                idx, queries, nprobe=local_nprobe, k=k, metric=metric,
                thres_scale=thres_scale, rerank=rerank, impl=impl,
                fused=fused, fused3=fused3, side=side, **rt_kw)
        else:
            s, ids = _search_batch(
                idx, queries, nprobe=local_nprobe, k=k, mode=mode,
                metric=metric, thres_scale=thres_scale, impl=impl, side=side,
                **rt_kw)
        nq = queries.shape[0]
        key = s if higher_better else -s
        keys = jax.lax.all_gather(key, gather_axes)       # (shards, Q, k)
        gids = jax.lax.all_gather(ids, gather_axes)
        flat_key = jnp.swapaxes(keys, 0, 1).reshape(nq, -1)
        flat_ids = jnp.swapaxes(gids, 0, 1).reshape(nq, -1)
        sel_key, sel = jax.lax.top_k(flat_key, k)
        out_ids = jnp.take_along_axis(flat_ids, sel, axis=1)
        out_scores = sel_key if higher_better else -sel_key
        return out_scores, out_ids

    in_specs = (specs, P(None, None))
    if with_side:
        in_specs = in_specs + (side_pspecs(),)
    if prefilter == "rt":
        in_specs = in_specs + (grid_pspecs(),)
    fn = jax.shard_map(local_search, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
    return jax.jit(fn)


def _routed_update(mesh: Mesh, local_update):
    """Jit a scatter into the cluster-sharded index, routed by cluster.

    ``apply(idx, clusters, *rest)`` runs as a ``shard_map`` body: each
    shard localizes the GLOBAL cluster ids to its own row range and
    ``local_update(idx_local, local_clusters, *rest)`` scatters with
    ``mode="drop"``, so rows owned by other shards (mapped to the
    out-of-range row ``n_local``) are dropped. The update therefore lands
    on the owning shard with no collective and no resharding, and the
    output keeps the input layout — on meshes with Auto and Explicit axes
    alike (a plain ``.at[].set`` on an Explicit mesh must name an output
    sharding). ``clusters`` and ``rest`` are replicated.
    """
    specs = index_pspecs(mesh)

    def body(idx: JunoIndexData, clusters, *rest):
        n_local = idx.ivf.point_ids.shape[0]
        local = clusters - _shard_lin(mesh) * n_local
        local = jnp.where((local >= 0) & (local < n_local), local, n_local)
        return local_update(idx, local, *rest)

    def apply(idx: JunoIndexData, clusters, *rest):
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(specs,) + (P(),) * (1 + len(rest)),
                           out_specs=specs, check_vma=False)
        return fn(idx, clusters, *rest)

    return jax.jit(apply)


def make_distributed_insert(mesh: Mesh):
    """Jitted ``apply(idx, clusters, slots, ids, codes) -> idx`` scatter.

    The scatter targets rows of the cluster-sharded arrays and runs on the
    shard that owns each cluster (:func:`_routed_update`) — inserts are
    "routed by owning cluster" with no resharding and no shape change (hot
    jitted search signatures stay warm).
    """
    def update(idx: JunoIndexData, clusters, slots, ids, codes):
        """Scatter new (id, code) cells into their owning clusters."""
        ivf = idx.ivf._replace(
            point_ids=idx.ivf.point_ids.at[clusters, slots].set(
                ids, mode="drop"),
            valid=idx.ivf.valid.at[clusters, slots].set(True, mode="drop"))
        return idx._replace(
            ivf=ivf, cluster_codes=idx.cluster_codes.at[clusters, slots].set(
                codes, mode="drop"))

    return _routed_update(mesh, update)


def make_distributed_row_update(mesh: Mesh):
    """Jitted whole-row scatter ``apply(idx, clusters, ids, valid, codes)``.

    The rebuild counterpart of :func:`make_distributed_insert`: instead of
    touching single (cluster, slot) cells it replaces ENTIRE padded rows
    (point ids, valid mask and PQ codes) of the given clusters, each on
    the shard owning it — a per-shard rebuild is one scatter with no
    resharding and no shape change.
    """
    def update(idx: JunoIndexData, clusters, row_ids, row_valid, row_codes):
        """Replace whole padded rows of the given clusters."""
        ivf = idx.ivf._replace(
            point_ids=idx.ivf.point_ids.at[clusters].set(row_ids,
                                                         mode="drop"),
            valid=idx.ivf.valid.at[clusters].set(row_valid, mode="drop"))
        return idx._replace(
            ivf=ivf, cluster_codes=idx.cluster_codes.at[clusters].set(
                row_codes, mode="drop"))

    return _routed_update(mesh, update)


def make_distributed_delete(mesh: Mesh):
    """Jitted ``apply(idx, clusters, slots) -> idx`` tombstone scatter."""
    def update(idx: JunoIndexData, clusters, slots):
        """Clear the valid bit of the given (cluster, slot) cells."""
        return idx._replace(ivf=idx.ivf._replace(
            valid=idx.ivf.valid.at[clusters, slots].set(False, mode="drop")))

    return _routed_update(mesh, update)


class DistributedMutableIndex(MutableIndexBase):
    """Sharded, online-mutable JUNO index (the serving-scale counterpart of
    :class:`repro.core.MutableJunoIndex`).

    Data plane: cluster-sharded :class:`JunoIndexData` + replicated
    :class:`SideBuffer`; searches go through ``make_distributed_search(...,
    with_side=True)`` which merges per-shard top-k exactly. Control plane:
    the host-side slot bookkeeping inherited from
    :class:`~repro.core.juno.MutableIndexBase`, with device updates applied
    by the routed scatter updaters above — each insert/delete lands on the
    shard owning its cluster, and ``compact()`` (also inherited) folds the
    replicated side buffer back through the same routed scatter.

    Pass ``rt_grid`` (built from the UNSHARDED index via ``rt.build_grid``)
    to serve ``prefilter="rt"`` searches: inserts then grow the touched
    clusters' projected reaches exactly as :class:`MutableJunoIndex` does,
    and callers hand the CURRENT ``self.rt_grid`` to the callable returned
    by ``searcher(..., prefilter="rt")`` so mutated reaches take effect.
    """

    def __init__(self, idx: JunoIndexData, mesh: Mesh, *,
                 side_capacity: int = 256, rt_grid=None):
        """Shard a built global index onto ``mesh`` and wire its updaters."""
        n_clusters = idx.ivf.point_ids.shape[0]
        n_shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        assert n_clusters % n_shards == 0, \
            f"clusters ({n_clusters}) must divide evenly over {n_shards} shards"
        self.mesh = mesh
        self.n_shards = n_shards
        self.data = shard_index(idx, mesh)
        self.rt_grid = rt_grid
        self._insert_fn = make_distributed_insert(mesh)
        self._delete_fn = make_distributed_delete(mesh)
        self._row_update_fn = make_distributed_row_update(mesh)
        self._replicate_encoder(idx)
        self._init_bookkeeping(idx.ivf.valid, idx.ivf.point_ids,
                               side_capacity=side_capacity,
                               first_new_id=int(idx.codes.shape[0]),
                               n_subspaces=int(idx.codes.shape[1]))

    def _replicate_encoder(self, idx: JunoIndexData) -> None:
        """Replicated copies of the small arrays insert-time encoding
        reads. ``idx`` may come back from a rebuild with cluster-sharded
        centroids; gathering rows of those on a mesh with Explicit axes
        would need an output sharding, so they are replicated here."""
        rep = NamedSharding(self.mesh, P())
        self._centroids = jax.device_put(idx.ivf.centroids, rep)
        self._codebook = jax.device_put(idx.codebook, rep)

    def _labels_codes(self, pts):
        return _label_encode(pts, self._centroids, self._codebook)

    def _rt_centroids(self):
        """Replicated centroids (the grid indexes GLOBAL cluster ids)."""
        return self._centroids

    def _apply_insert(self, cl, sl, ids, codes):
        self.data = self._insert_fn(self.data, jnp.asarray(cl),
                                    jnp.asarray(sl), jnp.asarray(ids), codes)

    def _apply_delete(self, cl, sl):
        self.data = self._delete_fn(self.data, jnp.asarray(cl),
                                    jnp.asarray(sl))

    def searcher(self, local_nprobe: int, k: int, **kw):
        """Side-aware distributed search callable for this index's mesh."""
        return make_distributed_search(self.mesh, local_nprobe, k,
                                       with_side=True, **kw)

    def merge_lanes(self) -> list[tuple[int, int]]:
        """Per-shard cluster ranges for the LSM merge scheduler.

        ``repro.core.freshness.MergeScheduler`` detects this hook and
        steps one lane per call, round-robin — each incremental fold's
        row scatter then lands on a single shard, giving the per-shard
        background-merge schedule without a scheduler object per shard.
        """
        n_clusters = self.data.ivf.point_ids.shape[0]
        cl = n_clusters // self.n_shards
        return [(s * cl, (s + 1) * cl) for s in range(self.n_shards)]

    # ---- rebuild / hot swap ---------------------------------------------
    def swap_data(self, new_data: JunoIndexData, *,
                  side_capacity: int | None = None) -> None:
        """Atomically install a rebuilt global index on this mesh.

        The distributed counterpart of
        :meth:`repro.core.MutableJunoIndex.swap_data`: the new index is
        cluster-sharded onto the mesh, slot bookkeeping is rederived
        from its ``point_ids``/``valid``, the side buffer resets to
        empty, the id watermark is preserved, and any attached rt grid
        is dropped (rebuild it from the new index when serving
        ``prefilter="rt"``). A capacity change retraces the jitted
        search/update programs on first use; an unchanged capacity
        keeps them warm.

        Parameters
        ----------
        new_data : JunoIndexData
            Replacement GLOBAL (unsharded) index; ``n_clusters`` must
            still divide over the mesh and point ids must be global.
        side_capacity : int, optional
            Capacity of the fresh side buffer (default: keep current).
        """
        first_new = max(
            self._next_id,
            int(np.asarray(new_data.ivf.point_ids).max(initial=-1)) + 1)
        self.data = shard_index(new_data, self.mesh)
        self.rt_grid = None
        self._replicate_encoder(new_data)
        self._init_bookkeeping(
            new_data.ivf.valid, new_data.ivf.point_ids,
            side_capacity=(self.side.capacity if side_capacity is None
                           else side_capacity),
            first_new_id=first_new,
            n_subspaces=int(new_data.codes.shape[1]))

    def rebuild_shard(self, shard: int) -> int:
        """Re-pack one cluster shard in place: drop tombstones, drain side.

        For every cluster owned by ``shard``, live in-cluster points are
        compacted to the front of their padded row (slot order preserved)
        and side-buffer points owned by those clusters are re-encoded
        into the freed slots (buffer order). The padded capacity is FIXED
        here — the (C, P) array shape is shared across shards — so
        spills that do not fit stay in the buffer; :meth:`rebuild`
        escalates those to a capacity-growing full swap. The whole shard
        lands on the device in ONE routed row scatter
        (:func:`make_distributed_row_update`), so the other shards — and
        every jitted search signature — are untouched while this shard
        rebuilds. Search results are unchanged by construction: a side
        point was already scored exactly like the in-cluster sibling it
        becomes.

        Parameters
        ----------
        shard : int
            Shard position in ``[0, n_shards)`` (clusters
            ``[shard*C/n, (shard+1)*C/n)``).

        Returns
        -------
        int
            Side-buffer points drained into this shard's clusters.
        """
        from repro.build.rebuild import live_points

        n_clusters = self.data.ivf.point_ids.shape[0]
        cl = n_clusters // self.n_shards
        lo, hi = shard * cl, (shard + 1) * cl
        point_ids = np.asarray(self.data.ivf.point_ids)
        valid = np.asarray(self.data.ivf.valid)
        cluster_codes = np.asarray(self.data.cluster_codes)
        cap = point_ids.shape[1]
        n_sub = cluster_codes.shape[-1]

        members = live_points(self, point_ids, valid, cluster_codes,
                              clusters=range(lo, hi))
        row_ids = np.full((cl, cap), -1, np.int32)
        row_codes = np.zeros((cl, cap, n_sub), np.uint8)
        for c in range(lo, hi):
            packed = members[c][:cap]      # overflow spills stay in side
            for slot, (pid, code) in enumerate(packed):
                row_ids[c - lo, slot] = pid
                row_codes[c - lo, slot] = code
                self._loc[pid] = (c, slot)
            self._free[c] = list(range(len(packed), cap))[::-1]
        # a side id that now has an in-cluster location frees its buffer slot
        side_ids = np.asarray(self.side.ids)
        side_valid = np.asarray(self.side.valid)
        freed_pos = [int(pos) for pos in np.where(side_valid)[0]
                     if self._loc.get(int(side_ids[pos]), (-1, -1))[0] >= 0]
        if freed_pos:
            pos_j = jnp.asarray(freed_pos)
            self.side = self.side._replace(
                valid=self.side.valid.at[pos_j].set(False))
            self._side_free.extend(freed_pos)
        # likewise, minor-generation points packed into this shard's base
        # rows are tombstoned in their generation (drained generations drop)
        freed_minor = 0
        for m in self._minors:
            mpos = [int(p) for p in np.where(m.valid)[0]
                    if self._loc.get(int(m.ids[p]), (-1, -1))[0] >= 0]
            if mpos:
                m.valid[np.asarray(mpos)] = False
                freed_minor += len(mpos)
        if freed_minor:
            self._minors = [m for m in self._minors if m.live]
        if freed_pos or freed_minor:
            self._delta_epoch += 1
        self.data = self._row_update_fn(
            self.data, np.arange(lo, hi, dtype=np.int32), row_ids,
            row_ids >= 0, row_codes)
        return len(freed_pos) + freed_minor

    def rebuild(self) -> int:
        """Drain the side buffer: per-shard repacks, then grow if stuck.

        Rebuilds every shard in sequence (:meth:`rebuild_shard` — cheap,
        fixed capacity, jit signatures stay warm). Spills whose owning
        cluster is still full afterwards cannot fit the fixed padded
        capacity, so they escalate to a full
        ``repro.build.rebuild.rebuild_index`` + :meth:`swap_data` —
        capacity grows and the buffer always ends empty, matching the
        single-device ``AnnServeEngine.compact()`` guarantee.

        Returns
        -------
        int
            Total side-buffer points drained (per-shard + escalation).
        """
        drained = sum(self.rebuild_shard(s) for s in range(self.n_shards))
        stuck = self.delta_fill      # L0 + minor points the repack left
        if stuck:
            from repro.build.rebuild import rebuild_index
            self.swap_data(rebuild_index(self))
            drained += stuck
        return drained
