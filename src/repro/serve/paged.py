"""Out-of-core paged ANN serving (`repro.serve.paged`).

JUNO's evaluation tops out where the PQ-coded index stops fitting in
accelerator memory; FusionANNS (PAPERS.md) shows the billion-scale
regime wants a tiered split instead — small hot metadata resident,
bulk data demand-paged. This module maps that split onto the artifact
store (``repro.build.store``):

* **Resident tier** — IVF centroids/point-ids/valid masks, PQ codebooks,
  the density→threshold model and (when saved into the artifact) the
  ``repro.rt`` centroid grid are promoted to device arrays at load time.
  Stage A cluster filtering, rt probe routing and the LUT/threshold
  machinery run entirely over this tier.
* **Paged tier** — the per-cluster PQ code shards (``cluster_codes``,
  the O(N·S) bulk) stay memory-mapped on disk
  (``load_index(mmap_mode="r")``) behind :class:`ClusterCache`, a
  byte-capacity LRU of hot clusters with hit/miss/eviction counters.
  Each cluster row is digest-verified on first touch against the
  manifest's per-row sha256 table — the mmap half of the store's
  fail-closed contract.
* **Exact-rerank tier** (optional) — FusionANNS's CPU/GPU cooperative
  split mapped to host-memory/VMEM: the paged search returns a top-C
  candidate list and the final top-k is re-scored exactly against raw
  vectors fetched (memory-mapped) for only those C candidates.

:class:`PagedJunoIndex` is the :class:`~repro.core.juno.MutableIndexBase`
wiring: inserts route to the side buffer (the paged shards are
read-only), deletes tombstone the resident valid mask, and
``swap_data``/:meth:`PagedAnnServeEngine.swap_index` atomically retarget
the cluster cache to a new artifact generation. Scoring reuses
``repro.core.juno``'s ``_score_probed`` / ``_score_probed_two_stage``
verbatim, so the paged path returns the same ids the resident path does
(``tests/test_paged.py``; gated at scale by ``benchmarks/serve_qps.py``
serving a dataset ≥ 4× the cache).
"""
from __future__ import annotations

import collections
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.build.store import (ArtifactError, _array_digest, load_index)
from repro.core.ivf import filter_clusters
from repro.core.juno import (JunoIndexData, MutableIndexBase, _score_probed,
                             _score_probed_two_stage)
from repro.serve.ann import AnnServeEngine


class ClusterCache:
    """Byte-capacity LRU cache of per-cluster PQ code rows.

    Keys are cluster ids, values are the materialized ``(P, S)`` uint8
    code rows read from the memory-mapped shard. Eviction is
    least-recently-used by bytes: rows are dropped until the new row
    fits ``capacity_bytes``. A row larger than the whole capacity is
    served but never cached (correctness never depends on residency).
    ``hits``/``misses``/``evictions``/``bytes`` make cache pressure
    observable; ``benchmarks/serve_qps.py`` asserts evictions > 0 to
    prove its gate really exercised the paged tier.
    """

    def __init__(self, capacity_bytes: int):
        """Create an empty cache bounded by ``capacity_bytes`` bytes."""
        self.capacity_bytes = int(capacity_bytes)
        self._rows: collections.OrderedDict[int, np.ndarray] = \
            collections.OrderedDict()
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._m = None          # registry metric handles once bound
        self._bound_to = None   # the registry the handles live in

    def bind(self, registry) -> None:
        """Mirror the counters into a ``repro.obs`` registry.

        Registers the documented ``juno_cache_*`` series (the ad-hoc
        int attributes and :meth:`stats` keys stay as the deprecated
        alias) and seeds them with the counts accumulated so far, so
        binding after warm-up loses nothing. Re-binding to the same
        registry is a no-op (generation swaps re-bind the adopted
        cache) — the seed must not double-count.
        """
        if self._bound_to is registry:
            return
        self._bound_to = registry
        m = {"hits": registry.counter("juno_cache_hits_total"),
             "misses": registry.counter("juno_cache_misses_total"),
             "evictions": registry.counter("juno_cache_evictions_total"),
             "evicted_bytes": registry.counter(
                 "juno_cache_evicted_bytes_total"),
             "bytes": registry.gauge("juno_cache_bytes", agg="sum"),
             "rows": registry.gauge("juno_cache_rows", agg="sum")}
        m["hits"].inc(self.hits)
        m["misses"].inc(self.misses)
        m["evictions"].inc(self.evictions)
        m["bytes"].set(self.bytes)
        m["rows"].set(len(self._rows))
        self._m = m

    def get(self, cid: int) -> np.ndarray | None:
        """Return the cached row for ``cid`` (refreshing LRU) or None."""
        row = self._rows.get(cid)
        if row is None:
            self.misses += 1
            if self._m is not None:
                self._m["misses"].inc()
            return None
        self._rows.move_to_end(cid)
        self.hits += 1
        if self._m is not None:
            self._m["hits"].inc()
        return row

    def put(self, cid: int, row: np.ndarray) -> None:
        """Insert ``row`` under ``cid``, evicting LRU rows to fit."""
        nb = row.nbytes
        if nb > self.capacity_bytes:
            return                    # larger than the whole cache: bypass
        while self._rows and self.bytes + nb > self.capacity_bytes:
            _, old = self._rows.popitem(last=False)
            self.bytes -= old.nbytes
            self.evictions += 1
            if self._m is not None:
                self._m["evictions"].inc()
                self._m["evicted_bytes"].inc(old.nbytes)
        self._rows[cid] = row
        self.bytes += nb
        if self._m is not None:
            self._m["bytes"].set(self.bytes)
            self._m["rows"].set(len(self._rows))

    def clear(self) -> None:
        """Drop every cached row (capacity and counters are kept)."""
        self._rows.clear()
        self.bytes = 0

    def __len__(self) -> int:
        """Number of cached cluster rows."""
        return len(self._rows)

    def stats(self) -> dict:
        """``{"capacity_bytes", "bytes", "rows", "hits", "misses",
        "evictions"}`` — deprecated-alias snapshot of the counters.

        These ad-hoc keys predate ``repro.obs``; the documented form is
        the ``juno_cache_*`` registry series a :meth:`bind` call keeps
        in lockstep with the same numbers.
        """
        return {"capacity_bytes": self.capacity_bytes, "bytes": self.bytes,
                "rows": len(self._rows), "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions}


def _to_device(nt):
    """Promote every field of a NamedTuple of arrays to device arrays."""
    return type(nt)(**{f: jnp.asarray(np.asarray(getattr(nt, f)))
                       for f in type(nt)._fields})


class PagedIndexData:
    """One artifact generation served out-of-core.

    Loads an artifact with ``load_index(mmap_mode="r")``: metadata
    (IVF/codebook/density, plus the rt grid when the artifact holds one)
    is promoted to resident device arrays in :attr:`meta` — a real
    :class:`~repro.core.juno.JunoIndexData` whose ``codes`` /
    ``cluster_codes`` / ``points_sq`` are zero-length placeholders — and
    the cluster code shards stay on disk behind a :class:`ClusterCache`.

    Integrity is fail-closed in two stages: the load itself runs the
    store's ``verify="manifest"`` pass (schema, config hash, array
    set/shapes/dtypes), and each cluster row is sha256-verified against
    the manifest's ``sha256_rows`` table the first time it is faulted in
    (:meth:`fetch_cluster` raises :class:`~repro.build.store.ArtifactError`
    on mismatch). Artifacts written before per-row digests existed can
    only be served with ``verify_rows=False`` — an explicit opt-out, not
    a silent downgrade.
    """

    def __init__(self, path: str, *, cache_bytes: int = 64 << 20,
                 expect_config=None, vectors=None,
                 verify_rows: bool = True, verify: str | None = None):
        """Open an artifact directory for paged serving.

        Parameters
        ----------
        path : str
            Artifact directory written by ``repro.build.store.save_index``
            (usually ``ArtifactStore.path(name, version)``).
        cache_bytes : int
            Hot-cluster cache capacity in bytes. Size it to the working
            set: ``C_hot · P · S`` bytes for the clusters the query
            distribution actually probes (docs/serving.md).
        expect_config : JunoConfig, optional
            Forwarded to ``load_index`` (config-hash guard).
        vectors : array-like or str, optional
            Raw ``(N, D)`` vectors for the exact-rerank tier — an
            ``np.memmap``/array, or a path to an ``.npy`` opened with
            ``mmap_mode="r"``. Only the final top-C candidate rows are
            ever read.
        verify_rows : bool
            Verify each cluster row's sha256 on first touch (default).
            Required when the manifest carries ``sha256_rows``-capable
            data; ``False`` is the explicit opt-out for old artifacts.
        verify : str, optional
            Load-time verification level forwarded to ``load_index``
            (default: the mmap default, ``"manifest"``).
        """
        loaded = load_index(path, expect_config=expect_config,
                            mmap_mode="r", verify=verify)
        self.path = path
        self.config = loaded.config
        self.manifest = loaded.manifest
        self.rt_grid = (None if loaded.rt_grid is None
                        else _to_device(loaded.rt_grid))
        self._cluster_codes = loaded.data.cluster_codes   # (C, P, S) memmap
        self._codes = loaded.data.codes                   # (N, S) memmap
        self._points_sq = loaded.data.points_sq           # (N,) memmap
        c, p, s = self._cluster_codes.shape
        ivf = _to_device(loaded.data.ivf)
        self.meta = JunoIndexData(
            ivf=ivf, codebook=_to_device(loaded.data.codebook),
            density=_to_device(loaded.data.density),
            codes=jnp.zeros((0, s), self._codes.dtype),
            cluster_codes=jnp.zeros((0, p, s), self._cluster_codes.dtype),
            points_sq=jnp.zeros((0,), jnp.float32))
        self.cluster_bytes = int(self._cluster_codes.nbytes)
        self._row_digests = loaded.manifest["arrays"]["cluster_codes"].get(
            "sha256_rows")
        if verify_rows and self._row_digests is None:
            raise ArtifactError(
                f"artifact has no per-row digests for cluster_codes; "
                f"re-save it with the current store, or opt out with "
                f"verify_rows=False ({path})")
        if not verify_rows:
            self._row_digests = None
        self._verified = np.zeros(c, bool)
        self.verified_rows = 0
        if isinstance(vectors, str):
            vectors = np.load(vectors, mmap_mode="r")
        self.vectors = vectors
        self.cache = ClusterCache(cache_bytes)
        self._obs = None        # Observability bundle once bound
        pid = np.asarray(loaded.data.ivf.point_ids)
        valid = np.asarray(loaded.data.ivf.valid)
        #: smallest id no committed point uses — seeds the mutable wrapper
        self.first_new_id = int(pid[valid].max(initial=-1)) + 1

    def bind_obs(self, obs) -> None:
        """Attach an ``repro.obs.Observability`` bundle to the fetch plane.

        Binds the cluster cache's counters to ``obs.registry`` and turns
        every cache miss into a ``paged.fault`` span plus
        ``juno_paged_faults_total`` / ``juno_paged_fault_bytes_total``
        counters, with first-touch digest time observed into
        ``juno_paged_verify_seconds``.
        """
        self._obs = obs
        self.cache.bind(obs.registry)

    # ---- paged fetch plane ----------------------------------------------
    def fetch_cluster(self, cid: int) -> np.ndarray:
        """Materialize one cluster's ``(P, S)`` code row, cached.

        Cache hit → the resident copy. Miss → one cluster-sized read
        from the memory-mapped shard, sha256-checked against the
        manifest on the row's first-ever touch (fail-closed: a flipped
        bit raises ``ArtifactError`` instead of serving garbage), then
        inserted into the LRU.
        """
        row = self.cache.get(cid)
        if row is not None:
            return row
        if self._obs is not None:
            with self._obs.tracer.span("paged.fault", cluster=cid):
                row = self._fault_in(cid)
            self._obs.registry.counter("juno_paged_faults_total").inc()
            self._obs.registry.counter(
                "juno_paged_fault_bytes_total").inc(row.nbytes)
        else:
            row = self._fault_in(cid)
        self.cache.put(cid, row)
        return row

    def _fault_in(self, cid: int) -> np.ndarray:
        """Miss path: mmap read + first-touch digest check for one cluster."""
        row = np.ascontiguousarray(self._cluster_codes[cid])
        if self._row_digests is not None and not self._verified[cid]:
            t0 = time.perf_counter()
            if _array_digest(row) != self._row_digests[cid]:
                raise ArtifactError(
                    f"cluster_codes[{cid}]: checksum mismatch on first "
                    f"touch ({self.path})")
            self._verified[cid] = True
            self.verified_rows += 1
            if self._obs is not None:
                self._obs.registry.histogram(
                    "juno_paged_verify_seconds").add(
                        time.perf_counter() - t0)
        return row

    def gather(self, cids) -> np.ndarray:
        """Gather probed clusters' codes: ``(...,) ids → (..., P, S)``.

        The host-side equivalent of the resident path's
        ``index.cluster_codes[cids]`` device gather — every distinct
        cluster is faulted through :meth:`fetch_cluster` exactly once
        per call, so a batch touching U unique clusters costs at most U
        cluster reads (0 when all are cache-hot).
        """
        cids = np.asarray(cids)
        uniq, inv = np.unique(cids, return_inverse=True)
        rows = np.stack([self.fetch_cluster(int(c)) for c in uniq])
        return rows[inv].reshape(cids.shape + rows.shape[1:])

    def fetch_vectors(self, ids) -> np.ndarray:
        """Raw vectors for the exact-rerank tier: ``(Q, C) ids → (Q, C, D)``.

        Reads only the addressed rows from the memory-mapped vector
        file. Negative (sentinel) ids are clamped to row 0 — callers
        mask them out of the rerank by score.
        """
        if self.vectors is None:
            raise RuntimeError("no raw-vector source attached "
                               "(PagedIndexData(vectors=...))")
        ids = np.asarray(ids)
        safe = np.clip(ids, 0, self.vectors.shape[0] - 1)
        return np.asarray(self.vectors[safe], np.float32)

    # ---- generation retargeting ------------------------------------------
    def adopt_cache(self, cache: ClusterCache) -> None:
        """Take over an existing cache for this generation.

        Every cached row is dropped first — rows belong to the
        generation that faulted them in — while the capacity and
        cumulative hit/miss/eviction counters carry over. This is the
        swap-time primitive: ``PagedJunoIndex.swap_data`` calls it so a
        hot-swapped engine keeps one cache whose contents can never
        alias across generations.
        """
        cache.clear()
        self.cache = cache

    def stats(self) -> dict:
        """Cache counters plus paged-tier sizing and verify progress."""
        out = self.cache.stats()
        out.update({"cluster_bytes": self.cluster_bytes,
                    "verified_rows": self.verified_rows,
                    "generation": self.path})
        return out


@functools.partial(jax.jit, static_argnames=("nprobe", "metric"))
def _paged_filter(ivf, q, *, nprobe: int, metric: str):
    """Stage A alone, over the resident IVF metadata (jitted)."""
    return filter_clusters(q, ivf, nprobe=nprobe, metric=metric)


@functools.partial(jax.jit,
                   static_argnames=("k", "mode", "metric", "impl",
                                    "prefilter"))
def _paged_score(index, q, base, cids, codes, *, k, mode, metric,
                 thres_scale, impl, side, prefilter, rt_grid, rt_scale):
    """Stages B+C over host-gathered codes (jitted).

    ``codes`` is the (Q, np, P, S) batch the cluster cache assembled;
    ``valid``/``ids`` are gathered here from the resident IVF arrays so
    tombstones committed after a row was cached still mask correctly.
    """
    valid = index.ivf.valid[cids]
    ids = index.ivf.point_ids[cids]
    return _score_probed(index, q, base, cids, codes, valid, ids, k=k,
                         mode=mode, metric=metric, thres_scale=thres_scale,
                         impl=impl, side=side, prefilter=prefilter,
                         rt_grid=rt_grid, rt_scale=rt_scale)


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "impl", "rerank",
                                    "fused", "fused3", "prefilter"))
def _paged_score_two_stage(index, q, base, cids, codes, *, k, metric,
                           thres_scale, rerank, impl, fused, side,
                           prefilter, rt_grid, rt_scale, fused3=None):
    """Mode-H2 stages over host-gathered codes (jitted); see
    :func:`_paged_score` for the gather contract."""
    valid = index.ivf.valid[cids]
    ids = index.ivf.point_ids[cids]
    return _score_probed_two_stage(
        index, q, base, cids, codes, valid, ids, k=k, metric=metric,
        thres_scale=thres_scale, rerank=rerank, impl=impl, fused=fused,
        fused3=fused3, side=side, prefilter=prefilter, rt_grid=rt_grid,
        rt_scale=rt_scale)


class PagedJunoIndex(MutableIndexBase):
    """Mutable serving wrapper over a :class:`PagedIndexData` generation.

    The control plane is the shared
    :class:`~repro.core.juno.MutableIndexBase` bookkeeping with one
    paged-tier rule: the on-disk cluster shards are read-only, so
    **every insert routes to the side buffer** (the per-cluster free
    lists are kept empty) and **deleted slots are never reused** —
    tombstones accumulate in the resident valid mask until the next
    offline rebuild lands as a new artifact generation
    (:meth:`swap_data`). ``compact()`` is therefore always a no-op here;
    draining the delta tiers is the offline rebuild's job.

    With the LSM freshness tiers enabled
    (``enable_tiers(max_minors, minor_store=...)``, see
    ``repro.core.freshness``), a full L0 no longer stalls inserts until
    the next rebuild: it is sealed into a minor generation committed
    through the :class:`~repro.build.store.ArtifactStore` and
    demand-paged back on first search touch with the same per-row sha256
    fail-closed verification the base shards get — the paged tier's
    insert headroom grows from B to B·(1 + max_minors) between rebuilds,
    while incremental folds into the sealed base naturally no-op.
    """

    def __init__(self, paged: PagedIndexData, *, side_capacity: int = 256):
        """Wrap one paged generation.

        Parameters
        ----------
        paged : PagedIndexData
            The artifact generation to serve.
        side_capacity : int
            Overflow-buffer capacity — the *only* insert headroom a
            paged index has between rebuilds.
        """
        self.paged = paged
        self.data = paged.meta
        self.rt_grid = paged.rt_grid
        self._init_bookkeeping(
            paged.meta.ivf.valid, paged.meta.ivf.point_ids,
            side_capacity=side_capacity, first_new_id=paged.first_new_id,
            n_subspaces=int(paged.meta.cluster_codes.shape[-1]))
        self._seal_clusters()

    def _seal_clusters(self) -> None:
        # read-only shards: no cluster slot is ever an insert target
        self._free = [[] for _ in self._free]

    def _labels_codes(self, pts):
        from repro.core.juno import _label_encode
        return _label_encode(pts, self.data.ivf.centroids,
                             self.data.codebook)

    def _rt_centroids(self):
        """Centroids for rt-grid reach maintenance (resident tier)."""
        return self.data.ivf.centroids

    def _apply_insert(self, cl, sl, ids, codes):
        raise RuntimeError(
            "paged cluster shards are read-only; inserts must land in the "
            "side buffer (this indicates a bookkeeping bug)")

    def _apply_delete(self, cl, sl):
        ivf = self.data.ivf._replace(
            valid=self.data.ivf.valid.at[jnp.asarray(cl),
                                         jnp.asarray(sl)].set(False))
        self.data = self.data._replace(ivf=ivf)

    def delete(self, ids) -> int:
        """Tombstone points by global id (see ``MutableIndexBase.delete``).

        Paged rule: the freed cluster slots do NOT become insert targets
        — the code shards on disk cannot be rewritten — so they stay
        dead until an offline rebuild. The resident valid mask updates
        immediately; a cached code row needs no invalidation because
        validity is applied at scoring time from the resident tier.
        """
        n = super().delete(ids)
        self._seal_clusters()
        return n

    def ensure_rt_grid(self, *, metric: str = "l2", **kw):
        """Return the artifact's rt grid; paged mode cannot build one.

        ``rt.build_grid`` calibrates against every PQ code — O(N) reads,
        exactly what paging exists to avoid — so the grid must have been
        folded into the artifact at build time
        (``save_index(rt_grid=...)``).
        """
        if self.rt_grid is None:
            raise RuntimeError(
                "paged serving cannot build an rt grid lazily (calibration "
                "decodes every point); save the grid into the artifact: "
                "save_index(path, data, config, rt_grid=build_grid(...))")
        return self.rt_grid

    # ---- hot swap --------------------------------------------------------
    def swap_data(self, new_data, *, side_capacity: int | None = None
                  ) -> None:
        """Atomically retarget serving to a new paged generation.

        ``new_data`` must be a :class:`PagedIndexData` (a rebuilt
        artifact generation, e.g. ``PagedIndexData(store.path(name,
        store.latest(name)))``). The new generation **adopts the current
        cluster cache** — same capacity, cumulative counters — with
        every cached row dropped, so no request served after the swap
        can ever read a stale generation's codes. Bookkeeping is
        rederived from the new resident metadata, the side buffer resets
        (the rebuild drained it), the id counter never goes backwards,
        and the rt grid becomes the new artifact's.
        """
        if not isinstance(new_data, PagedIndexData):
            raise TypeError(
                f"a paged index swaps to a new PagedIndexData generation, "
                f"got {type(new_data).__name__} (build the artifact "
                f"offline and wrap it)")
        new_data.adopt_cache(self.paged.cache)
        first_new = max(self._next_id, new_data.first_new_id)
        self.paged = new_data
        self.data = new_data.meta
        self.rt_grid = new_data.rt_grid
        self._init_bookkeeping(
            new_data.meta.ivf.valid, new_data.meta.ivf.point_ids,
            side_capacity=(self.side.capacity if side_capacity is None
                           else side_capacity),
            first_new_id=first_new,
            n_subspaces=int(new_data.meta.cluster_codes.shape[-1]))
        self._seal_clusters()

    # ---- query -----------------------------------------------------------
    def search(self, queries, *, nprobe: int = 16, k: int = 10,
               mode: str = "H", metric: str = "l2",
               thres_scale: float = 1.0, impl: str | None = None,
               rerank: int = 0, fused: bool = False,
               fused3: bool | None = None,
               prefilter: str = "scan", rt_scale: float = 1.0):
        """One paged search batch: filter → cache gather → shared scoring.

        The single-shot counterpart of
        :meth:`PagedAnnServeEngine._dispatch` (same three phases, no
        batching/bucketing): stage A runs jitted over the resident IVF,
        the probed clusters' codes are gathered on the host through the
        cluster cache, and the jitted scoring tail is the *same
        function* the resident path runs — so returned ids match
        resident serving (tests/test_paged.py pins this).

        Parameters
        ----------
        queries : array-like
            (Q, D) f32 query rows.
        nprobe, k, mode, metric, thres_scale, impl, rerank, fused, fused3
            As :func:`repro.core.juno.search` (``fused`` +
            ``prefilter="rt"`` serves the three-stage kernel over the
            paged codes unless ``fused3=False``).
        prefilter : str
            "scan" | "rt" — "rt" requires the artifact-stored grid.
        rt_scale : float
            Query-sphere radius knob for "rt".

        Returns
        -------
        tuple of np.ndarray
            ``(scores (Q, k), ids (Q, k))``.
        """
        if fused and mode != "H2":
            raise ValueError(f"fused=True requires mode='H2', got {mode!r}")
        q = jnp.asarray(np.atleast_2d(np.asarray(queries, np.float32)))
        nprobe = min(nprobe, self.data.ivf.centroids.shape[0])
        rt_grid = (self.ensure_rt_grid(metric=metric)
                   if prefilter == "rt" else None)
        side = self.delta_view()
        base, cids = _paged_filter(self.data.ivf, q, nprobe=nprobe,
                                   metric=metric)
        codes = jnp.asarray(self.paged.gather(np.asarray(cids)))
        if mode == "H2":
            s, ids = _paged_score_two_stage(
                self.data, q, base, cids, codes, k=k, metric=metric,
                thres_scale=thres_scale, rerank=rerank, impl=impl,
                fused=fused, fused3=fused3, side=side, prefilter=prefilter,
                rt_grid=rt_grid, rt_scale=rt_scale)
        else:
            s, ids = _paged_score(
                self.data, q, base, cids, codes, k=k, mode=mode,
                metric=metric, thres_scale=thres_scale, impl=impl,
                side=side, prefilter=prefilter, rt_grid=rt_grid,
                rt_scale=rt_scale)
        return np.asarray(s), np.asarray(ids)


class PagedAnnServeEngine(AnnServeEngine):
    """An :class:`~repro.serve.ann.AnnServeEngine` over a paged index.

    Inherits the whole request plane — knob quantization, size-bucketed
    batching, recall routing, rt probe-budget shrinking (the routing
    state reads only resident metadata) — and replaces dispatch with the
    three-phase paged pipeline: jitted stage-A filter over the resident
    tier, host gather of the probed clusters through the LRU cache,
    jitted shared scoring tail. With ``exact_rerank=C > 0`` each
    dispatch widens the paged search to C candidates and re-scores them
    exactly against the raw-vector tier before returning top-k
    (FusionANNS's final-stage split; scores become exact squared-l2
    distances / inner products).

    Mutations follow the paged rules (side-buffer inserts, tombstone
    deletes); ``swap_index`` requires an explicit new
    :class:`PagedIndexData` generation and atomically retargets the
    cluster cache to it.
    """

    def __init__(self, index, *, exact_rerank: int = 0,
                 side_capacity: int = 256, minor_store=None,
                 minor_name: str = "minors", **kw):
        """Wrap a paged index (or raw :class:`PagedIndexData`).

        Parameters
        ----------
        index : PagedIndexData or PagedJunoIndex
            The paged generation to serve (a bare ``PagedIndexData`` is
            wrapped in a :class:`PagedJunoIndex`).
        exact_rerank : int
            Candidate budget C for the exact-rerank tier (0 disables).
            Requires the index's ``PagedIndexData(vectors=...)`` source.
        side_capacity : int
            Side-buffer capacity when wrapping a bare ``PagedIndexData``.
        minor_store : repro.build.store.ArtifactStore, optional
            With ``max_minors > 0``, promoted minor generations are
            committed through this store and demand-paged back on first
            search touch (per-row sha256-verified) instead of staying
            resident — the out-of-core freshness tier. Default: minors
            stay resident (they are small: B rows each).
        minor_name : str
            Store name minors are committed under.
        **kw
            Remaining :class:`AnnServeEngine` knobs (``metric``,
            ``impl``, ``batch_buckets``, ``fused``, ``prefilter``,
            ``max_minors``, ...).
        """
        if isinstance(index, PagedIndexData):
            index = PagedJunoIndex(index, side_capacity=side_capacity)
        if not isinstance(index, PagedJunoIndex):
            raise TypeError(f"PagedAnnServeEngine serves a PagedIndexData/"
                            f"PagedJunoIndex, got {type(index).__name__}")
        if exact_rerank and index.paged.vectors is None:
            raise ValueError("exact_rerank needs a raw-vector source: "
                             "PagedIndexData(vectors=...)")
        self.exact_rerank = int(exact_rerank)
        if minor_store is not None:
            index._minor_sink = (minor_store, minor_name)
        super().__init__(index, side_capacity=side_capacity, **kw)
        if self.obs is not None:
            index.paged.bind_obs(self.obs)

    def _dispatch(self, qb, k, mode, nprobe, side):
        """One padded batch: filter jit → cache gather → scoring jit."""
        rt_grid, rt_scale = None, 1.0
        prefilter = "scan"
        if self.prefilter == "rt":
            prefilter = "rt"
            rt_grid = self.index.ensure_rt_grid(metric=self.metric)
            rt_scale = self.rt_scale
        p = self.index.data.ivf.point_ids.shape[1]
        kq = k if not self.exact_rerank else min(max(k, self.exact_rerank),
                                                 nprobe * p)
        with self._span("paged.filter", nprobe=nprobe):
            base, cids = _paged_filter(self.index.data.ivf, qb,
                                       nprobe=nprobe, metric=self.metric)
        with self._span("paged.gather"):
            codes = jnp.asarray(self.index.paged.gather(np.asarray(cids)))
        with self._span("paged.score", mode=mode):
            if mode == "H2":
                s, ids = _paged_score_two_stage(
                    self.index.data, qb, base, cids, codes, k=kq,
                    metric=self.metric, thres_scale=self.thres_scale,
                    rerank=self.FUSED_RERANK_MULT * k if self.fused else 0,
                    impl=self.impl, fused=self.fused, fused3=self.fused3,
                    side=side, prefilter=prefilter, rt_grid=rt_grid,
                    rt_scale=rt_scale)
            else:
                s, ids = _paged_score(
                    self.index.data, qb, base, cids, codes, k=kq, mode=mode,
                    metric=self.metric, thres_scale=self.thres_scale,
                    impl=self.impl, side=side, prefilter=prefilter,
                    rt_grid=rt_grid, rt_scale=rt_scale)
        if self.exact_rerank:
            s, ids = self._rerank_exact(qb, ids, k)
        return s, ids

    def _rerank_exact(self, qb, cand_ids, k):
        """Re-score top-C candidates exactly from the raw-vector tier.

        Fetches only the C candidate rows (memory-mapped), computes the
        exact metric on the host, and returns the stable top-k. Sentinel
        ids (< 0, padded probes) score ±inf and sort last; candidate
        *selection* stays the paged search's, only the final order and
        scores are exact.
        """
        ids_np = np.asarray(cand_ids)
        q_np = np.asarray(qb, np.float32)
        vecs = self.index.paged.fetch_vectors(ids_np)        # (Q, C, D)
        ok = ids_np >= 0
        if self.metric == "l2":
            d = np.sum((vecs - q_np[:, None, :]) ** 2, axis=-1)
            d = np.where(ok, d, np.inf)
            order = np.argsort(d, axis=1, kind="stable")[:, :k]
            out_s = np.take_along_axis(d, order, axis=1)
        else:
            sim = np.einsum("qcd,qd->qc", vecs, q_np)
            sim = np.where(ok, sim, -np.inf)
            order = np.argsort(-sim, axis=1, kind="stable")[:, :k]
            out_s = np.take_along_axis(sim, order, axis=1)
        return out_s, np.take_along_axis(ids_np, order, axis=1)

    def compact(self, *, rebuild: bool | str = "auto") -> int:
        """Schedule merge work; never rebuilds in-process.

        The cluster shards are read-only, so folds into the base are
        always no-ops here, and the in-process rebuild the resident
        engine escalates to would need every PQ code resident.
        ``rebuild=True`` raises to make that contract explicit; build
        the next generation offline and :meth:`swap_index` it instead.
        With the LSM tiers enabled (``max_minors > 0``) this drains the
        merge scheduler, which promotes a stuck L0 into an
        artifact-backed minor generation — the paged tier's only
        in-process way to reclaim side-buffer headroom between rebuilds.
        """
        if rebuild is True:
            raise RuntimeError(
                "paged serving cannot rebuild in-process; build the next "
                "generation offline (ArtifactStore.put) and swap_index() "
                "a new PagedIndexData")
        if self.scheduler is not None:
            return self.scheduler.drain()
        return self.index.compact()

    def swap_index(self, new_data=None) -> int:
        """Swap to a new artifact generation, retargeting the cache.

        Unlike the resident engine there is no in-process rebuild
        default — the PQ codes needed to re-encode live out-of-core —
        so ``new_data`` is required: a :class:`PagedIndexData` over the
        next generation (typically ``PagedIndexData(store.path(name,
        store.latest(name)), ...)``). The swap is atomic on the control
        path: the new generation adopts the existing cluster cache with
        all rows dropped (see :meth:`PagedIndexData.adopt_cache`), so
        post-swap requests can never mix generations. Returns the new
        engine generation number.
        """
        if new_data is None:
            raise RuntimeError(
                "paged serving cannot rebuild in-process; pass a "
                "PagedIndexData over the next artifact generation")
        gen = super().swap_index(new_data)
        if self.obs is not None:
            # the adopted cache keeps its registry handles, but the new
            # generation's fetch plane needs its own obs binding
            self.index.paged.bind_obs(self.obs)
        return gen

    def cache_stats(self) -> dict:
        """Paged-tier observability: cache + verify counters
        (see :meth:`PagedIndexData.stats`)."""
        return self.index.paged.stats()
