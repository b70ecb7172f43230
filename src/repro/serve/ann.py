"""Online ANN query serving (`repro.serve.ann`).

Mirrors the fixed-slot design of the LM ``ServeEngine``: requests enter an
async queue and every engine tick drains ONE group of compatible requests
into a single jitted search call. Three mechanisms keep the number of
compiled programs small and the batches dense:

* **Knob quantization** — per-request (k, mode, nprobe) are resolved to a
  small lattice of static jit signatures (``K_BUCKETS × modes ×
  NPROBE_BUCKETS``), so arbitrary client knobs never trigger fresh traces
  on the hot path.
* **Size-bucketed dynamic batching** — queued requests with the same
  resolved signature are coalesced into one batch, padded up to the next
  bucket in ``BATCH_BUCKETS`` (pad rows replicate the last real query, so
  they are in-distribution work whose results are sliced off).
* **Recall-target routing** — ``mode="auto"`` requests are routed to
  L/M/H2/H by the declared ``recall_target``, exposing the paper's
  quality/throughput dial as a per-request SLA knob.
* **Fused two-stage serving** (``fused=True``) — the H and H2 recall
  tiers fold onto one fused-H2 signature served by the fused
  hit-count→masked-ADC scan (``kernels.ops.fused_two_stage_scan``),
  coalescing both tiers' requests into shared batches; see ``__init__``.
* **RT-prefilter serving** (``prefilter="rt"``) — every dispatched
  search masks probes through the sphere-intersection filter
  (``repro.rt``), and the router shrinks each request's probe budget to
  the smallest ``RT_NPROBE_BUCKETS`` entry covering its queries'
  last-surviving-probe ranks (``rt.probe_budget``) — the spatial pruning
  shows up as smaller jitted scans, not just masked lanes
  (docs/serving.md).

The engine owns a :class:`repro.core.MutableJunoIndex`: ``insert``/
``delete``/``compact`` are served between ticks with no rebuild and no
change to any jitted search signature (the side buffer rides along as a
fixed-shape argument).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.juno import (JunoIndexData, MutableIndexBase,
                             MutableJunoIndex, _search_batch,
                             _search_batch_two_stage)
from repro.obs.trace import annotation


@dataclasses.dataclass
class AnnRequest:
    """One queued search request (inputs + engine-filled results).

    The engine stamps a timestamp chain onto every served request —
    ``t_submit`` (queued) → ``t_batch`` (picked into a tick's batch) →
    ``t_compute`` (jitted search returned, host-materialized) →
    ``t_done`` (results sliced back onto the request) — so queue wait,
    compute, and merge time are separable per request (the fleet layer's
    latency histogram is fed from exactly these, see
    ``repro.serve.fleet``).
    """

    rid: int
    queries: np.ndarray                 # (q, D) f32
    k: int = 10
    mode: str = "auto"                  # "H" | "M" | "L" | "H2" | "auto"
    nprobe: int = 0                     # 0 → engine default for the mode
    recall_target: float = 0.9          # router input when mode == "auto"
    # filled in by the engine
    rt_probes: int = -1                 # cached rt survivor count (-1 unset)
    rt_epoch: int = -1                  # index rt_mutations the cache is for
    scores: Optional[np.ndarray] = None
    ids: Optional[np.ndarray] = None
    done: bool = False
    t_submit: float = 0.0
    t_batch: float = 0.0                # batch formation (tick picked it)
    t_compute: float = 0.0              # jitted search done (host-synced)
    t_done: float = 0.0

    @property
    def latency(self) -> float:
        """Submit → completion wall time in seconds."""
        return self.t_done - self.t_submit


class AnnServeEngine:
    """Dynamic-batching ANN serving engine over a mutable JUNO index."""

    K_BUCKETS = (10, 100)
    NPROBE_BUCKETS = (4, 8, 16, 32)
    # extended lattice the rt shrink may route DOWN onto: explicit client
    # knobs still quantize to NPROBE_BUCKETS, but a geometrically prunable
    # request deserves the finer low end (an nprobe-2 signature exists only
    # if the workload produces such queries)
    RT_NPROBE_BUCKETS = (2,) + NPROBE_BUCKETS
    BATCH_BUCKETS = (8, 32, 128)
    MODE_NPROBE = {"L": 8, "M": 8, "H2": 16, "H": 16}
    # recall_target lower bound → mode, checked in order (router table)
    ROUTES = ((0.9, "H"), (0.8, "H2"), (0.5, "M"), (0.0, "L"))
    # fused serving: rerank budget C = FUSED_RERANK_MULT · k for the shared
    # H/H2 fused signature — wide enough that the H tier keeps near-H recall
    # (tests/test_recall_matrix.py pins the floors), small enough that
    # stage 2 stays ≪ stage 1
    FUSED_RERANK_MULT = 32

    def __init__(self, index: JunoIndexData | MutableJunoIndex, *,
                 metric: str = "l2", impl: str | None = None,
                 thres_scale: float = 1.0, side_capacity: int = 256,
                 batch_buckets: tuple[int, ...] | None = None,
                 fused: bool = False, fused3: bool | None = None,
                 prefilter: str = "scan",
                 rt_scale: float = 1.0, max_minors: int = 0,
                 merge_clusters_per_step: int = 32,
                 obs=None):
        """Wrap an index (mutable or not) in a serving engine.

        Parameters
        ----------
        index : JunoIndexData or MutableJunoIndex
            The index to serve (a bare ``JunoIndexData`` is wrapped).
        metric : str
            "l2" | "ip".
        impl : str, optional
            "ref" | "pallas" — forwarded to the search kernels. ``None``
            follows the platform: "pallas" on a TPU, "ref" elsewhere
            (``kernels.ops.default_impl``).
        thres_scale : float
            Selectivity-threshold multiplier forwarded to search.
        side_capacity : int
            Overflow-buffer capacity when wrapping a bare index.
        batch_buckets : tuple of int, optional
            Dynamic-batching bucket sizes (default ``BATCH_BUCKETS``;
            use small buckets on CPU where per-query cost grows with
            batch size).
        fused : bool
            Serve the H and H2 recall tiers through the fused two-stage
            kernel path on ONE shared jit signature (see class notes).
        fused3 : bool, optional
            Three-stage dispatch override (``core.juno.search``): with
            ``fused=True`` and ``prefilter="rt"`` the engine serves the
            three-stage RT→hit-count→ADC kernels by default;
            ``False`` forces the composed rt-mask + two-stage path
            (bit-identical ids/scores — the parity baseline the
            benchmarks gate against).
        prefilter : str
            "scan" | "rt". With "rt" every dispatched search masks
            non-intersecting probes via the sphere-intersection filter
            (``repro.rt``), AND the router shrinks each request's probe
            budget to the smallest ``RT_NPROBE_BUCKETS`` entry covering
            its queries' last-surviving-probe ranks — fewer clusters
            scanned per tick for queries whose sphere the grid prunes
            well.
        rt_scale : float
            Radius knob for "rt" (monotone; large ⇒ no pruning).
        max_minors : int
            With a value > 0, enable the LSM freshness tiers
            (``repro.core.freshness``): a full L0 side buffer is
            promoted into one of up to ``max_minors`` sealed minor
            generations instead of rejecting inserts, and a
            ``MergeScheduler`` folds generations back into the base
            incrementally between ticks. 0 (default) keeps the legacy
            single-SideBuffer behavior.
        merge_clusters_per_step : int
            Fold budget per between-ticks merge step (clusters).
        obs : repro.obs.Observability or bool, optional
            Observability bundle: metrics land in ``obs.registry`` under
            the ``juno_engine_*`` names, engine ticks open nested spans
            in ``obs.tracer``, and ``obs.recall`` (when set) shadows a
            sample of served requests for online recall@k. ``True``
            creates a fresh bundle. Instrumentation is host-side only:
            no jit argument changes, no new signatures, bit-identical
            results (pinned by tests/test_obs.py). Default None = off.
            The engine's live spans reach a recording profiler
            (``jax.profiler``) with or without a bundle.
        """
        # any MutableIndexBase works as the served index: the sharded
        # DistributedMutableIndex flows through here too (the fleet layer's
        # _ShardedAnnServeEngine passes one and overrides _dispatch)
        self.index = (index if isinstance(index, MutableIndexBase)
                      else MutableJunoIndex(index,
                                            side_capacity=side_capacity))
        self.metric = metric
        self.impl = impl
        self.thres_scale = thres_scale
        # observability is opt-in and host-side only (see docstring)
        if obs is True:
            from repro.obs import Observability
            obs = Observability()
        self.obs = obs or None
        #: signatures already traced, keyed (k, mode, nprobe, bucket,
        #: side-is-empty) — drives juno_engine_jit_retraces_total
        self._obs_sigs: set = set()
        if prefilter not in ("scan", "rt"):
            raise ValueError(f"unknown prefilter {prefilter!r}")
        self.prefilter = prefilter
        self.rt_scale = rt_scale
        #: cached (grid, routing_state, rt_mutations) for route(); the
        #: mutation counter invalidates it when inserts grow grid reaches
        self._rt_state = None
        if prefilter == "rt":
            self.index.ensure_rt_grid(metric=metric)
        #: between-ticks merge driver when the LSM tiers are enabled
        self.scheduler = None
        if max_minors:
            from repro.core.freshness import MergeScheduler
            self.index.enable_tiers(max_minors)
            self.scheduler = MergeScheduler(
                self.index, clusters_per_step=merge_clusters_per_step,
                registry=self.obs.registry if self.obs else None)
        #: route the high-recall tiers (H and H2) through the fused
        #: two-stage kernel path: both collapse onto ONE jit signature
        #: (mode "H2", rerank = FUSED_RERANK_MULT·k), so their requests
        #: coalesce into shared batches AND each call replaces the full
        #: masked-ADC scan / wide top-k with the fused hit-count → in-kernel
        #: threshold → compacted-rerank pipeline. H-tier results become
        #: two-stage approximations (recall floors pinned in
        #: tests/test_recall_matrix.py); H2-tier ids are unchanged only in
        #: the candidate-budget sense (C grows from 4k to 32k).
        self.fused = fused
        #: three-stage override forwarded to every H2 dispatch (None =
        #: auto: the three-stage kernel serves fused+rt requests)
        self.fused3 = fused3
        # deployment-tunable: big buckets fill a TPU's batch dim; smaller
        # buckets suit CPU where per-query cost grows with batch size
        self.batch_buckets = tuple(batch_buckets or self.BATCH_BUCKETS)
        self.queue: collections.deque[AnnRequest] = collections.deque()
        self.completed: list[AnnRequest] = []
        self._rid = 0
        #: bumped by every swap_index(); search results served after a
        #: bump come from the new index generation
        self.generation = 0
        self.stats = {"queries": 0, "requests": 0, "ticks": 0,
                      "padded_rows": 0, "inserts": 0, "deletes": 0,
                      "swaps": 0, "signatures": collections.Counter()}

    def _span(self, name: str, trace_id: str = None, **attrs):
        """A tracer span (also a profiler span) when obs is on; otherwise the
        bare profiler span, so a recording profiler sees the engine's spans
        with or without ``obs``."""
        if self.obs is None:
            return annotation(name, trace_id=trace_id, **attrs)
        return self.obs.tracer.span(name, trace_id=trace_id, **attrs)

    # ---- request plane ---------------------------------------------------
    def submit(self, queries, *, k: int = 10, mode: str = "auto",
               nprobe: int = 0, recall_target: float = 0.9) -> AnnRequest:
        """Enqueue a search request; ``step``/``run`` fills its results.

        Parameters
        ----------
        queries : array-like
            (q, D) f32 query rows (a single (D,) vector is promoted).
        k : int
            Results per query (rounded up to a ``K_BUCKETS`` entry).
        mode : str
            "H" | "M" | "L" | "H2", or "auto" to route by
            ``recall_target``.
        nprobe : int
            Explicit probe budget; 0 uses the mode default
            (``MODE_NPROBE``), then rounds onto ``NPROBE_BUCKETS``.
        recall_target : float
            Router input for ``mode="auto"`` (the per-request SLA knob).

        Returns
        -------
        AnnRequest
            The queued request; after serving, ``.scores``/``.ids`` are
            (q, k) arrays and ``.done`` is True.
        """
        req = AnnRequest(rid=self._rid, queries=np.atleast_2d(
            np.asarray(queries, np.float32)), k=k, mode=mode, nprobe=nprobe,
            recall_target=recall_target, t_submit=time.perf_counter())
        self._rid += 1
        self.queue.append(req)
        return req

    @property
    def queued_rows(self) -> int:
        """Total query rows currently waiting in this engine's queue.

        The fleet router's load signal: least-outstanding-rows balancing
        (``repro.serve.fleet``) routes each new request to the replica
        whose engine reports the smallest value here.
        """
        return sum(r.queries.shape[0] for r in self.queue)

    def route(self, req: AnnRequest) -> tuple[int, str, int]:
        """Resolve per-request knobs to one static jit signature.

        With ``fused=True`` the H recall tier folds into the H2 signature
        (see ``__init__``), so H and H2 requests batch together. With
        ``prefilter="rt"`` the probe budget additionally shrinks to the
        smallest bucket covering the request's rt survivor counts — the
        RT filter's throughput win on a batch-oriented backend: clusters
        the sphere test prunes are not merely masked, the whole jitted
        scan runs at a smaller nprobe.

        Parameters
        ----------
        req : AnnRequest
            The request to resolve (its ``rt_probes`` cache is filled on
            first call).

        Returns
        -------
        tuple
            ``(k, mode, nprobe)`` — one point of the static signature
            lattice.
        """
        mode = req.mode
        if mode == "auto":
            mode = next(m for lo, m in self.ROUTES if req.recall_target >= lo)
        if self.fused and mode == "H":
            mode = "H2"
        k = next((b for b in self.K_BUCKETS if b >= req.k), None) or req.k
        nprobe = req.nprobe or self.MODE_NPROBE[mode]
        nprobe = next((b for b in self.NPROBE_BUCKETS if b >= nprobe),
                      self.NPROBE_BUCKETS[-1])
        if self.prefilter == "rt":
            muts = getattr(self.index, "rt_mutations", 0)
            if req.rt_probes < 0 or req.rt_epoch != muts:
                # a request's cached probe budget is only valid for the
                # index mutation state it was computed against: inserts
                # grow grid reaches, so a budget cached before an insert
                # would under-probe the freshly inserted points
                from repro import rt as rt_lib
                # rebuilt lazily after swap_index() dropped it
                grid = self.index.ensure_rt_grid(metric=self.metric)
                if (self._rt_state is None or self._rt_state[0] is not grid
                        or self._rt_state[2] != muts):
                    # inserts replace the grid object (update_radii), so
                    # identity plus the mutation counter keys the cached
                    # host routing state
                    self._rt_state = (grid, rt_lib.routing_state(
                        grid, self.index.data), muts)
                with self._span("engine.rt_probe", trace_id=str(req.rid),
                                rows=req.queries.shape[0]):
                    req.rt_probes = int(rt_lib.probe_budget(
                        grid, self.index.data, req.queries,
                        metric=self.metric, scale=self.rt_scale,
                        thres_scale=self.thres_scale,
                        max_probes=nprobe, state=self._rt_state[1]).max())
                req.rt_epoch = muts
            shrunk = next((b for b in self.RT_NPROBE_BUCKETS
                           if b >= max(req.rt_probes, 1)),
                          self.RT_NPROBE_BUCKETS[-1])
            nprobe = min(nprobe, shrunk)
        nprobe = min(nprobe, self.index.data.ivf.centroids.shape[0])
        return k, mode, nprobe

    # ---- engine ticks ----------------------------------------------------
    def step(self) -> int:
        """Serve one signature group in one jitted call. Returns #queries."""
        if not self.queue:
            return 0
        if self.obs is not None:
            # queue depth sampled at tick entry; agg="sum" so the fleet
            # view adds replicas' backlogs instead of picking one
            self.obs.registry.gauge("juno_engine_queue_rows",
                                    agg="sum").set(self.queued_rows)
        with self._span("engine.tick"):
            return self._step_inner()

    def _step_inner(self) -> int:
        """One tick's pick → dispatch → merge body (inside the tick span).

        Each dispatch splits into ``engine.launch`` (host-to-device copy and
        the jitted call) and ``engine.readback`` (waiting for the device and
        copying the answers back), so an idle device is pinned on one.
        """
        with self._span("engine.pick"):
            sig = self.route(self.queue[0])
            max_rows = self.batch_buckets[-1]
            # one linear pass: pick head-signature requests FIFO until the
            # batch budget closes; everything else keeps its order for later
            picked, rest, rows, closed = [], [], 0, False
            for req in self.queue:
                if closed or self.route(req) != sig:
                    rest.append(req)
                    continue
                if picked and rows + req.queries.shape[0] > max_rows:
                    closed = True     # preserve FIFO within the signature
                    rest.append(req)
                    continue
                picked.append(req)
                rows += req.queries.shape[0]
            self.queue = collections.deque(rest)
            t_batch = time.perf_counter()   # batch formed; queue wait ends

            k, mode, nprobe = sig
            batch = np.concatenate([r.queries for r in picked], axis=0)
            # an empty delta tier contributes nothing: drop the argument so
            # the jitted program skips side scoring entirely (side=None and
            # side≠None are separate traces; crossing over costs one
            # compile, not a rebuild). With the LSM tiers enabled this is
            # the combined fixed-capacity L0 ⊕ minors view, so merge cycles
            # never retrace.
            side = self.index.delta_view()
        # a single request larger than the top bucket is served in top-bucket
        # chunks, so the jit-signature lattice stays closed for any request
        out_s, out_i = [], []
        for lo in range(0, rows, max_rows):
            chunk = batch[lo:lo + max_rows]
            n = chunk.shape[0]
            bucket = next(b for b in self.batch_buckets if b >= n)
            if n < bucket:  # in-distribution pad rows (see module docstring)
                chunk = np.pad(chunk, ((0, bucket - n), (0, 0)), mode="edge")
            if self.obs is not None:
                self._observe_dispatch(k, mode, nprobe, bucket, n,
                                       side is None)
            with self._span("engine.dispatch", mode=mode, k=k,
                            nprobe=nprobe, bucket=bucket, rows=n):
                with self._span("engine.launch"):
                    s, ids = self._dispatch(jnp.asarray(chunk), k, mode,
                                            nprobe, side)
                with self._span("engine.readback"):
                    out_s.append(np.asarray(s)[:n])
                    out_i.append(np.asarray(ids)[:n])
            self.stats["padded_rows"] += bucket - n
            self.stats["signatures"][(k, mode, nprobe, bucket)] += 1
        # np.asarray above forced host materialization, so this bounds the
        # jitted compute (incl. device->host) for every request in the tick
        t_compute = time.perf_counter()
        s, ids = np.concatenate(out_s), np.concatenate(out_i)

        with self._span("engine.merge", requests=len(picked)):
            off, now = 0, time.perf_counter()
            for req in picked:
                q = req.queries.shape[0]
                req.scores = s[off:off + q, :req.k]
                req.ids = ids[off:off + q, :req.k]
                req.t_batch, req.t_compute = t_batch, t_compute
                req.done, req.t_done = True, now
                off += q
                self.completed.append(req)
        self.stats["queries"] += rows
        self.stats["requests"] += len(picked)
        self.stats["ticks"] += 1
        if self.obs is not None:
            self._observe_served(picked, mode, rows)
        if self.scheduler is not None:
            # background merge: one bounded step between ticks (the same
            # control-path hook pattern as swap_index), so promotions and
            # folds amortize across serving instead of stopping the world
            self.scheduler.maybe_step()
        return rows

    def _dispatch(self, qb, k, mode, nprobe, side):
        """Run one padded batch through the jitted search for its mode."""
        fn, kw = self.search_call(k, mode, nprobe, side)
        return fn(self.index.data, qb, **kw)

    def search_call(self, k, mode, nprobe, side):
        """The jitted search a dispatch of this signature runs, and its
        keyword arguments: ``fn(index.data, queries, **kw)``. Exposed so a
        caller can ``fn.lower(...)`` exactly what the engine serves."""
        kw = dict(k=k, nprobe=nprobe, metric=self.metric,
                  thres_scale=self.thres_scale, impl=self.impl, side=side)
        if self.prefilter == "rt":
            kw.update(prefilter="rt",
                      rt_grid=self.index.ensure_rt_grid(metric=self.metric),
                      rt_scale=self.rt_scale)
        if mode == "H2":
            return _search_batch_two_stage, dict(
                kw, fused=self.fused, fused3=self.fused3,
                rerank=self.FUSED_RERANK_MULT * k if self.fused else 0)
        return _search_batch, dict(kw, mode=mode)

    def _observe_dispatch(self, k, mode, nprobe, bucket, n, empty_side):
        """Record per-dispatch registry metrics (obs is known non-None).

        Batch occupancy lands in ``juno_engine_batch_fill_ratio``; the
        first time a (signature, side-emptiness) combination is
        dispatched it counts as a jit retrace
        (``juno_engine_jit_retraces_total``) — side=None and side≠None
        are separate traces, so emptiness is part of the key.
        """
        reg = self.obs.registry
        reg.histogram("juno_engine_batch_fill_ratio", lo=1e-3, hi=1.0,
                      mode=mode).add(n / bucket)
        sig_key = (k, mode, nprobe, bucket, empty_side)
        if sig_key not in self._obs_sigs:
            self._obs_sigs.add(sig_key)
            reg.counter("juno_engine_jit_retraces_total").inc()

    def _observe_served(self, picked, mode, rows):
        """Record per-request metrics + spans for one served tick.

        Feeds the per-tier latency histograms, retro-stamps one
        ``engine.enqueue`` span per request (submit → batch formation,
        i.e. queue wait), and hands a sample of requests to the recall
        probe when the bundle carries one.
        """
        reg, tracer = self.obs.registry, self.obs.tracer
        reg.counter("juno_engine_ticks_total").inc()
        reg.counter("juno_engine_queries_total").inc(rows)
        reg.counter("juno_engine_requests_total", mode=mode).inc(len(picked))
        lat = reg.histogram("juno_engine_request_seconds", mode=mode)
        h_queue = reg.histogram("juno_engine_queue_seconds")
        h_compute = reg.histogram("juno_engine_compute_seconds")
        h_merge = reg.histogram("juno_engine_merge_seconds")
        for req in picked:
            lat.add(req.latency)
            h_queue.add(req.t_batch - req.t_submit)
            h_compute.add(req.t_compute - req.t_batch)
            h_merge.add(req.t_done - req.t_compute)
            tracer.record("engine.enqueue", req.t_submit, req.t_batch,
                          trace_id=str(req.rid),
                          rows=req.queries.shape[0], mode=mode)
            if self.obs.recall is not None:
                self.obs.recall.observe(req, mode)

    def run(self, max_ticks: int = 100_000) -> int:
        """Drain the queue; returns total queries served."""
        total = 0
        for _ in range(max_ticks):
            if not self.queue:
                break
            total += self.step()
        return total

    # ---- mutation plane (control path, between ticks) --------------------
    def insert(self, points) -> list[int]:
        """Insert a (B, D) point batch into the served index.

        Runs between ticks on the control path — no rebuild, no jit
        signature change (see :class:`repro.core.MutableJunoIndex`).
        Returns the assigned global ids.
        """
        ids = self.index.insert(points)
        self.stats["inserts"] += len(ids)
        if self.obs is not None:
            self.obs.registry.counter(
                "juno_engine_inserts_total").inc(len(ids))
        return ids

    def delete(self, ids) -> int:
        """Tombstone points by global id; returns how many were removed.

        All-or-nothing: an unknown or duplicated id raises before any
        state is touched.
        """
        n = self.index.delete(ids)
        self.stats["deletes"] += n
        if self.obs is not None:
            self.obs.registry.counter("juno_engine_deletes_total").inc(n)
        return n

    def compact(self, *, rebuild: bool | str = "auto") -> int:
        """Schedule merge work instead of rebuilding the world.

        With the LSM tiers enabled (``max_minors > 0``) this drains the
        merge scheduler: L0 folds into free base slots, full L0s promote
        into minor generations, and generations fold incrementally into
        the base — a :meth:`swap_index` rebuild happens only when the
        tiers themselves are exhausted (every minor slot taken AND the
        stuck points' clusters full). Without tiers it keeps the legacy
        behavior: fold spills into already-free slots (a search no-op by
        construction), then — with ``rebuild="auto"`` (default) — any
        spills that remain stuck trigger the full rebuild so the side
        buffer always ends empty. ``rebuild=True`` forces the rebuild,
        ``rebuild=False`` never rebuilds.

        Parameters
        ----------
        rebuild : bool or "auto"
            Rebuild policy for stuck spills (see above).

        Returns
        -------
        int
            Total points moved between tiers.
        """
        if self.scheduler is not None:
            moved = self.scheduler.drain()
        else:
            moved = self.index.compact()
        stuck = self.index.side_fill
        if rebuild is True or (rebuild == "auto" and stuck):
            self.swap_index()
            moved += stuck
        return moved

    def swap_index(self, new_data=None) -> int:
        """Atomically install a rebuilt index — zero-downtime hot swap.

        Runs on the control path between ticks: requests completed
        before the call were served by the old generation, anything
        still queued (and everything after) is served by the new one,
        and no request ever observes a half-installed index. With the
        default rebuild, the side buffer is drained into the new index
        (spills re-encoded into proper cluster slots), tombstones are
        dropped, and results are preserved; the rt grid and router
        state are invalidated and rebuilt lazily, and the jitted search
        signatures stay warm whenever the rebuild kept the padded
        capacity unchanged.

        Parameters
        ----------
        new_data : JunoIndexData, optional
            The replacement index. Default: rebuild from the live state
            (``repro.build.rebuild.rebuild_index``), which preserves
            every live point. A caller-supplied index (e.g. loaded from
            a ``repro.build.store`` artifact) REPLACES the serving
            state wholesale: the side buffer and bookkeeping are reset
            to exactly what ``new_data`` contains, so any live
            mutations not already reflected in it are discarded — the
            caller owns that consistency (rebuild into the artifact
            first, or replay the mutation log after the swap).

        Returns
        -------
        int
            The new generation number.
        """
        if new_data is None:
            from repro.build.rebuild import rebuild_index
            new_data = rebuild_index(self.index)
        self.index.swap_data(new_data)
        self._rt_state = None    # routing snapshot belongs to the old grid
        self.generation += 1
        self.stats["swaps"] += 1
        if self.obs is not None:
            self.obs.registry.counter("juno_engine_swaps_total").inc()
        return self.generation
