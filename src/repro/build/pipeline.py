"""Streaming, memory-bounded JUNO index construction (`repro.build.pipeline`).

The in-memory ``core.build`` holds the full (N, D) point set plus every
intermediate (residuals, codes) at once — fine at 10^5 points, hopeless at
the paper's 10^7-10^8. This pipeline makes two passes over a re-iterable
chunk source and never materialises more than one chunk of raw points (plus
the bounded training sample):

pass 1  reservoir-sample ``max_train_points`` rows (uniform, deterministic)
        and count N. Train IVF centroids (``kmeans_subsampled``) and the
        residual PQ codebook on the sample; fix the density grid's bounding
        box from the sample's residual projections; draw the calibration
        queries from the sample. When the sample covers the whole set
        (N <= max_train_points) AND no cluster overflows its padded
        capacity, training, box and queries match the in-memory build bit
        for bit; an overflow spill keeps recall parity but not bit
        identity (``core.build`` retrains on post-spill residuals, the
        stream trains pre-spill and patches — see pass 3).

pass 2  per chunk, under one jit: chunked assignment (the ``|x-c|^2``
        MXU expansion), residual PQ encoding, density-histogram
        accumulation, ``|p|^2`` — while a streaming exact top-k merge
        accumulates the calibration queries' ground truth. Only O(N)
        bytes of codes/labels accumulate on the host.

finalize  padded cluster layout (shared ``ivf.padded_layout`` spill pass),
        threshold-regressor fit on the streamed grid
        (``density.calibrate_from_grid``), bit-compatible
        :class:`repro.core.juno.JunoIndexData` out.

Every chunk that enters the pipeline is recorded on a :class:`BuildProbe`,
so tests assert the memory bound structurally (max resident chunk rows)
instead of scraping RSS.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import density as density_lib
from repro.core.ivf import IVFIndex, cluster_capacity, padded_layout
from repro.core.juno import (JunoConfig, JunoIndexData, _calib_query_subspaces,
                             _calib_tau_needed)
from repro.core.kmeans import kmeans_subsampled
from repro.core.pq import encode, split_subspaces, train_codebook

#: rows per jitted encode call inside a chunk (bounds the (B, C) distance
#: matrix at ~`_EVAL_ROWS * C * 4` bytes regardless of the chunk budget)
_EVAL_ROWS = 8192


@dataclasses.dataclass
class BuildProbe:
    """Structural memory-bound instrumentation for the streaming build.

    Attributes
    ----------
    passes : int
        Completed passes over the chunk source (2 for a spill-free
        build; 3 when overflow spill forced targeted re-encoding).
    chunks : int
        Total chunks consumed across all passes.
    max_chunk_rows : int
        Largest single chunk seen — the raw-point residency bound: the
        pipeline never holds more than this many (D,)-rows of input at
        once beyond the training sample.
    train_rows : int
        Rows held in the bounded training sample (<= max_train_points).
    n_points : int
        Total rows streamed (N).
    """

    passes: int = 0
    chunks: int = 0
    max_chunk_rows: int = 0
    train_rows: int = 0
    n_points: int = 0

    def note_chunk(self, rows: int) -> None:
        """Record one consumed chunk of ``rows`` points."""
        self.chunks += 1
        self.max_chunk_rows = max(self.max_chunk_rows, rows)


def array_source(points, chunk_points: int = 65536
                 ) -> Callable[[], Iterator[np.ndarray]]:
    """Wrap an in-memory / memory-mapped (N, D) array as a chunk source.

    Parameters
    ----------
    points : array-like
        (N, D) array; ``np.memmap`` works — slices are materialised one
        chunk at a time.
    chunk_points : int
        Rows per yielded chunk.

    Returns
    -------
    callable
        Zero-arg callable returning a fresh chunk iterator (the pipeline
        makes two passes, so the source must be re-iterable).
    """
    def it() -> Iterator[np.ndarray]:
        n = points.shape[0]
        for lo in range(0, n, chunk_points):
            yield np.asarray(points[lo:lo + chunk_points], np.float32)
    return it


def _chunks(source) -> Iterator[np.ndarray]:
    """One pass over a chunk source (callable or re-iterable)."""
    it: Iterable = source() if callable(source) else source
    for chunk in it:
        arr = np.asarray(chunk, np.float32)
        if arr.ndim != 2:
            raise ValueError(f"chunk must be (B, D), got {arr.shape}")
        if arr.shape[0]:
            yield arr


def _reservoir_extend(sample: np.ndarray, fill: int, seen: int,
                      chunk: np.ndarray, rng: np.random.Generator
                      ) -> tuple[int, int]:
    """Vectorised reservoir sampling (algorithm R) over one chunk.

    Mutates ``sample`` in place; returns the new (fill, seen). While the
    reservoir is not yet full, rows are appended in stream order — so for
    N <= capacity the sample IS the stream, and sample-trained stages
    match the in-memory build bit for bit.
    """
    cap = sample.shape[0]
    b = chunk.shape[0]
    take = min(cap - fill, b)
    if take:
        sample[fill:fill + take] = chunk[:take]
        fill += take
    if take < b:
        rest = chunk[take:]
        idx = seen + take + np.arange(rest.shape[0])
        accept = rng.integers(0, idx + 1) < cap
        slots = rng.integers(0, cap, size=int(accept.sum()))
        sample[slots] = rest[accept]
    return fill, seen + b


@jax.jit
def _encode_chunk(pts, centroids, codebook, counts, lo, hi, n_valid):
    """labels, codes, density counts and |p|^2 for one padded chunk.

    One jitted program per (chunk-shape) signature: nearest-centroid
    assignment via the MXU expansion, residual PQ encode, histogram
    accumulation (pad rows weighted out), squared norms.
    """
    c_sq = jnp.sum(centroids * centroids, axis=-1)
    d = c_sq[None, :] - 2.0 * pts @ centroids.T
    labels = jnp.argmin(d, axis=-1).astype(jnp.int32)
    res = pts - centroids[labels]
    codes = encode(res, codebook)
    sub = jnp.swapaxes(split_subspaces(res, codebook.sub_dim), 0, 1)
    w = (jnp.arange(pts.shape[0]) < n_valid).astype(jnp.float32)
    counts = density_lib.accumulate_density_counts(counts, sub, lo, hi, w)
    return labels, codes, counts, jnp.sum(pts * pts, axis=-1)


@functools.partial(jax.jit, static_argnames=("metric",))
def _merge_topk(best_s, best_i, queries, chunk_pts, base, n_valid, *, metric):
    """Fold one chunk into the calibration queries' running exact top-k.

    Same internal score convention and matmul precision as
    ``core.ref.exact_topk`` (l2 drops the |q|^2 rank-only term; internally
    higher-is-better), so the merged ground-truth ids match the oracle's.
    """
    dots = jnp.matmul(queries, chunk_pts.T,                  # (Q, B)
                      precision=jax.lax.Precision.HIGHEST)
    if metric == "l2":
        p_sq = jnp.sum(chunk_pts * chunk_pts, axis=-1)
        scores = -(p_sq[None, :] - 2.0 * dots)
    else:
        scores = dots
    b = chunk_pts.shape[0]
    ids = base + jnp.arange(b, dtype=jnp.int32)[None, :]
    scores = jnp.where(jnp.arange(b)[None, :] < n_valid, scores, -jnp.inf)
    cat_s = jnp.concatenate([best_s, scores], axis=1)
    cat_i = jnp.concatenate(
        [best_i, jnp.broadcast_to(ids, (best_s.shape[0], b))], axis=1)
    top_s, sel = jax.lax.top_k(cat_s, best_s.shape[1])
    return top_s, jnp.take_along_axis(cat_i, sel, axis=1)


class _EvalBatcher:
    """Regroup arbitrary chunk sizes into fixed ``_EVAL_ROWS`` jit batches.

    At most two jit signatures exist per build: the full eval batch and
    one final partial flush — chunk-size heterogeneity never retraces.
    """

    def __init__(self, d: int, rows: int = _EVAL_ROWS):
        self.buf = np.empty((rows, d), np.float32)
        self.fill = 0

    def feed(self, chunk: np.ndarray):
        """Yield (batch, n_valid) eval batches as the chunk fills them."""
        pos = 0
        rows = self.buf.shape[0]
        while pos < chunk.shape[0]:
            take = min(rows - self.fill, chunk.shape[0] - pos)
            self.buf[self.fill:self.fill + take] = chunk[pos:pos + take]
            self.fill += take
            pos += take
            if self.fill == rows:
                yield self.buf, rows
                self.fill = 0

    def flush(self):
        """Yield the final partial batch, edge-padded to a static shape."""
        if self.fill:
            self.buf[self.fill:] = self.buf[self.fill - 1]
            yield self.buf, self.fill
            self.fill = 0


def _gather_rows(source, ids: np.ndarray, probe: BuildProbe) -> np.ndarray:
    """Fetch specific rows (sorted global ids) in one extra streaming pass.

    Used to re-encode overflow-spilled points; residency is bounded by one
    chunk plus the (small) requested row set.
    """
    ids = np.asarray(ids)
    out = np.empty((ids.shape[0], 0), np.float32)
    base = 0
    filled = False
    for chunk in _chunks(source):
        probe.note_chunk(chunk.shape[0])
        if not filled:
            out = np.empty((ids.shape[0], chunk.shape[1]), np.float32)
            filled = True
        lo = np.searchsorted(ids, base)
        hi = np.searchsorted(ids, base + chunk.shape[0])
        if hi > lo:
            out[lo:hi] = chunk[ids[lo:hi] - base]
        base += chunk.shape[0]
    probe.passes += 1
    return out


def build_streaming(source, config: JunoConfig, *,
                    key: jax.Array | None = None,
                    probe: BuildProbe | None = None) -> JunoIndexData:
    """Out-of-core offline build: chunked two-pass JUNO construction.

    Produces a :class:`repro.core.juno.JunoIndexData` bit-compatible with
    ``core.build`` (identical array shapes/dtypes; H-tier recall within
    the in-memory build's on the same data — tests/test_build.py pins
    0.01; bit-identical arrays only in the spill-free N <=
    ``max_train_points`` regime, see the module docstring) while the raw
    point set is only ever resident one chunk at a time plus the bounded
    training sample.

    Parameters
    ----------
    source : callable or iterable
        Chunk source yielding (B, D) float arrays. A callable is invoked
        once per pass (the pipeline makes two); a plain iterable must be
        re-iterable (e.g. a list of arrays — NOT a generator).
        :func:`array_source` adapts arrays/memmaps.
    config : JunoConfig
        Build-time knobs; ``max_train_points`` bounds the training
        sample (<= 0 falls back to 200_000 — a streaming build cannot
        train on "all" points).
    key : jax.Array, optional
        PRNG key (default ``PRNGKey(0)``), split exactly as
        ``core.build`` splits it.
    probe : BuildProbe, optional
        Filled with chunk/pass/residency counters for memory-bound
        assertions.

    Returns
    -------
    JunoIndexData
        The built index.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    probe = probe if probe is not None else BuildProbe()
    k_ivf, k_pq, k_cal = jax.random.split(key, 3)
    t_max = config.max_train_points if config.max_train_points > 0 else 200_000

    # ---- pass 1: reservoir sample + count --------------------------------
    sample = None
    fill = seen = 0
    rng = np.random.default_rng(
        int(np.asarray(jax.random.randint(jax.random.fold_in(k_ivf, 17), (),
                                          0, 2 ** 31 - 1))))
    for chunk in _chunks(source):
        probe.note_chunk(chunk.shape[0])
        if sample is None:
            sample = np.empty((t_max, chunk.shape[1]), np.float32)
        fill, seen = _reservoir_extend(sample, fill, seen, chunk, rng)
    if sample is None:
        raise ValueError("empty point source")
    probe.passes += 1
    n, d = seen, sample.shape[1]
    sample = sample[:fill]
    probe.train_rows = fill
    probe.n_points = n
    s = d // config.sub_dim

    # ---- train on the sample --------------------------------------------
    sample_j = jnp.asarray(sample)
    st = kmeans_subsampled(sample_j, n_clusters=config.n_clusters,
                           n_iters=config.kmeans_iters, key=k_ivf,
                           max_train_points=t_max)
    centroids = st.centroids
    # sample residuals train the PQ codebook and fix the density box
    c_sq = jnp.sum(centroids * centroids, axis=-1)
    s_labels = jnp.argmin(c_sq[None, :] - 2.0 * sample_j @ centroids.T,
                          axis=-1)
    s_res = sample_j - centroids[s_labels]
    codebook = train_codebook(s_res, n_entries=config.n_entries,
                              m=config.sub_dim,
                              n_iters=config.kmeans_iters, key=k_pq)
    s_sub = jnp.swapaxes(split_subspaces(s_res, config.sub_dim), 0, 1)
    dens_lo = jnp.min(s_sub, axis=1)                         # (S, M)
    dens_hi = jnp.max(s_sub, axis=1)

    # calibration queries from the sample (== the full set when it fits)
    nq = min(config.calib_queries, fill)
    k_choice, k_noise = jax.random.split(k_cal)
    qidx = jax.random.choice(k_choice, fill, shape=(nq,), replace=False)
    noise = (0.01 * jax.random.normal(k_noise, (nq, d))
             * jnp.std(sample_j))
    queries = sample_j[qidx] + noise.astype(jnp.float32)

    # ---- pass 2: encode + density + streaming ground truth ---------------
    counts = jnp.zeros((s, config.grid_size, config.grid_size), jnp.float32)
    kcal = min(config.calib_topk, n)
    best_s = jnp.full((nq, kcal), -jnp.inf)
    best_i = jnp.full((nq, kcal), -1, jnp.int32)
    labels_all = np.empty((n,), np.int32)
    codes_all = np.empty((n, s), np.uint8)
    psq_all = np.empty((n,), np.float32)
    batcher = _EvalBatcher(d)
    pos = 0

    def eat(batch: np.ndarray, n_valid: int):
        nonlocal counts, best_s, best_i, pos
        bj = jnp.asarray(batch)
        labels, codes, counts, psq = _encode_chunk(
            bj, centroids, codebook, counts, dens_lo, dens_hi, n_valid)
        best_s, best_i = _merge_topk(best_s, best_i, queries, bj,
                                     pos, n_valid, metric=config.metric)
        labels_all[pos:pos + n_valid] = np.asarray(labels[:n_valid])
        codes_all[pos:pos + n_valid] = np.asarray(codes[:n_valid])
        psq_all[pos:pos + n_valid] = np.asarray(psq[:n_valid])
        pos += n_valid

    for chunk in _chunks(source):
        probe.note_chunk(chunk.shape[0])
        for batch, n_valid in batcher.feed(chunk):
            eat(batch, n_valid)
    for batch, n_valid in batcher.flush():
        eat(batch, n_valid)
    probe.passes += 1
    if pos != n:
        raise ValueError(
            f"source yielded {pos} rows on pass 2 but {n} on pass 1 — "
            "the chunk source must be re-iterable and stable")

    # ---- finalize: layout + density model --------------------------------
    cap = cluster_capacity(n, config.n_clusters, config.capacity_mult)
    labels_pre = labels_all.copy()
    point_ids, labels_all = padded_layout(labels_all, config.n_clusters, cap)
    # overflow spill moved some points to an adoptive cluster: their codes
    # must be residuals w.r.t. the OWNING centroid (the in-memory build
    # encodes after the spill pass). A targeted third pass re-fetches just
    # those rows and patches codes + density counts.
    changed = np.nonzero(labels_pre != labels_all)[0]
    if changed.size:
        rows = _gather_rows(source, changed, probe)
        rows_j = jnp.asarray(rows)
        old_res = rows_j - centroids[labels_pre[changed]]
        new_res = rows_j - centroids[labels_all[changed]]
        codes_all[changed] = np.asarray(encode(new_res, codebook))
        sub_old = jnp.swapaxes(split_subspaces(old_res, config.sub_dim), 0, 1)
        sub_new = jnp.swapaxes(split_subspaces(new_res, config.sub_dim), 0, 1)
        neg = jnp.full((changed.size,), -1.0, jnp.float32)
        counts = density_lib.accumulate_density_counts(
            counts, sub_old, dens_lo, dens_hi, neg)
        counts = density_lib.accumulate_density_counts(
            counts, sub_new, dens_lo, dens_hi, -neg)
    point_ids = jnp.asarray(point_ids)
    ivf = IVFIndex(centroids=centroids, centroid_sq=c_sq,
                   point_ids=point_ids, valid=point_ids >= 0,
                   labels=jnp.asarray(labels_all))
    codes = jnp.asarray(codes_all)
    safe_ids = jnp.maximum(ivf.point_ids, 0)
    cluster_codes = codes[safe_ids]

    grid = density_lib.density_grid_from_counts(counts, dens_lo, dens_hi)
    qsub = _calib_query_subspaces(queries, ivf, config)
    gt_codes = codes[best_i].astype(jnp.int32)               # (nq, K, S)
    tau_needed = _calib_tau_needed(qsub, gt_codes, codebook, config.metric)
    dens_model = density_lib.calibrate_from_grid(
        grid, dens_lo, dens_hi, qsub, tau_needed, degree=config.poly_degree)

    return JunoIndexData(ivf=ivf, codebook=codebook, codes=codes,
                         cluster_codes=cluster_codes, density=dens_model,
                         points_sq=jnp.asarray(psq_all))


def split_shards(data: JunoIndexData, n_shards: int) -> list[JunoIndexData]:
    """Slice a built index into cluster-partitioned per-shard parts.

    Shard ``i`` owns clusters ``[i*C/n .. (i+1)*C/n)`` — exactly the rows
    ``dist.shard_index`` would place on mesh position ``i`` — with the
    codebook, density model, flat codes and GLOBAL labels/ids replicated,
    so each part can be stored and shipped as its own artifact and
    :func:`merge_shards` reassembles the global index losslessly.

    Parameters
    ----------
    data : JunoIndexData
        A built index.
    n_shards : int
        Shard count; must divide ``n_clusters``.

    Returns
    -------
    list of JunoIndexData
        One cluster-sliced part per shard.
    """
    c = data.ivf.centroids.shape[0]
    if c % n_shards:
        raise ValueError(f"clusters ({c}) must divide over {n_shards} shards")
    cl = c // n_shards
    out = []
    for i in range(n_shards):
        sl = slice(i * cl, (i + 1) * cl)
        out.append(data._replace(
            ivf=data.ivf._replace(
                centroids=data.ivf.centroids[sl],
                centroid_sq=data.ivf.centroid_sq[sl],
                point_ids=data.ivf.point_ids[sl],
                valid=data.ivf.valid[sl]),
            cluster_codes=data.cluster_codes[sl]))
    return out


def merge_shards(parts: list[JunoIndexData]) -> JunoIndexData:
    """Reassemble :func:`split_shards` parts into one global index.

    Parameters
    ----------
    parts : list of JunoIndexData
        Cluster-sliced parts in shard order (replicated components are
        taken from part 0).

    Returns
    -------
    JunoIndexData
        The concatenated global index.
    """
    first = parts[0]
    cat = lambda f: jnp.concatenate([getattr(p.ivf, f) for p in parts])  # noqa: E731
    return first._replace(
        ivf=first.ivf._replace(
            centroids=cat("centroids"), centroid_sq=cat("centroid_sq"),
            point_ids=cat("point_ids"), valid=cat("valid")),
        cluster_codes=jnp.concatenate([p.cluster_codes for p in parts]))


def build_streaming_sharded(source, config: JunoConfig, n_shards: int, **kw
                            ) -> list[JunoIndexData]:
    """Streaming build that emits per-shard indices for ``repro.dist``.

    Runs :func:`build_streaming` once, then cluster-partitions the result
    (:func:`split_shards`); each part is ready to be persisted as its own
    artifact (``store.save_index`` with a shard tag in ``extra``) and
    reassembled with :func:`merge_shards` before ``dist.shard_index``.

    Parameters
    ----------
    source : callable or iterable
        Re-iterable chunk source (see :func:`build_streaming`).
    config : JunoConfig
        Build-time knobs; ``n_clusters`` must divide over ``n_shards``.
    n_shards : int
        Number of cluster partitions to emit.
    **kw
        Forwarded to :func:`build_streaming` (``key``, ``probe``).

    Returns
    -------
    list of JunoIndexData
        One part per shard, in shard order.
    """
    return split_shards(build_streaming(source, config, **kw), n_shards)
