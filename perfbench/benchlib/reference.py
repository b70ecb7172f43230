"""Plain reference of the JUNO "H2" query path, written from its semantics.

It imports nothing of the program. It reads the served index as data,
through :class:`IndexState`: the centroids, the PQ codebook, the density
threshold model, the cluster layout (point ids per padded slot), each
point's codes and cluster. For a block of queries it computes, in straightforward jnp:

1. stage A: the ``nprobe`` best centroids by ``|c|^2 - 2 q.c`` (l2) or
   ``q.c`` (ip), at the device's default matmul precision, which the
   configuration states for stage A: on a TPU the operands rounded to bf16
   and the products summed in f32 (``stage_a="bf16"``), elsewhere f32
   (``stage_a="f32"``); ties go to the lower cluster id;
2. per probe, the query's subspace projections (residual to the probed
   centroid for l2, the raw query for ip), the density-model threshold
   ``tau`` per subspace, and per (subspace, entry) the LUT value and the
   hit code: +1 inside ``tau/2``, 0 inside ``tau``, -1 outside;
3. each probed slot's hit count, the ``4k`` best counts (ties to the lower
   probe-major slot index), their masked-LUT distance (pruned entries count
   ``tau^2`` for l2, the row's worst kept similarity for ip; ip adds the
   stage-A ``q.c``), and the top ``k`` of those.

:func:`score_of` gives the same masked-LUT score for any (query, id) pair,
which is how a returned id's score is checked whatever probes found it.

``precision`` sets how the LUT's products ``r_s . e`` are formed: "f32"
(the configuration's precision) or "bf16x3", the three-pass bf16 product a
TPU forms at ``Precision.HIGH`` -- the control of the comparison.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_COUNT = -(2 ** 30)   # count of a slot that holds no live point
HIGHEST = jax.lax.Precision.HIGHEST


class IndexState(NamedTuple):
    """The served index as plain arrays (see module docstring)."""
    centroids: jax.Array     # (C, D) f32
    entries: jax.Array       # (S, E, 2) f32
    entry_sq: jax.Array      # (S, E) f32
    dens_grid: jax.Array     # (S, G, G) f32
    dens_lo: jax.Array       # (S, 2) f32
    dens_hi: jax.Array       # (S, 2) f32
    coeffs: jax.Array        # (deg+1,) f32, highest degree first
    tau_min: jax.Array       # () f32
    tau_max: jax.Array       # () f32
    point_ids: jax.Array     # (C, P) int32, -1 = empty slot
    codes: jax.Array         # (N, S) uint8
    slot_codes: jax.Array    # (C, P, S) uint8: codes[point_ids], 0 if empty
    labels: jax.Array        # (N,) int32 cluster of each point


def round_bf16(x: jax.Array) -> jax.Array:
    """f32 rounded to the nearest bf16 (ties to even), done on the bits so
    that no compiler pass can drop the rounding."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(b & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _mul(a, b, precision):
    """a * b in f32, or as the three-pass bf16 product (``Precision.HIGH``)."""
    if precision == "f32":
        return a * b
    ah, bh = round_bf16(a), round_bf16(b)
    al, bl = round_bf16(a - ah), round_bf16(b - bh)
    return ah * bh + (ah * bl + al * bh)


def _gemm(a, b, stage_a):
    """a @ b at the stated stage-A precision, summed in f32."""
    if stage_a == "bf16":
        a, b = round_bf16(a), round_bf16(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _tau(st: IndexState, qsub):
    """Density-model thresholds (..., S) for projections (..., S, 2)."""
    g = st.dens_grid.shape[-1]
    span = jnp.maximum(st.dens_hi - st.dens_lo, 1e-6)
    ij = jnp.clip(((qsub - st.dens_lo) / span * g).astype(jnp.int32),
                  0, g - 1)
    s_idx = jnp.broadcast_to(jnp.arange(st.dens_grid.shape[0]),
                             qsub.shape[:-1])
    dens = st.dens_grid[s_idx, ij[..., 0], ij[..., 1]]
    return jnp.clip(jnp.polyval(st.coeffs, dens), st.tau_min, st.tau_max)


def _lut(st: IndexState, qsub, tau, metric, precision):
    """(masked LUT f32, inside tau/2, inside tau), each (..., S, E); an
    entry's hit code is +1 inside tau/2, 0 inside tau, -1 outside."""
    q0, q1 = qsub[..., 0:1], qsub[..., 1:2]
    e0, e1 = st.entries[..., 0], st.entries[..., 1]
    dot = _mul(q0, e0, precision) + _mul(q1, e1, precision)
    tau_sq = (tau * tau)[..., None]
    if metric == "l2":
        dist = (q0 * q0 + q1 * q1) - 2.0 * dot + st.entry_sq
    else:   # the ip selection geometry: |e - r|^2 - |r|^2
        dist = st.entry_sq - 2.0 * dot
    outer = dist <= tau_sq
    inner = dist <= 0.25 * tau_sq
    if metric == "l2":
        mlut = jnp.where(outer, dist, tau_sq)
    else:
        fill = jnp.min(jnp.where(outer, dot, jnp.inf), axis=-1, keepdims=True)
        mlut = jnp.where(outer, dot, jnp.where(jnp.isfinite(fill), fill, 0.0))
    return mlut, inner, outer


def _bits(mask):
    """(..., E) bool as (..., E/32) uint32 words: entry e is bit e % 32 of
    word e // 32."""
    e = mask.shape[-1]
    m = mask.reshape(mask.shape[:-1] + (e // 32, 32)).astype(jnp.uint32)
    return jnp.sum(m << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def _bit_of(words, codes):
    """Bit ``codes[..., p, s]`` of the entry mask ``words[..., s, :]``:
    the table lookup ``mask[..., s, code]`` done with selects and shifts
    (words (Qb, np, S, W) with W a power of two, codes (Qb, np, P, S))."""
    c = codes.astype(jnp.uint32)
    word, bit = c >> 5, c & 31
    cand = [words[:, :, None, :, j] for j in range(words.shape[-1])]
    level = 0
    while len(cand) > 1:
        odd = ((word >> level) & 1) == 1
        cand = [jnp.where(odd, cand[i + 1], cand[i])
                for i in range(0, len(cand), 2)]
        level += 1
    return (cand[0] >> bit) & 1


def hit_counts(inner, outer, codes):
    """(Qb, np, P) int32 sum over subspaces of each slot's hit code, from
    the (Qb, np, S, E) masks and the (Qb, np, P, S) slot codes."""
    n_sub = inner.shape[2]
    per_sub = _bit_of(_bits(inner), codes) + _bit_of(_bits(outer), codes)
    return jnp.sum(per_sub, axis=-1).astype(jnp.int32) - n_sub


def _projections(st: IndexState, q, cids, metric):
    """(Qb, np, S, 2) subspace projections of each query for each probe."""
    nq, d = q.shape
    if metric == "l2":
        res = q[:, None, :] - st.centroids[cids]
        return res.reshape(nq, cids.shape[1], d // 2, 2)
    return jnp.broadcast_to(q.reshape(nq, 1, d // 2, 2),
                            (nq, cids.shape[1], d // 2, 2))


def _stage_a(st: IndexState, q, nprobe, metric, stage_a):
    """(base (Qb, np), probed cluster ids (Qb, np)): the ``nprobe`` best
    centroids; ``base`` is ``q.c`` for ip, 0 for l2."""
    qc = _gemm(q, st.centroids.T, stage_a)
    if metric == "l2":
        csq = jnp.sum(st.centroids * st.centroids, axis=-1)
        neg, cids = jax.lax.top_k(-(csq[None, :] - 2.0 * qc), nprobe)
        return jnp.zeros_like(neg), cids
    return jax.lax.top_k(qc, nprobe)


@functools.partial(jax.jit, static_argnames=("nprobe", "metric", "stage_a"))
def probed_points(st: IndexState, q, *, nprobe: int, metric: str,
                  stage_a: str):
    """(Qb,) live points in the clusters each query probes."""
    sizes = jnp.sum(st.point_ids >= 0, axis=1)
    return jnp.sum(sizes[_stage_a(st, q, nprobe, metric, stage_a)[1]],
                   axis=1)


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "metric",
                                             "stage_a", "precision"))
def search_block(st: IndexState, q, *, k: int, nprobe: int, metric: str,
                 stage_a: str, precision: str = "f32"):
    """Top-k (scores (Qb, k), ids (Qb, k)) of a block of queries; slots that
    hold no answer read +inf (l2) / -inf (ip)."""
    nq = q.shape[0]
    base, cids = _stage_a(st, q, nprobe, metric, stage_a)
    qsub = _projections(st, q, cids, metric)
    tau = _tau(st, qsub)                                     # (Qb, np, S)
    mlut, inner, outer = _lut(st, qsub, tau, metric, precision)

    slot_ids = st.point_ids[cids]                            # (Qb, np, P)
    live = slot_ids >= 0
    codes = st.slot_codes[cids]                              # (Qb,np,P,S)
    counts = jnp.where(live, hit_counts(inner, outer, codes), NEG_COUNT)
    p = slot_ids.shape[2]
    n_cand = min(4 * k, nprobe * p)
    _, cand = jax.lax.top_k(counts.reshape(nq, -1), n_cand)  # (Qb, C)
    probe = cand // p
    cand_codes = jnp.take_along_axis(
        codes.reshape(nq, -1, codes.shape[-1]), cand[..., None], axis=1)
    s_idx = jnp.arange(mlut.shape[2])[None, None, :]
    exact = jnp.sum(mlut[jnp.arange(nq)[:, None, None], probe[..., None],
                         s_idx, cand_codes.astype(jnp.int32)], axis=-1)
    ok = jnp.take_along_axis(live.reshape(nq, -1), cand, axis=1)
    ids = jnp.take_along_axis(slot_ids.reshape(nq, -1), cand, axis=1)
    if metric == "ip":
        exact = jnp.where(ok, exact + jnp.take_along_axis(base, probe, 1),
                          -jnp.inf)
        top, sel = jax.lax.top_k(exact, k)
    else:
        top, sel = jax.lax.top_k(-jnp.where(ok, exact, jnp.inf), k)
        top = -top
    return top, jnp.take_along_axis(ids, sel, axis=1)


@functools.partial(jax.jit, static_argnames=("metric", "stage_a",
                                             "precision"))
def score_of(st: IndexState, q, ids, *, metric: str, stage_a: str,
             precision: str = "f32"):
    """(Qb, k) masked-LUT score of point ``ids[i, j]`` for query ``q[i]``
    (NaN where the id is not a point of the index)."""
    n = st.codes.shape[0]
    ok = (ids >= 0) & (ids < n)
    safe = jnp.where(ok, ids, 0)
    lab = st.labels[safe]                                    # (Qb, k)
    qsub = _projections(st, q, lab, metric)                  # (Qb,k,S,2)
    mlut, _, _ = _lut(st, qsub, _tau(st, qsub), metric, precision)
    codes = st.codes[safe].astype(jnp.int32)                 # (Qb, k, S)
    total = jnp.sum(jnp.take_along_axis(mlut, codes[..., None], axis=3)
                    [..., 0], axis=-1)
    if metric == "ip":
        total = total + jnp.take_along_axis(
            _gemm(q, st.centroids.T, stage_a), lab, 1)
    return jnp.where(ok, total, jnp.nan)


@jax.jit
def slot_codes(codes, point_ids):
    """(C, P, S) codes of each padded slot (code 0 where the slot is empty)."""
    return codes[jnp.maximum(point_ids, 0)]


def run_blocks(fn, queries: np.ndarray, block: int, *extra):
    """Apply ``fn(q_block, *extra_block)`` over blocks of rows (the last
    block padded with its own last row) and join the (scores, ids) or
    array results on the host."""
    n = queries.shape[0]
    outs = []
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        idx = np.minimum(np.arange(lo, lo + block), hi - 1)
        out = fn(jnp.asarray(queries[idx]),
                 *(jnp.asarray(x[idx]) for x in extra))
        outs.append(jax.tree.map(lambda a: np.asarray(a)[:hi - lo], out))
    return jax.tree.map(lambda *xs: np.concatenate(xs), *outs)
