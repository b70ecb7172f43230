"""Pallas TPU kernels: two-stage scan — int8 hit-count prefilter, top-C
survivor selection and survivor-masked ADC (paper §5.5; DESIGN.md §3).

The paper pipelines the RT-core membership test into the tensor-core
distance stage. The TPU version runs the two stages as two kernels over the
same (query-block, point-block) tiles, with the survivor selection between
them:

  counts kernel — int8 hit scores for every tile (the SLAB one-hot MXU
      contraction of ``onehot.slab_onehot_dot``); invalid points get
      ``_NEG``, point-axis padding the lower ``_PAD``.
  top-C        — ``lax.top_k(counts, cap_c)`` in XLA: ``cand`` is exactly its
      index choice (value desc, index asc) and θ_q, the survivor floor, is
      its cap_c-th value. Mosaic has no lowering for ``top_k`` or for the
      lane gathers a streamed in-kernel merge needs, so the selection sits
      between the kernels.
  ADC kernel   — the masked-LUT ADC, only where ``count >= θ_q``: blocks
      with zero survivors skip the f32 contraction entirely (``pl.when``).
      ``cand_dist`` is ``dist`` gathered at ``cand``.

Outputs (Q = queries, W = nprobe·P points, C = cap_c):
  counts (Q, np, P) int32 — stage-1 scores (== ``hit_count`` composed)
  dist   (Q, np, P) f32  — ADC totals at survivors, ``bad_value`` elsewhere
  cand   (Q, C)     int32 — flat top-C-by-count indices into (np·P),
                            equal to ``lax.top_k(counts, C)[1]``
  cand_dist (Q, C)  f32  — ``dist`` gathered at ``cand``

so the downstream two-stage search needs no wide rerank gather: stage 2
consumes the compacted candidates directly.

Tiles: grid (Q/bQ, np·Ppad/bP). bP is a multiple of 128 (points run on
lanes) and P is padded per probe to a multiple of it; padded slots carry
the ``_PAD`` sentinel STRICTLY below the invalid-point ``_NEG``, so — with
cap_c clamped to the real candidate count — they never enter the top-C and
the real entries' (value desc, index asc) order is unchanged (the padded
flat index is monotone in the unpadded one); the wrapper maps candidate
indices back to the unpadded layout. bQ is the whole query axis up to 8
queries, else a multiple of 8 with the query axis padded and sliced off.
The per-query LUT/hit table is one (bQ, S, E) block per probe. VMEM per
program ≈ bQ·S·E·4 [LUT] + bQ·bP·S [codes] + bP·SLAB·E·4 [one-hot slab]
≈ 1.5 MB at (bQ, bP, S, E) = (8, 128, 48, 256).

``fused_two_stage_host`` is the schedule-equivalent host path used for
off-TPU serving (see its docstring); the kernels themselves are validated
in interpret mode by tests/test_fused_kernel.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .onehot import lane_tile, slab_onehot_dot, sublane_tile

DEFAULT_BQ = 8     # query rows per program
DEFAULT_BP = 128   # points per program
SLAB = 8           # subspaces one-hot-expanded at a time (VMEM control)

_NEG = -(2 ** 30)  # invalid-point count sentinel (matches hit_count kernel)
# point-padding sentinel: STRICTLY below _NEG so a padded slot loses every
# tie against a real (even invalid) point and never enters the top-C
_PAD = -(2 ** 30) - 1

# hit-count accumulation dtypes the autotuner may pick (kernels.autotune).
# Every option is EXACT for hit counts (|count| <= S << 2^8 terms of
# {-1, 0, 1}): "f32" contracts f32 operands, "bf16" halves the one-hot
# operand bytes (8 mantissa bits hold these integers), "int8" keeps the
# table in int8 and accumulates on the integer pipeline (int32) — so the
# knob is result-invariant by construction (tests/test_autotune.py).
ACC_DTYPES = ("f32", "bf16", "int8")


def count_dot(codes, table_i8, *, n_entries, acc="f32", slab=SLAB):
    """Stage-1 hit-count contraction with a tunable operand dtype.

    codes (bP, S) int32, table_i8 (S, E) int8 → (1, bP) int32.
    ``acc`` selects the MXU operand dtype (see ACC_DTYPES); all options
    produce bit-identical int32 counts.
    """
    if acc == "bf16":
        tab, od = table_i8.astype(jnp.bfloat16), jnp.float32
    elif acc == "int8":
        tab, od = table_i8, jnp.int32
    else:
        tab, od = table_i8.astype(jnp.float32), jnp.float32
    out = slab_onehot_dot(codes, tab, n_entries=n_entries, out_dtype=od,
                          slab=slab)
    return out.astype(jnp.int32)


def _stack_rows(bq, bp, dtype, row):
    """(bQ, bP) array whose row q is ``row(q)`` (1, bP), for q < bQ.

    A ``fori_loop`` rather than an unrolled Python loop, so one query's
    one-hot temporaries are dead before the next query's are built (the
    unrolled form exceeds the 16 MB scoped VMEM at S = 100)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bp), 0)
    return jax.lax.fori_loop(
        0, bq, lambda q, acc: jnp.where(rows == q, row(q), acc),
        jnp.zeros((bq, bp), dtype))


def _count_kernel(*refs, n_entries, bq, bp, p_real, p_pad, acc, rt):
    """Stage 1 over one (bQ, bP) tile: hit counts, sentinel-masked.

    With ``rt`` the tile's probe first takes the RT sphere test (the
    three-stage kernel's stage 0): the query disc (q0, q1, radius) against
    the probed cluster's projected centroid and reach, as (bQ, 1) columns.
    Failing probes mask ``valid``; the verdict is also written out.
    """
    if rt:
        (q0_ref, q1_ref, r_ref, c0_ref, c1_ref, reach_ref, table_ref,
         codes_ref, valid_ref, counts_ref, pok_ref) = refs
    else:
        table_ref, codes_ref, valid_ref, counts_ref = refs
    j = pl.program_id(1)                              # flat point block
    valid = valid_ref[...] != 0                       # (bQ, bP)
    if rt:
        sx = q0_ref[...] - c0_ref[...]                # (bQ, 1)
        sy = q1_ref[...] - c1_ref[...]
        d2 = sx * sx + sy * sy
        thr = r_ref[...] + reach_ref[...]
        hit = (thr >= 0.0) & (d2 <= thr * thr)
        pok_ref[...] = hit.astype(jnp.int32)
        valid = valid & hit
    bad = _NEG
    if p_pad != p_real:            # point axis padded: mark pad slots
        lane = j * bp + jax.lax.broadcasted_iota(jnp.int32, (1, bp), 1)
        bad = jnp.where(lane % p_pad < p_real, _NEG, _PAD)
    cnt = _stack_rows(bq, bp, jnp.int32, lambda q: count_dot(
        codes_ref[q].astype(jnp.int32), table_ref[q], n_entries=n_entries,
        acc=acc))
    counts_ref[...] = jnp.where(valid, cnt, bad)


def _adc_kernel(theta_ref, lut_ref, codes_ref, counts_ref, dist_ref, *,
                n_entries, bq, bad_value):
    """Stage 2 over one tile: masked-LUT ADC where ``count >= θ``.

    Real points are exactly those with ``count > _NEG`` (valid counts lie
    in [-S, S]), so the survivor mask needs no ``valid`` input."""
    counts = counts_ref[...]                          # (bQ, bP)
    keep = (counts > _NEG) & (counts >= theta_ref[...])
    dist_ref[...] = jnp.full(dist_ref.shape, bad_value, jnp.float32)

    # zero-survivor block: stage-2 f32 work skipped entirely
    @pl.when(jnp.max(keep.astype(jnp.int32)) > 0)
    def _adc():
        adc = _stack_rows(bq, keep.shape[1], jnp.float32,
                          lambda q: slab_onehot_dot(
                              codes_ref[q].astype(jnp.int32), lut_ref[q],
                              n_entries=n_entries, out_dtype=jnp.float32,
                              slab=SLAB))
        dist_ref[...] = jnp.where(keep, adc, bad_value)


def two_stage(lut, table, codes, valid, *, cap_c, metric, bq, bp, acc,
              interpret, rt=None):
    """Shared body of the two- and three-stage scans (see module docstring).

    ``rt`` is None, or ``(q0, q1, radius, pc0, pc1, preach)``: the (Q,)
    ray-plane queries and the (Q, np) projected centroid and reach of each
    probed cluster. Then the counts kernel applies the sphere test per
    probe and a fifth output, ``probe_ok`` (Q, np) bool, is returned.
    """
    q, n_probe, p, s = codes.shape
    e = lut.shape[-1]
    cap_c = max(1, min(cap_c, n_probe * p))
    bp = lane_tile(bp or DEFAULT_BP)
    p_pad = -(-p // bp) * bp
    bq = sublane_tile(q, bq)
    pad_q = (-q) % bq
    qp = q + pad_q
    pads = ((0, pad_q), (0, 0), (0, p_pad - p))
    codes = jnp.pad(codes, pads + ((0, 0),))
    valid = jnp.pad(valid, pads)
    if pad_q:                         # (Q, np, S, E) tables: copy only if due
        lut = jnp.pad(lut, ((0, pad_q), (0, 0), (0, 0), (0, 0)))
        table = jnp.pad(table, ((0, pad_q), (0, 0), (0, 0), (0, 0)))
    w = n_probe * p_pad
    npb = p_pad // bp                 # point blocks per probe
    grid = (qp // bq, w // bp)
    tab_spec = pl.BlockSpec((bq, None, s, e), lambda i, j: (i, j // npb, 0, 0))
    codes_spec = pl.BlockSpec((bq, bp, s), lambda i, j: (i, j, 0))
    row_spec = pl.BlockSpec((bq, bp), lambda i, j: (i, j))
    col_spec = pl.BlockSpec((bq, 1), lambda i, j: (i, 0))
    in_specs = [tab_spec, codes_spec, row_spec]
    args = [table, codes.reshape(qp, w, s),
            valid.reshape(qp, w).astype(jnp.int32)]
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((qp, w), jnp.int32)]
    if rt is not None:
        # per-probe operands as (np, Q, 1): a tile's probe is j // npb
        probe_spec = pl.BlockSpec((None, bq, 1),
                                  lambda i, j: (j // npb, i, 0))
        cols = [jnp.pad(x, (0, pad_q))[:, None] for x in rt[:3]]
        probes = [jnp.pad(x, ((0, pad_q), (0, 0))).T[..., None]
                  for x in rt[3:]]
        in_specs = [col_spec] * 3 + [probe_spec] * 3 + in_specs
        args = cols + probes + args
        out_specs.append(probe_spec)
        out_shape.append(jax.ShapeDtypeStruct((n_probe, qp, 1), jnp.int32))
    outs = pl.pallas_call(
        functools.partial(_count_kernel, n_entries=e, bq=bq, bp=bp,
                          p_real=p, p_pad=p_pad, acc=acc, rt=rt is not None),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
    )(*args)
    counts = outs[0]

    topv, cand = jax.lax.top_k(counts, cap_c)         # (Qp, C)
    theta = topv[:, cap_c - 1:]                       # (Qp, 1) survivor floor
    bad = float("inf") if metric == "l2" else float("-inf")
    dist = pl.pallas_call(
        functools.partial(_adc_kernel, n_entries=e, bq=bq, bad_value=bad),
        grid=grid, in_specs=[col_spec, tab_spec, codes_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((qp, w), jnp.float32),
        interpret=interpret,
    )(theta, lut, args[-2], counts)
    cdist = jnp.take_along_axis(dist, cand, axis=1)[:q]
    cand = cand[:q]
    counts = counts[:q].reshape(q, n_probe, p_pad)[:, :, :p]
    dist = dist[:q].reshape(q, n_probe, p_pad)[:, :, :p]
    if p_pad != p:
        # remap candidate indices from the padded to the real flat layout
        # (cand never contains pad slots — see _PAD — and the mapping is
        # monotone, so top-k order is preserved)
        cand = (cand // p_pad) * p + cand % p_pad
    out = (counts, dist, cand, cdist)
    if rt is not None:
        out += (outs[1][:, :q, 0].T > 0,)
    return out


@functools.partial(jax.jit,
                   static_argnames=("cap_c", "metric", "bq", "bp", "acc",
                                    "interpret"))
def fused_two_stage(lut: jnp.ndarray, table: jnp.ndarray, codes: jnp.ndarray,
                    valid: jnp.ndarray, *, cap_c: int, metric: str = "l2",
                    bq: int = DEFAULT_BQ, bp: int | None = None,
                    acc: str = "f32", interpret: bool = False):
    """lut (Q, np, S, E) f32, table (Q, np, S, E) int8,
    codes (Q, np, P, S) uint8, valid (Q, np, P) bool →
    (counts (Q, np, P) i32, dist (Q, np, P) f32,
     cand (Q, C) i32, cand_dist (Q, C) f32). See module docstring.
    ``bq``/``bp``/``acc`` are the autotuner's tile/accumulation knobs
    (``kernels.autotune``) — all result-invariant; tiles are rounded to
    ones the chip accepts."""
    return two_stage(lut, table, codes, valid, cap_c=cap_c, metric=metric,
                     bq=bq, bp=bp, acc=acc, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("cap_c", "metric", "topc_impl"))
def fused_two_stage_host(lut: jnp.ndarray, table: jnp.ndarray,
                         codes: jnp.ndarray, valid: jnp.ndarray, *,
                         cap_c: int, metric: str = "l2",
                         topc_impl: str = "sort"):
    """Schedule-equivalent host path for off-TPU serving. Same contract as
    the kernel with two documented deviations, both invisible to the
    two-stage search (which consumes only ``cand``/``cand_dist``/``counts``):

    * ``cand`` holds the identical top-C-by-count SET, but ordered by flat
      index instead of ``lax.top_k``'s (value desc, index asc);
    * ``dist`` carries ADC totals only at ``cand`` positions (``bad_value``
      elsewhere) — count-ties beyond the C-th candidate are not scored.

    The in-kernel streamed threshold becomes an exact θ-selection: a
    values-only sort yields the per-query C-th-largest count θ_q, the
    index-ascending rank among θ-ties falls out of one cumsum, and the
    selected indices are compacted with searchsorted over that cumsum.
    A key-value select (``lax.top_k`` / argsort) costs ~5× a values-only
    sort on CPU at serving widths, and it is exactly the wide top-k that
    dominates the composed two-stage path there — this is the host-side
    payoff of the kernel's "threshold in-kernel, compact per block" design.
    Stage 2 then gathers the masked LUT for exactly the C survivors.

    ``topc_impl`` is the autotuner's θ-selection knob (``kernels.autotune``):
    "sort" (default) derives θ_q from a values-only sort + searchsorted;
    "topk" derives the same θ_q from ``lax.top_k`` values and a count of
    strictly-greater entries. Both feed the identical tie-rank/compaction
    tail, so candidate sets, order and every output are bit-identical —
    only the selection cost differs by backend and problem width.
    """
    q, n_probe, p, s = codes.shape
    w = n_probe * p
    cap_c = max(1, min(cap_c, w))
    bad = jnp.float32(jnp.inf if metric == "l2" else -jnp.inf)
    rows = jnp.arange(q)[:, None]

    # ---- stage 1: hit counts by direct gather (CPU-optimal) -------------
    qi = jnp.arange(q)[:, None, None, None]
    pri = jnp.arange(n_probe)[None, :, None, None]
    si = jnp.arange(s)[None, None, None, :]
    ci = codes.astype(jnp.int32)
    tvals = table[qi, pri, si, ci]                       # (Q, np, P, S) int8
    counts = jnp.where(valid, jnp.sum(tvals.astype(jnp.int32), axis=-1),
                       _NEG)
    flat = counts.reshape(q, w)

    # ---- survivor threshold: exact θ-selection ---------------------------
    if topc_impl == "topk":
        theta = jax.lax.top_k(flat, cap_c)[0][:, -1]     # C-th largest count
        n_gt = jnp.sum((flat > theta[:, None]).astype(jnp.int32), axis=1)
    else:                                                # values-only sort
        srt = jnp.sort(flat, axis=1)
        theta = srt[:, w - cap_c]            # C-th largest count (with ties)
        n_gt = w - jax.vmap(
            lambda sr, th: jnp.searchsorted(sr, th, side="right"))(srt, theta)
    tie = flat == theta[:, None]
    tie_rank = jnp.cumsum(tie.astype(jnp.int32), axis=1) - 1
    take = (flat > theta[:, None]) | (
        tie & (tie_rank < (cap_c - n_gt)[:, None]))      # exactly C True

    # ---- compaction: the C selected flat indices, index-ascending -------
    cum = jnp.cumsum(take.astype(jnp.int32), axis=1)
    ranks = jnp.arange(1, cap_c + 1)
    cand = jax.vmap(
        lambda c: jnp.searchsorted(c, ranks))(cum).astype(jnp.int32)

    # ---- stage 2: masked-LUT ADC for the C survivors only ---------------
    cand_probe = cand // p
    cand_codes = jnp.take_along_axis(
        codes.reshape(q, w, s), cand[..., None], axis=1).astype(jnp.int32)
    s2 = jnp.arange(s)[None, None, :]
    vals = lut[rows[..., None], cand_probe[..., None], s2, cand_codes]
    cand_valid = jnp.take_along_axis(valid.reshape(q, w), cand, axis=1)
    cdist = jnp.where(cand_valid, jnp.sum(vals, axis=-1), bad)

    dist = jnp.full((q, w), bad, jnp.float32).at[rows, cand].set(cdist)
    return counts, dist.reshape(q, n_probe, p), cand, cdist
