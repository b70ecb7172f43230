"""Differential validation of the single-residency three-stage kernel.

The three-stage kernel (``kernels.fused_three_stage``) must be EQUIVALENT
to the composition it replaces — ``rt.sphere_hits`` (stage 0) → the
probe-mask gather of ``core.juno._rt_probe_mask`` (``slot_of`` lookup,
probe-0 backstop) → ``fused_two_stage`` over the masked ``valid``:

* ``counts``/``cand`` bit-identical to the composed path (including the
  value-desc/index-asc top-C tie order);
* ``probe_ok`` bit-identical to the host-side mask gather;
* ``dist``/``cand_dist`` equal at survivors, metric sentinel elsewhere;
* ids AND scores through the dense oracle
  (``kernels.ref.fused_three_stage_ref``) as semantics of record.

Grids come from the ``test_rt_filter`` synthesizer (build invariants:
slot coords inside their cell AABB, ``-inf`` pad/empty sentinels,
degenerate zero/cover-all radii in every batch). All Pallas executions
run in interpret mode; hypothesis drives the shape/seed sweep through
tests/_hypothesis_fallback.py when the real package is absent.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import rt
from repro.kernels import ref
from repro.kernels.fused_three_stage import (fused_three_stage,
                                             fused_three_stage_host)
from repro.kernels.fused_two_stage import fused_two_stage
from repro.kernels.onehot import lane_tile, sublane_tile

pytestmark = pytest.mark.interpret


def _inputs(seed, q, n_probe, p, s, e, valid_p=0.85):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    lut = jax.random.normal(ks[0], (q, n_probe, s, e), jnp.float32)
    table = jax.random.randint(ks[1], (q, n_probe, s, e), -1, 2
                               ).astype(jnp.int8)
    codes = jax.random.randint(ks[2], (q, n_probe, p, s), 0, e
                               ).astype(jnp.uint8)
    if valid_p <= 0.0:
        valid = jnp.zeros((q, n_probe, p), bool)
    elif valid_p >= 1.0:
        valid = jnp.ones((q, n_probe, p), bool)
    else:
        valid = jax.random.bernoulli(ks[3], valid_p, (q, n_probe, p))
    return lut, table, codes, valid


def _synth_grid(seed, n_cells_side, cap, q, n_probe):
    """Random grid honoring the build invariants (slot coords inside their
    cell's AABB, cell_reach = max slot_reach, -inf pad/empty sentinels)
    plus a probed-cluster slot_idx table — the test_rt_filter synthesizer
    extended with the kernel's stage-0 probe plumbing. Every batch carries
    degenerate radii: the first quarter 0.0 (point queries), the second
    quarter 10.0 (cover-all)."""
    rng = np.random.default_rng(seed)
    g = n_cells_side
    n_cells = g * g
    lo = np.stack(np.meshgrid(np.arange(g), np.arange(g), indexing="ij"),
                  -1).reshape(-1, 2) / g
    boxes = np.concatenate([lo, lo + 1.0 / g], 1).astype(np.float32)
    counts = rng.integers(0, cap + 1, n_cells)
    c0 = np.zeros((n_cells, cap), np.float32)
    c1 = np.zeros((n_cells, cap), np.float32)
    reach = np.full((n_cells, cap), -np.inf, np.float32)
    for cell in range(n_cells):
        k = counts[cell]
        u = rng.random((k, 2)).astype(np.float32)
        c0[cell, :k] = boxes[cell, 0] + u[:, 0] / g
        c1[cell, :k] = boxes[cell, 1] + u[:, 1] / g
        reach[cell, :k] = np.abs(rng.normal(0, 0.2, k)).astype(np.float32)
    cell_reach = reach.max(1)
    q0 = rng.uniform(-0.3, 1.3, q).astype(np.float32)
    q1 = rng.uniform(-0.3, 1.3, q).astype(np.float32)
    radius = rng.uniform(0, 0.5, q).astype(np.float32)
    radius[: q // 4] = 0.0                       # degenerate: point queries
    radius[q // 4: 2 * (q // 4)] = 10.0         # degenerate: cover-all
    slot_idx = rng.integers(0, n_cells * cap, (q, n_probe)).astype(np.int32)
    return tuple(map(jnp.asarray, (q0, q1, radius, boxes, cell_reach,
                                   c0, c1, reach, slot_idx)))


def _kargs(grid_args):
    """The kernel's grid operands: the synthesizer's minus boxes and
    cell_reach (the kernel tests only the probed slots, no cell walk)."""
    q0, q1, radius, _, _, c0, c1, reach, slot_idx = grid_args
    return q0, q1, radius, c0, c1, reach, slot_idx


def _composed(lut, table, codes, valid, grid_args, cap_c, metric):
    """The replaced pipeline: interpret-mode sphere walk → _rt_probe_mask
    gather (slot_of lookup + probe-0 backstop) → interpret-mode fused
    two-stage over the masked valid."""
    q0, q1, radius, boxes, cell_reach, c0, c1, reach, slot_idx = grid_args
    hits = rt.sphere_hits(q0, q1, radius, boxes, cell_reach, c0, c1, reach,
                          interpret=True)
    pok = jnp.take_along_axis(hits, slot_idx, axis=1) > 0
    pok = pok.at[:, 0].set(True)
    masked = valid & pok[:, :, None]
    counts, dist, cand, cdist = fused_two_stage(
        lut, table, codes, masked, cap_c=cap_c, metric=metric,
        interpret=True)
    return counts, dist, cand, cdist, pok


def _check_kernel(seed, q, n_probe, p, s, e, cap_c, metric, g=3, cap=8,
                  valid_p=0.85):
    lut, table, codes, valid = _inputs(seed, q, n_probe, p, s, e, valid_p)
    grid_args = _synth_grid(seed + 1, g, cap, q, n_probe)
    want = _composed(lut, table, codes, valid, grid_args, cap_c, metric)
    oracle = ref.fused_three_stage_ref(
        lut, table, codes, valid, grid_args[0], grid_args[1], grid_args[2],
        grid_args[5], grid_args[6], grid_args[7], grid_args[8],
        cap_c=cap_c, metric=metric)
    got = fused_three_stage(lut, table, codes, valid, *_kargs(grid_args),
                            cap_c=cap_c, metric=metric, interpret=True)
    g_counts, g_dist, g_cand, g_cdist, g_pok = (np.asarray(x) for x in got)

    # vs composed rt → mask → fused two-stage: integer planes bit-equal
    np.testing.assert_array_equal(g_pok, np.asarray(want[4]))
    np.testing.assert_array_equal(g_counts, np.asarray(want[0]))
    np.testing.assert_array_equal(g_cand, np.asarray(want[2]))
    w_dist = np.asarray(want[1])
    np.testing.assert_array_equal(np.isinf(g_dist), np.isinf(w_dist))
    fin = np.isfinite(w_dist)
    np.testing.assert_allclose(g_dist[fin], w_dist[fin], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(g_cdist, np.asarray(want[3]), rtol=1e-5,
                               atol=1e-5)
    # vs the dense oracle (semantics of record)
    np.testing.assert_array_equal(g_pok, np.asarray(oracle[4]))
    np.testing.assert_array_equal(g_counts, np.asarray(oracle[0]))
    np.testing.assert_array_equal(g_cand, np.asarray(oracle[2]))
    np.testing.assert_allclose(g_cdist, np.asarray(oracle[3]), rtol=1e-5,
                               atol=1e-4)


# (Q, np, P, S, E, cap_c, g, cap) — ragged Q (bQ padding), ragged cell
# grids (cells >/< point blocks exercise BOTH clamp directions on the
# shared grid axis), prime P above the tile size (point-padding path)
SHAPES = [
    (4, 2, 17, 6, 8, 9, 3, 8),
    (5, 3, 12, 5, 16, 7, 2, 16),
    (9, 2, 10, 12, 32, 20, 4, 8),   # Q=9 → bQ pad; 16 cells > point blocks
    (6, 2, 31, 7, 8, 15, 2, 8),     # P=31 prime → bP=31
    (2, 1, 8, 4, 8, 50, 3, 8),      # cap_c > W → clamped to W
    (1, 4, 13, 3, 4, 5, 2, 8),      # single query
    (4, 2, 131, 5, 8, 20, 3, 8),    # P=131 prime > 128 → padded tiles
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_fused3_matches_composed(shape, metric):
    _check_kernel(sum(shape), *shape[:6], metric, g=shape[6], cap=shape[7])


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("valid_p", [0.0, 1.0])
def test_fused3_edge_masks(metric, valid_p):
    """All-pruned (every point invalid) and all-valid masks — composed,
    oracle and kernel must still agree bit-for-bit."""
    _check_kernel(11, 4, 2, 16, 8, 8, 12, metric, valid_p=valid_p)


def test_fused3_probe0_backstop():
    """A query whose sphere misses EVERY cell still scans probe 0: its
    probe_ok row is the backstop pattern [True, False, ...] and its
    candidates come exclusively from probe 0 — never sentinels only."""
    lut, table, codes, valid = _inputs(5, 4, 3, 16, 4, 8, valid_p=1.0)
    grid_args = list(_synth_grid(9, 3, 8, 4, 3))
    grid_args[2] = jnp.full((4,), -1.0, jnp.float32)   # negative radius:
    # thr = r + reach < 0 for every slot (max reach < 1), so no hits
    got = fused_three_stage(lut, table, codes, valid, *_kargs(grid_args),
                            cap_c=8, metric="l2", interpret=True)
    pok = np.asarray(got[4])
    np.testing.assert_array_equal(
        pok, np.broadcast_to(np.arange(3) == 0, (4, 3)))
    assert np.all(np.asarray(got[2]) < 16)   # all candidates in probe 0
    assert np.all(np.isfinite(np.asarray(got[3])))


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 6), st.integers(1, 3), st.integers(1, 24),
       st.integers(1, 10), st.integers(2, 5), st.integers(1, 30),
       st.integers(2, 4), st.sampled_from([8, 16]),
       st.sampled_from(["l2", "ip"]), st.integers(0, 2 ** 31 - 1))
def test_fused3_kernel_property(q, n_probe, p, s, log_e, cap_c, g, cap,
                                metric, seed):
    """Property sweep: arbitrary shapes/caps/grids/seeds, kernel ==
    composed == oracle."""
    _check_kernel(seed, q, n_probe, p, s, 2 ** log_e, cap_c, metric,
                  g=g, cap=cap)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 5), st.integers(1, 3), st.integers(2, 20),
       st.integers(1, 8), st.integers(1, 25), st.sampled_from(["l2", "ip"]),
       st.floats(0.0, 1.0), st.integers(0, 2 ** 31 - 1))
def test_fused3_host_matches_oracle(q, n_probe, p, s, cap_c, metric,
                                    valid_p, seed):
    """Host fast path: same probe verdicts and counts, same candidate SET
    (index-ascending by contract), same distances at the candidates."""
    e = 16
    lut, table, codes, valid = _inputs(seed, q, n_probe, p, s, e, valid_p)
    ga = _synth_grid(seed + 1, 3, 8, q, n_probe)
    ro = ref.fused_three_stage_ref(lut, table, codes, valid, ga[0], ga[1],
                                   ga[2], ga[5], ga[6], ga[7], ga[8],
                                   cap_c=cap_c, metric=metric)
    rh = fused_three_stage_host(lut, table, codes, valid, ga[0], ga[1],
                                ga[2], ga[5], ga[6], ga[7], ga[8],
                                cap_c=cap_c, metric=metric)
    np.testing.assert_array_equal(np.asarray(rh[4]), np.asarray(ro[4]))
    np.testing.assert_array_equal(np.asarray(rh[0]), np.asarray(ro[0]))
    np.testing.assert_array_equal(np.sort(np.asarray(rh[2]), axis=1),
                                  np.sort(np.asarray(ro[2]), axis=1))
    assert np.all(np.diff(np.asarray(rh[2]), axis=1) > 0)
    want = np.take_along_axis(np.asarray(ro[1]).reshape(q, -1),
                              np.asarray(rh[2]), axis=1)
    np.testing.assert_allclose(np.asarray(rh[3]), want, rtol=1e-5,
                               atol=1e-4)


def test_fused3_acc_dtype_invariance():
    """The autotuner's hit-count accumulation knob must be invisible in
    results: every ACC_DTYPES option yields bit-equal counts/cand/probe_ok
    and allclose distances (same contraction, different operand dtype)."""
    from repro.kernels.fused_two_stage import ACC_DTYPES
    lut, table, codes, valid = _inputs(23, 5, 2, 16, 8, 16, 0.8)
    ga = _synth_grid(24, 3, 8, 5, 2)
    outs = [fused_three_stage(lut, table, codes, valid, *_kargs(ga),
                              cap_c=10, metric="l2", acc=acc,
                              interpret=True)
            for acc in ACC_DTYPES]
    c0, d0, i0, cd0, p0 = (np.asarray(x) for x in outs[0])
    for o in outs[1:]:
        c, d, i, cd, pk = (np.asarray(x) for x in o)
        np.testing.assert_array_equal(c0, c)
        np.testing.assert_array_equal(i0, i)
        np.testing.assert_array_equal(p0, pk)
        np.testing.assert_array_equal(np.isinf(d0), np.isinf(d))
        np.testing.assert_allclose(d0[np.isfinite(d0)], d[np.isfinite(d)],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(cd0, cd, rtol=1e-5, atol=1e-5)


def test_fused3_block_size_invariance():
    """Results must not depend on the (bQ, bP) tiling requested (the
    kernel rounds each request to a tile the chip accepts). Q = 20 and
    P = 300 keep the rounded tilings distinct: several query blocks with a
    padded query tail, several point blocks per probe with a padded lane
    tail, and the per-probe operands indexed across point blocks."""
    q, p = 20, 300
    tiles = [(8, 128), (16, 128), (8, 256), (24, 384)]
    assert len({(sublane_tile(q, bq), lane_tile(bp))
                for bq, bp in tiles}) == len(tiles)
    lut, table, codes, valid = _inputs(17, q, 2, p, 6, 16, 0.8)
    ga = _synth_grid(18, 3, 8, q, 2)
    outs = [fused_three_stage(lut, table, codes, valid, *_kargs(ga),
                              cap_c=10, metric="l2", bq=bq, bp=bp,
                              interpret=True)
            for bq, bp in tiles]
    c0, d0, i0, cd0, p0 = (np.asarray(x) for x in outs[0])
    counts, _, cand, _, pok = _composed(lut, table, codes, valid, ga, 10,
                                        "l2")
    np.testing.assert_array_equal(c0, np.asarray(counts))
    np.testing.assert_array_equal(i0, np.asarray(cand))
    np.testing.assert_array_equal(p0, np.asarray(pok))
    for o in outs[1:]:
        c, d, i, cd, pk = (np.asarray(x) for x in o)
        np.testing.assert_array_equal(c0, c)
        np.testing.assert_array_equal(i0, i)
        np.testing.assert_array_equal(p0, pk)
        np.testing.assert_array_equal(np.isinf(d0), np.isinf(d))
        np.testing.assert_allclose(d0[np.isfinite(d0)], d[np.isfinite(d)],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(cd0, cd, rtol=1e-5, atol=1e-5)
