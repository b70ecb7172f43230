"""Differential validation of the fused two-stage kernel.

The fused kernel (``kernels.fused_two_stage``) must be EQUIVALENT to the
composition it replaces — ``hit_count`` (stage 1) + ``pq_scan`` (stage 2) +
a wide ``lax.top_k`` between them:

* ``counts`` bit-identical to the composed ``hit_count`` kernel;
* ``cand`` bit-identical to ``lax.top_k(counts, cap_c)[1]`` (the composed
  stage-1 selection, including its value-desc/index-asc tie order);
* ``dist`` = the composed ``pq_scan`` totals at every survivor
  (count >= θ = cap_c-th largest), the metric sentinel elsewhere;
* ``cand_dist`` = ``dist`` gathered at ``cand``.

All Pallas executions run in interpret mode (real block iteration on CPU
CI). The host fast path (``fused_two_stage_host``) is held to the same
contract modulo its two documented deviations (index-ordered ``cand``,
``dist`` populated only at ``cand``). Hypothesis drives the shape/seed
sweep through tests/_hypothesis_fallback.py when the real package is
absent.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.fused_two_stage import (fused_two_stage,
                                           fused_two_stage_host)
from repro.kernels.hit_count import hit_count
from repro.kernels.onehot import lane_tile, sublane_tile
from repro.kernels.pq_scan import pq_scan

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


def _composed(lut, table, codes, valid, cap_c, metric):
    """The replaced pipeline: per-(q, probe) kernels + wide top_k."""
    counts = jax.vmap(jax.vmap(
        lambda t, c, v: hit_count(t, c, v, interpret=True)))(
        table, codes, valid)
    totals = jax.vmap(jax.vmap(
        lambda l, c, v: pq_scan(l, c, v, metric=metric, interpret=True)))(
        lut, codes, valid)
    q = counts.shape[0]
    flat = counts.reshape(q, -1)
    cap_c = max(1, min(cap_c, flat.shape[1]))
    topv, cand = jax.lax.top_k(flat, cap_c)
    return counts, totals, topv, cand


def _check_kernel(seed, q, n_probe, p, s, e, cap_c, metric, valid_p=0.85):
    lut, table, codes, valid = _inputs(seed, q, n_probe, p, s, e, valid_p)
    counts, totals, topv, cand = _composed(lut, table, codes, valid, cap_c,
                                           metric)
    got = fused_two_stage(lut, table, codes, valid, cap_c=cap_c,
                          metric=metric, interpret=True)
    g_counts, g_dist, g_cand, g_cdist = (np.asarray(x) for x in got)
    bad = np.inf if metric == "l2" else -np.inf

    np.testing.assert_array_equal(g_counts, np.asarray(counts))
    np.testing.assert_array_equal(g_cand, np.asarray(cand))
    # dist: pq_scan totals at survivors (count >= θ), sentinel elsewhere
    theta = np.asarray(topv)[:, -1]
    keep = np.asarray(valid) & (np.asarray(counts)
                                >= theta[:, None, None])
    np.testing.assert_allclose(g_dist[keep], np.asarray(totals)[keep],
                               rtol=1e-5, atol=1e-4)
    assert np.all(g_dist[~keep] == bad)
    # compacted candidate distances == dist gathered at cand
    want_cdist = np.take_along_axis(g_dist.reshape(g_counts.shape[0], -1),
                                    g_cand, axis=1)
    np.testing.assert_array_equal(g_cdist, want_cdist)


# (Q, np, P, S, E, cap_c) — ragged Q (query-tail padding), P below and
# above the 128-lane tile (lane-tail padding), S not a SLAB multiple
SHAPES = [
    (3, 2, 17, 6, 8, 9),
    (5, 3, 12, 5, 16, 7),
    (9, 2, 10, 12, 32, 20),    # Q=9 → two 8-row blocks, query tail padded
    (6, 2, 31, 7, 8, 15),      # P=31 → one 128-lane block, padded
    (2, 1, 8, 4, 8, 50),       # cap_c > W → clamped to W
    (1, 4, 13, 3, 4, 5),       # single query
    (4, 2, 131, 5, 8, 20),     # P=131 → two 128-lane blocks per probe
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_fused_matches_composed_kernels(shape, metric):
    _check_kernel(sum(shape), *shape, metric)


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("valid_p", [0.0, 1.0])
def test_fused_edge_masks(metric, valid_p):
    """All-pruned (every point invalid) and all-valid survivor masks."""
    _check_kernel(11, 4, 2, 16, 8, 8, 12, metric, valid_p=valid_p)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_fused_lane_padding_never_candidate(metric):
    """Fewer valid points than cap_c: cand takes the valid points, then
    invalid ones index-ascending, and never a lane-padding slot. P = 131
    pads each probe to two 128-lane blocks, so probe 0's padding sits
    between real points in the padded flat layout."""
    _check_kernel(5, 3, 2, 131, 5, 8, 200, metric, valid_p=0.02)


def test_fused_all_pruned_sentinels():
    """With nothing valid, every dist is the sentinel and every count the
    NEG marker — and cand still lists cap_c well-formed indices."""
    lut, table, codes, valid = _inputs(3, 2, 2, 9, 4, 8, valid_p=0.0)
    counts, dist, cand, cdist = fused_two_stage(
        lut, table, codes, valid, cap_c=6, metric="l2", interpret=True)
    assert np.all(np.asarray(counts) == -(2 ** 30))
    assert np.all(np.isinf(np.asarray(dist)))
    assert np.all(np.isinf(np.asarray(cdist)))
    c = np.asarray(cand)
    assert c.shape == (2, 6) and np.all((c >= 0) & (c < 18))
    # ties at NEG break index-ascending, exactly like lax.top_k
    np.testing.assert_array_equal(c, np.broadcast_to(np.arange(6), (2, 6)))


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 6), st.integers(1, 3), st.integers(1, 24),
       st.integers(1, 10), st.integers(2, 5), st.integers(1, 30),
       st.sampled_from(["l2", "ip"]), st.integers(0, 2 ** 31 - 1))
def test_fused_kernel_property(q, n_probe, p, s, log_e, cap_c, metric, seed):
    """Property sweep: arbitrary shapes/caps/seeds, kernel == composed."""
    _check_kernel(seed, q, n_probe, p, s, 2 ** log_e, cap_c, metric)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 5), st.integers(1, 3), st.integers(2, 20),
       st.integers(1, 8), st.integers(1, 25),
       st.sampled_from(["l2", "ip"]), st.floats(0.0, 1.0),
       st.integers(0, 2 ** 31 - 1))
def test_host_path_matches_oracle(q, n_probe, p, s, cap_c, metric, valid_p,
                                  seed):
    """Host fast path: same counts, same candidate SET (order is
    index-ascending by contract), same distances at the candidates."""
    e = 16
    lut, table, codes, valid = _inputs(seed, q, n_probe, p, s, e, valid_p)
    ro = ref.fused_two_stage_ref(lut, table, codes, valid, cap_c=cap_c,
                                 metric=metric)
    rh = fused_two_stage_host(lut, table, codes, valid, cap_c=cap_c,
                              metric=metric)
    np.testing.assert_array_equal(np.asarray(rh[0]), np.asarray(ro[0]))
    np.testing.assert_array_equal(np.sort(np.asarray(rh[2]), axis=1),
                                  np.sort(np.asarray(ro[2]), axis=1))
    # host cand is index-sorted by construction
    assert np.all(np.diff(np.asarray(rh[2]), axis=1) > 0)
    want = np.take_along_axis(np.asarray(ro[1]).reshape(q, -1),
                              np.asarray(rh[2]), axis=1)
    np.testing.assert_allclose(np.asarray(rh[3]), want, rtol=1e-5, atol=1e-4)


def test_kernel_matches_dense_oracle():
    """The interpret-mode kernel reproduces the dense oracle EXACTLY —
    including the survivor-masked dist plane and tie handling."""
    for seed, metric in [(0, "l2"), (1, "ip")]:
        lut, table, codes, valid = _inputs(seed, 5, 2, 19, 7, 8, 0.7)
        ro = ref.fused_two_stage_ref(lut, table, codes, valid, cap_c=13,
                                     metric=metric)
        rk = fused_two_stage(lut, table, codes, valid, cap_c=13,
                             metric=metric, interpret=True)
        np.testing.assert_array_equal(np.asarray(rk[0]), np.asarray(ro[0]))
        np.testing.assert_array_equal(np.asarray(rk[2]), np.asarray(ro[2]))
        dk, do = np.asarray(rk[1]), np.asarray(ro[1])
        np.testing.assert_array_equal(np.isinf(dk), np.isinf(do))
        np.testing.assert_allclose(dk[np.isfinite(dk)], do[np.isfinite(do)],
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(rk[3]), np.asarray(ro[3]),
                                   rtol=1e-5, atol=1e-4)


def test_fused_block_size_invariance():
    """Results must not depend on the (bQ, bP) tiling — pure BlockSpec
    property, mirroring test_kernels.test_block_size_invariance. Integer
    outputs (counts, cand) are bit-equal; f32 ADC totals may differ by
    accumulation order across tile shapes, so they get a tight allclose.

    Q = 20 and P = 300 keep the tilings distinct after the kernel rounds
    them to chip tiles: several query blocks with a padded query tail, and
    several point blocks per probe with a padded lane tail."""
    q, p = 20, 300
    tiles = [(8, 128), (16, 128), (8, 256), (24, 384)]
    assert len({(sublane_tile(q, bq), lane_tile(bp))
                for bq, bp in tiles}) == len(tiles)
    lut, table, codes, valid = _inputs(17, q, 2, p, 6, 16, 0.8)
    outs = [fused_two_stage(lut, table, codes, valid, cap_c=10, metric="l2",
                            bq=bq, bp=bp, interpret=True)
            for bq, bp in tiles]
    c0, d0, i0, cd0 = (np.asarray(x) for x in outs[0])
    counts, _, _, cand = _composed(lut, table, codes, valid, 10, "l2")
    np.testing.assert_array_equal(c0, np.asarray(counts))
    np.testing.assert_array_equal(i0, np.asarray(cand))
    for o in outs[1:]:
        c, d, i, cd = (np.asarray(x) for x in o)
        np.testing.assert_array_equal(c0, c)
        np.testing.assert_array_equal(i0, i)
        np.testing.assert_array_equal(np.isinf(d0), np.isinf(d))
        np.testing.assert_allclose(d0[np.isfinite(d0)], d[np.isfinite(d)],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(cd0, cd, rtol=1e-5, atol=1e-5)
