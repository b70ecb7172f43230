"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
each asserting allclose against the pure-jnp oracle in repro.kernels.ref.
Kernels execute with interpret=True on CPU (real block iteration)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.pq_scan import pq_scan
from repro.kernels.hit_count import hit_count

pytestmark = pytest.mark.interpret


def _inputs(key, b, s, e, p, tau_scale=1.0):
    ks = jax.random.split(key, 6)
    qsub = jax.random.normal(ks[0], (b, s, 2))
    entries = jax.random.normal(ks[1], (s, e, 2))
    esq = jnp.sum(entries ** 2, -1)
    tau = jax.random.uniform(ks[2], (b, s), minval=0.3, maxval=2.0) * tau_scale
    codes = jax.random.randint(ks[3], (p, s), 0, e).astype(jnp.uint8)
    valid = jax.random.bernoulli(ks[4], 0.85, (p,))
    return qsub, entries, esq, tau, codes, valid


SHAPES = [  # (B, S, E, P) — covers non-divisible blocks, tiny/large E
    (8, 48, 256, 257),
    (16, 40, 128, 64),
    (3, 12, 64, 100),     # B not divisible by block
    (8, 100, 256, 130),   # S=100 (tti-like PQ100), odd P
    (1, 4, 16, 8),        # minimal
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_selective_lut_sweep(shape, metric):
    b, s, e, p = shape
    qsub, entries, esq, tau, *_ = _inputs(jax.random.PRNGKey(b * s), b, s, e, p)
    lut, hit = ops.build_selective_lut(qsub, entries, esq, tau, metric=metric)
    lut_r, hit_r = ref.selective_lut_ref(qsub[..., 0], qsub[..., 1],
                                         entries[..., 0], entries[..., 1],
                                         esq, tau, metric=metric)
    np.testing.assert_allclose(np.asarray(lut), np.asarray(lut_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(hit), np.asarray(hit_r))
    assert hit.dtype == jnp.int8


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_pq_scan_sweep(shape, metric):
    b, s, e, p = shape
    key = jax.random.PRNGKey(b + s + e)
    _, _, _, _, codes, valid = _inputs(key, b, s, e, p)
    lut = jax.random.normal(jax.random.fold_in(key, 5), (s, e))
    got = ops.masked_adc_scan(lut, codes, valid, metric=metric)
    want = ref.pq_scan_ref(lut, codes, valid, metric=metric)
    m = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got)[m], np.asarray(want)[m],
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got)[~m], np.asarray(want)[~m])


@pytest.mark.parametrize("shape", SHAPES)
def test_hit_count_sweep(shape):
    b, s, e, p = shape
    key = jax.random.PRNGKey(7 * b + s)
    _, _, _, _, codes, valid = _inputs(key, b, s, e, p)
    table = jax.random.randint(jax.random.fold_in(key, 9), (s, e), -1, 2
                               ).astype(jnp.int8)
    got = ops.hit_count_scan(table, codes, valid)
    want = ref.hit_count_ref(table, codes, valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int32


def test_pq_scan_batched_leading_dims():
    key = jax.random.PRNGKey(11)
    s, e, p = 12, 64, 50
    lut = jax.random.normal(key, (2, 3, s, e))
    codes = jax.random.randint(jax.random.fold_in(key, 1), (2, 3, p, s), 0, e
                               ).astype(jnp.uint8)
    valid = jnp.ones((2, 3, p), bool)
    got = ops.masked_adc_scan(lut, codes, valid)
    for i in range(2):
        for j in range(3):
            want = ref.pq_scan_ref(lut[i, j], codes[i, j], valid[i, j])
            np.testing.assert_allclose(np.asarray(got[i, j]),
                                       np.asarray(want), rtol=1e-5, atol=1e-4)


def test_block_size_invariance():
    """Result must not depend on the BlockSpec tiling — pure tiling property."""
    key = jax.random.PRNGKey(3)
    s, e, p = 16, 128, 300      # three, two and one lane blocks, padded
    lut = jax.random.normal(key, (s, e))
    codes = jax.random.randint(jax.random.fold_in(key, 1), (p, s), 0, e
                               ).astype(jnp.uint8)
    valid = jnp.ones((p,), bool)
    outs = [pq_scan(lut, codes, valid, bp=bp, interpret=True)
            for bp in (128, 256, 384)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-6, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 6), st.integers(1, 8), st.integers(2, 5),
       st.integers(1, 70), st.integers(0, 2 ** 31 - 1))
def test_hit_count_property(b_blocks, s, log_e, p, seed):
    """Property: hit-count totals are bounded by ±S and exactly match the
    oracle for arbitrary shapes/seeds."""
    e = 2 ** log_e
    key = jax.random.PRNGKey(seed)
    codes = jax.random.randint(key, (p, s), 0, e).astype(jnp.uint8)
    table = jax.random.randint(jax.random.fold_in(key, 1), (s, e), -1, 2
                               ).astype(jnp.int8)
    valid = jnp.ones((p,), bool)
    got = hit_count(table, codes, valid, bp=min(32, p), interpret=True)
    want = ref.hit_count_ref(table, codes, valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(jnp.max(jnp.abs(got))) <= s


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 4), st.integers(1, 6), st.integers(1, 50),
       st.integers(0, 2 ** 31 - 1))
def test_selective_lut_mask_property(b, s, e, seed):
    """Property: every LUT value is <= tau^2 after masking (L2): kept values
    pass the threshold, pruned are substituted with exactly tau^2."""
    key = jax.random.PRNGKey(seed)
    qsub = jax.random.normal(key, (b, s, 2))
    entries = jax.random.normal(jax.random.fold_in(key, 1), (s, e, 2))
    esq = jnp.sum(entries ** 2, -1)
    tau = jax.random.uniform(jax.random.fold_in(key, 2), (b, s),
                             minval=0.1, maxval=3.0)
    lut, hit = ops.build_selective_lut(qsub, entries, esq, tau, metric="l2")
    assert bool(jnp.all(lut <= (tau * tau)[..., None] + 1e-5))
    # hit table values only in {-1, 0, 1}
    assert set(np.unique(np.asarray(hit))).issubset({-1, 0, 1})


@pytest.mark.parametrize("batched", [False, True])
def test_slab_onehot_dot_dtypes(batched):
    """Pin the MXU-path accumulation dtype of the shared SLAB one-hot
    helper: int32 for the hit-count path, f32 for the ADC path — and exact
    agreement with the plain-gather formulation in both."""
    key = jax.random.PRNGKey(21)
    s, e, p = 13, 32, 29                      # non-SLAB-multiple S
    lead = (3,) if batched else ()
    codes = jax.random.randint(key, (*lead, p, s), 0, e)
    tab_i = jax.random.randint(jax.random.fold_in(key, 1), (*lead, s, e),
                               -1, 2).astype(jnp.int8)
    tab_f = jax.random.normal(jax.random.fold_in(key, 2), (*lead, s, e))

    def onehot(tab, out_dtype):
        # the helper takes one query row: codes (P, S), tab (S, E) → (1, P)
        fn = lambda c, t: ops.slab_onehot_dot(  # noqa: E731
            c, t, n_entries=e, out_dtype=out_dtype)[0]
        return jax.vmap(fn)(codes, tab) if batched else fn(codes, tab)

    got_i = onehot(tab_i.astype(jnp.int32), jnp.int32)
    got_f = onehot(tab_f, jnp.float32)
    assert got_i.dtype == jnp.int32
    assert got_f.dtype == jnp.float32

    def gather_sum(tab):
        vals = jnp.take_along_axis(tab[..., None, :, :], codes[..., None],
                                   axis=-1)[..., 0]          # (..., P, S)
        return jnp.sum(vals, axis=-1)

    want_i = gather_sum(tab_i.astype(jnp.int32))
    want_f = gather_sum(tab_f)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(got_f), np.asarray(want_f),
                               rtol=1e-5, atol=1e-5)

    # f32 accumulation of small-int tables is still exact (the fused kernel
    # relies on this to share one one-hot between both stages)
    got_fi = onehot(tab_i.astype(jnp.float32), jnp.float32)
    np.testing.assert_array_equal(np.asarray(got_fi).astype(np.int32),
                                  np.asarray(want_i))


@pytest.mark.parametrize("shape", [(64, 96, 128), (17, 40, 37),
                                   (128, 200, 300), (1, 8, 9)])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_ivf_filter_sweep(shape, metric):
    """4th kernel: fused filtering distances vs oracle (+ rank agreement
    with the exact L2 ordering, which is what stage A consumes)."""
    nq, d, c = shape
    key = jax.random.PRNGKey(nq + d + c)
    q = jax.random.normal(key, (nq, d))
    cents = jax.random.normal(jax.random.fold_in(key, 1), (c, d))
    csq = jnp.sum(cents ** 2, -1)
    got = ops.filter_scores(q, cents, csq, metric=metric)
    want = ref.ivf_filter_ref(q, cents, csq, metric=metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    if metric == "l2":  # rank-equivalence with true distances
        # tie-tolerant: the ordering induced by the kernel scores must be a
        # valid sort of the true distances (exact argsort equality is not
        # stable for centroid pairs closer than f32 resolution)
        true_d = np.asarray(jnp.sum((q[:, None] - cents[None]) ** 2, -1))
        true_at_rank = np.take_along_axis(
            true_d, np.argsort(np.asarray(got), axis=1), axis=1)
        np.testing.assert_allclose(true_at_rank, np.sort(true_d, axis=1),
                                   rtol=1e-5, atol=1e-4)
