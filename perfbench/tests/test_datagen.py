"""The copied generator: one seed gives one corpus and one pool; another
seed another; seeds beyond 32 bits are valid and distinct."""
import numpy as np

from benchlib import datagen

DATA = {"dim": 8, "metric": "l2", "n_points": 500, "chunk_rows": 200,
        "generator": {"n_modes": 4, "anisotropy": 4.0, "power": 1.5,
                      "mixture_seed": 1}}


def _draw(seed, data=DATA):
    key = datagen.seed_key(seed)
    chunks = datagen.corpus_chunks(data, key)
    return (np.concatenate([np.asarray(c) for c in chunks]),
            np.asarray(datagen.query_pool(data, key, 64)))


def test_same_seed_same_inputs():
    p1, q1 = _draw(2 ** 33 + 5)
    p2, q2 = _draw(2 ** 33 + 5)
    assert p1.shape == (500, 8) and q1.shape == (64, 8)
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_array_equal(q1, q2)


def test_other_seed_other_inputs():
    p1, q1 = _draw(11)
    for other in (12, 11 + 2 ** 32):
        p2, q2 = _draw(other)
        assert not np.array_equal(p1, p2)
        assert not np.array_equal(q1, q2)


def test_ip_corpus_norms_and_unit_queries():
    p, q = _draw(3, dict(DATA, metric="ip"))
    norms = np.linalg.norm(p, axis=1)
    assert norms.min() >= 1.0 - 1e-5 and norms.max() < 1.3 + 1e-5
    np.testing.assert_allclose(np.linalg.norm(q, axis=1), 1.0, rtol=1e-5)


def test_mixture_is_the_configurations_not_the_seeds():
    gen, key = DATA["generator"], datagen.seed_key
    (mu1, sc1, _), _ = datagen._mixture(gen, 8, key(11))
    (mu2, sc2, _), _ = datagen._mixture(gen, 8, key(12))
    np.testing.assert_array_equal(mu1, mu2)
    np.testing.assert_array_equal(sc1, sc2)
    (mu3, _, _), _ = datagen._mixture(dict(gen, mixture_seed=2), 8, key(11))
    assert not np.array_equal(mu1, mu3)
