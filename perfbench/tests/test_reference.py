"""The reference's hit-count lookup (bit masks, selects and shifts) equals
the plain table gather it stands for."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import reference as ref


@pytest.mark.parametrize("entries", [64, 256])
def test_hit_counts_equal_the_gather(entries):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (3, 4, 6, entries)                     # (Qb, np, S, E)
    dist = jax.random.uniform(k1, shape)
    tau_sq = jax.random.uniform(k2, shape[:3] + (1,))
    outer, inner = dist <= tau_sq, dist <= 0.25 * tau_sq
    codes = jax.random.randint(k3, (3, 4, 50, 6), 0, entries).astype(
        jnp.uint8)
    hit = inner.astype(jnp.int32) - (~outer).astype(jnp.int32)
    want = jnp.take_along_axis(hit, jnp.swapaxes(codes, 2, 3).astype(
        jnp.int32), axis=3).sum(axis=2)
    np.testing.assert_array_equal(ref.hit_counts(inner, outer, codes), want)


def test_round_bf16_matches_a_cast():
    x = jax.random.normal(jax.random.PRNGKey(1), (1000,)) * 100
    np.testing.assert_array_equal(
        ref.round_bf16(x), x.astype(jnp.bfloat16).astype(jnp.float32))
