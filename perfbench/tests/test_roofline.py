"""The roofline counts on shapes whose work is known by hand."""
import pytest

from benchlib import roofline


def test_hit_count_counts():
    # 6 (query, cluster) pairs holding 50 live points; S = 4, E = 8: per
    # pair a 32-byte table, per point 4 codes and a 4-byte count
    ops, nbytes = roofline.hit_count(6, 50, 4, 8)
    assert ops == 50 * 4
    assert nbytes == 6 * 32 + 50 * 8


def test_hit_count_deep_pass_is_memory_bound():
    # one 128-query pass of the DEEP cell: 16 probes of about 2,441 live
    # points each (10M points over 4,096 clusters), S = 48, E = 256
    probes = 128 * 16
    ops, nbytes = roofline.hit_count(probes, probes * 2441, 48, 256)
    t, bound = roofline.least_seconds(ops, nbytes, 393e12, 819e9)
    assert bound == "memory"
    assert t == pytest.approx(nbytes / 819e9)


def test_least_seconds_picks_the_larger_bound():
    assert roofline.least_seconds(10.0, 1.0, 1.0, 100.0) == (10.0, "compute")
    assert roofline.least_seconds(1.0, 1000.0, 1.0, 100.0) == (10.0, "memory")
