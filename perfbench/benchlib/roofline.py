"""Operations and bytes the algorithm needs per kernel, from what the window
asked of it, and the least time the chip could take for them.

The counts are of the work the algorithm asks for, not of how today's code
moves data: a later change that moves fewer bytes, or skips the padded
slots of a cluster, then reads as nearer its roofline against the same
yardstick.
"""
from __future__ import annotations


def hit_count(probes: int, points: int, subspaces: int,
              entries: int) -> tuple[float, float]:
    """(int ops, bytes) of the hit-count scan over ``probes`` (query,
    probed cluster) pairs holding ``points`` live points in all: per pair
    the (S, E) int8 hit table read once; per live point its S one-byte codes
    read once, S hit codes accumulated, and one int32 count written."""
    ops = points * subspaces
    nbytes = probes * subspaces * entries + points * (subspaces + 4)
    return float(ops), float(nbytes)


def least_seconds(ops: float, nbytes: float, peak_ops: float,
                  peak_bytes: float) -> tuple[float, str]:
    """The larger of the compute and memory bounds, and which bound it is."""
    t_ops, t_mem = ops / peak_ops, nbytes / peak_bytes
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
