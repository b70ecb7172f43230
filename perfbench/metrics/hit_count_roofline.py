"""The hit-count kernel's share of its roofline, in percent: the least time
the chip could take for the work the window asked of it
(``benchlib.roofline.hit_count`` over the live points of every probed
cluster; memory-bound at these shapes) over the device time of the
kernel's events in the trace."""
from benchlib import roofline

#: device op names of the hit-count kernel (``kernels/hit_count.py``)
PATTERN = r"hit_count"


def read(r):
    t = r.trace.op_seconds(PATTERN) if r.trace is not None else 0.0
    if t <= 0 or r.probed_points <= 0:
        return None
    ops, nbytes = roofline.hit_count(r.probes, r.probed_points,
                                     r.subspaces, r.entries)
    least, _bound = roofline.least_seconds(ops, nbytes,
                                           r.peaks["int8_ops_per_s"],
                                           r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
