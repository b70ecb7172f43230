"""Pallas TPU kernels for JUNO's compute hot-spots (paper §4.2/§5.3/§5.4):

    selective_lut — fused pairwise-dist + threshold mask + hit table
                    (the RT-core stage, re-mapped per DESIGN.md §2)
    pq_scan       — masked ADC accumulation as one-hot·LUT MXU contraction
                    (the Tensor-core A×B(=1) trick, TPU-native)
    hit_count     — int8 reward/penalty scan (aggressive approximation)
    ivf_filter    — fused stage-A filtering distances (the cuBLAS
                    x^2-2xq^T+q^2 trick, §5.3, MXU-native)
    fused_two_stage — hit-count prefilter kernel → top-C survivor
                    threshold → survivor-masked ADC kernel + candidate
                    compaction (the RT→TC pipelining of §5.5, DESIGN.md §3)
    fused_three_stage — the same with each probe's RT sphere test folded
                    into the counts kernel
    onehot        — the shared one-hot MXU contraction and the chip's
                    (8, 128) tile rules

The cluster-granularity RT stage (sphere-intersection prefilter over the
centroid grid) lives in ``repro.rt``; its kernel is dispatched from here
(``ops.rt_sphere_hits``). Contracts and grid/VMEM budgets for everything:
docs/kernels.md.

``ops`` holds the jit'd public wrappers and the platform's default path
(``default_impl``: the kernels on a TPU, the jnp reference elsewhere;
interpret mode or host paths off-TPU — see docs/kernels.md);
``ref`` holds the pure-jnp oracles every kernel is tested against.
"""
from . import ops, ref  # noqa: F401
