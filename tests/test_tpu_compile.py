"""The main-path kernels compile for a TPU v5e chip at real widths.

The TPU compiler is installed even where no chip is attached, and it
compiles for a *described* chip: block shapes the chip refuses, layouts
Mosaic cannot lower and kernels that overflow the scoped VMEM all fail
here, at no chip time — interpret mode on the CPU catches none of them.

Widths are DEEP-like (D = 96, S = 48 subspaces, E = 256 entries, P = 1024
points per probe, 16 probes, 8 queries) plus the three-stage kernel at
Text-to-Image widths (S = 100). Nothing runs: these are compiles only.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_three_stage as f3
from repro.kernels import fused_two_stage as f2
from repro.kernels import hit_count as hc
from repro.kernels import pq_scan as pq
from repro.kernels import selective_lut as sl
from repro.rt import intersect as rt_intersect

S, E, P, NP, Q = 48, 256, 1024, 16, 8
N_CELLS, CAP = 1024, 64          # a 32x32 grid over C = 4096 centroids
CAP_C = 320


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 topology, with the persistent
    compilation cache off (such compiles cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


f32, i8, u8, i32, b1 = jnp.float32, jnp.int8, jnp.uint8, jnp.int32, jnp.bool_


def _two_stage_specs(s):
    return [((Q, NP, s, E), f32), ((Q, NP, s, E), i8), ((Q, NP, P, s), u8),
            ((Q, NP, P), b1)]


def _rt_specs():
    return [((Q,), f32)] * 3


CASES = {
    "selective_lut_l2": (
        lambda *a: sl.selective_lut(*a, metric="l2"),
        [((Q * NP, S), f32)] * 2 + [((S, E), f32)] * 3 + [((Q * NP, S), f32)]),
    "selective_lut_ip": (
        lambda *a: sl.selective_lut(*a, metric="ip"),
        [((Q * NP, S), f32)] * 2 + [((S, E), f32)] * 3 + [((Q * NP, S), f32)]),
    "hit_count": (hc.hit_count, [((S, E), i8), ((P, S), u8), ((P,), b1)]),
    "pq_scan": (pq.pq_scan, [((S, E), f32), ((P, S), u8), ((P,), b1)]),
    "fused_two_stage": (
        lambda *a: f2.fused_two_stage(*a, cap_c=CAP_C), _two_stage_specs(S)),
    "fused_three_stage_deep": (
        lambda *a: f3.fused_three_stage(*a, cap_c=CAP_C),
        _two_stage_specs(S) + _rt_specs()
        + [((N_CELLS, CAP), f32)] * 3 + [((Q, NP), i32)]),
    "fused_three_stage_tti": (
        lambda *a: f3.fused_three_stage(*a, cap_c=CAP_C, metric="ip"),
        _two_stage_specs(100) + _rt_specs()
        + [((N_CELLS, CAP), f32)] * 3 + [((Q, NP), i32)]),
    "rt_intersect": (
        rt_intersect.sphere_hits,
        _rt_specs() + [((N_CELLS, 4), f32), ((N_CELLS,), f32)]
        + [((N_CELLS, CAP), f32)] * 3),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = CASES[name]
    compiled = jax.jit(fn).lower(*_shapes(one_chip, *specs)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') >= 1
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0 and mem.output_size_in_bytes > 0
