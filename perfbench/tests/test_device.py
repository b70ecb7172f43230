"""The harness measures on a TPU it has peaks for, and nowhere else."""
import types

import pytest

from benchlib import harness

PEAKS = harness.load_json(harness.BENCH_DIR / "peaks.json")


def _devs(platform, kind, n=1):
    return [types.SimpleNamespace(platform=platform, device_kind=kind)] * n


def test_known_tpu_is_accepted():
    assert harness.check_device(_devs("tpu", "TPU v5 lite"), 1,
                                PEAKS)["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("devs, chips", [
    (_devs("cpu", "cpu"), 1),                 # no accelerator
    (_devs("gpu", "NVIDIA H100"), 1),         # not a TPU
    (_devs("tpu", "TPU v9 imaginary"), 1),    # no row in the peaks table
    (_devs("tpu", "TPU v5 lite"), 4),         # fewer chips than the cell
    ([], 1),
])
def test_other_devices_are_refused(devs, chips):
    with pytest.raises(harness.BenchError):
        harness.check_device(devs, chips, PEAKS)


def test_run_refuses_the_cpu():
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.run("deep10m-l2.h2-batch128", 1, 1.0, False, t_process=0.0)


def test_a_checkout_without_the_program_is_refused(tmp_path):
    with pytest.raises(harness.BenchError, match="not in this checkout"):
        harness.import_program(tmp_path)
