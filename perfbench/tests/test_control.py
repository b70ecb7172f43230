"""The control -- the reference put in the program's place with its LUT
products one precision step lower (the three-pass bf16 product) -- fails
each cell's limits, while the program passes them, at CPU size."""
import json

import pytest

import control
from benchlib import harness
from conftest import tiny_files

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCH["workloads"]])
def test_control_fails_and_program_passes(workload):
    rows = list(control.readings(tiny_files(workload), [21, 22],
                                 control_seeds=2, seconds=0.5,
                                 require_tpu=False))
    assert len(rows) == 2
    for row in rows:
        lim = row["limits"]
        assert all(row["program"][k] <= v for k, v in lim.items()), row
        assert any(row["control"].get(k, 0) > v for k, v in lim.items()), row
