"""Shared pieces of the benchmark's own tests (run them by path:
``python -m pytest perfbench/tests``): the benchmark's directory on the
import path, and its cells cut to a size the CPU holds."""
import copy
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib import harness  # noqa: E402

#: a DEEP-shaped index cut to CPU size (16-d, 6,000 points, 32 clusters)
TINY_DATA = {"dim": 16, "n_points": 6000, "chunk_rows": 3000,
             "generator": {"n_modes": 16, "anisotropy": 4.0, "power": 1.5,
                           "mixture_seed": 1}}
TINY_INDEX = {"n_clusters": 32, "n_entries": 64, "sub_dim": 2,
              "kmeans_iters": 4, "calib_queries": 32, "grid_size": 16}


def tiny_files(workload: str, **traffic) -> dict:
    """The cell's files with the configuration cut to CPU size and the
    traffic's sizes cut to match (``traffic`` overrides more keys)."""
    files = copy.deepcopy(harness.cell_files(workload))
    conf, trf = files["config"], files["traffic"]
    metric = conf["dataset"]["metric"]
    conf["dataset"] = dict(TINY_DATA, metric=metric)
    conf["index"] = dict(TINY_INDEX, metric=metric)
    trf.update(n_pool=96, nprobe=4, check_sample=40, rows_per_request=16,
               batch_buckets=[16], n_eval=64 if trf["n_eval"] else 0)
    trf.update(traffic)
    return files


@pytest.fixture
def run_tiny():
    """Runs a CPU-sized cell through the whole harness (no chip check)."""
    def go(workload, seed=7, seconds=1.0, trace=False, **traffic):
        return harness.run(workload, seed, seconds, trace,
                           t_process=time.perf_counter(), require_tpu=False,
                           files=tiny_files(workload, **traffic))
    return go
