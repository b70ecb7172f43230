"""The general traffic generator: reads a traffic file's parameters and
yields the requests of one run.

A traffic file (``perfbench/traffic/<name>.json``) sets:

* ``loop``: "closed" (one client; the next request is sent when the last
  is answered), the one loop the harness drives today;
* ``rows_per_request``, ``k``, ``mode``, ``nprobe``: what each request asks;
* ``n_pool``: queries made from the seed per run; requests take them in
  order and wrap around;
* ``n_eval``: the first ``n_eval`` pool queries form the evaluation set
  of ``recall_at_10`` (0: no recall);
* ``batch_buckets`` and ``engine``: the engine's batch sizes and options;
* ``check_sample``: how many answered queries the comparison checks.
"""
from __future__ import annotations

import numpy as np


def pool_rows(start: int, rows: int, n_pool: int) -> np.ndarray:
    """Pool indices of a request of ``rows`` queries starting at ``start``
    (wrapping around the pool)."""
    return (start + np.arange(rows)) % n_pool


def sample_rows(n_answered: int, n: int, seed: int) -> np.ndarray:
    """Sorted indices of ``min(n, n_answered)`` answers to compare, drawn
    from the seed."""
    rng = np.random.default_rng([seed, 1])
    return np.sort(rng.choice(n_answered, size=min(n, n_answered),
                              replace=False))
