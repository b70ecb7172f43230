"""recall@10 is taken over the first ``n_eval`` pool queries, whatever
number of queries the window answered."""
import types

import numpy as np

from benchlib import harness

TRF = {"n_eval": 8, "rows_per_request": 4, "k": 2, "n_pool": 12,
       "mode": "H2", "nprobe": 4}


def _req(rows, lap):
    # the answer to pool row r on lap l is (100 l + r, -1)
    ids = np.stack([100 * lap + rows, -np.ones_like(rows)], axis=1)
    return types.SimpleNamespace(ids=ids, done=True)


def _served(n_answered):
    rows = [(np.arange(s, s + 4) % 12) for s in range(0, n_answered, 4)]
    laps = [s // 12 for s in range(0, n_answered, 4)]
    return harness.Served(rows=rows, t0=0.0, t_end=1.0,
                          reqs=[_req(r, lap) for r, lap in zip(rows, laps)])


class _Engine:
    """Serves any request at once with lap 9's answers."""

    def submit(self, q, **kw):
        self.last = _req(q[:, 0].astype(int), 9)
        return self.last

    def run(self):
        pass


def test_same_set_at_one_and_two_times_n_eval():
    pool = np.arange(12, dtype=np.float32)[:, None].repeat(3, axis=1)
    once = harness.finish_eval(_Engine(), TRF, pool, _served(8))
    twice = harness.finish_eval(_Engine(), TRF, pool, _served(16))
    np.testing.assert_array_equal(once, twice)
    np.testing.assert_array_equal(once[:, 0], np.arange(8))


def test_rest_of_the_set_served_after_a_short_window():
    pool = np.arange(12, dtype=np.float32)[:, None].repeat(3, axis=1)
    got = harness.finish_eval(_Engine(), TRF, pool, _served(4))
    np.testing.assert_array_equal(got[:4, 0], np.arange(4))
    np.testing.assert_array_equal(got[4:, 0], 900 + np.arange(4, 8))
