"""A run whose timed path is broken underneath comes out not correct.

Each case drives the whole harness at CPU size with no chip check, with a
fault planted in the program. In the engine's dispatch
(``AnnServeEngine._dispatch``, the jitted search of one padded batch): a
tick that hands back the previous tick's answers (the state left
unchanged), half of a batch left out (its rows get the other half's
answers), an answer altered where it is produced (one id of every row
replaced), half of ``nprobe`` probed, and 2k candidates reranked instead
of 4k. In the build: points left out of the cluster layout, as a capacity
cap would drop them. There is no exchange between chips: every cell has
one.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import harness

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _stale(orig):
    first = {}

    def dispatch(self, qb, *a):
        out = orig(self, qb, *a)
        return first.setdefault(qb.shape, out)
    return dispatch


def _half(orig):
    def dispatch(self, qb, *a):
        h = qb.shape[0] // 2
        s, i = orig(self, qb, *a)
        return (jnp.concatenate([s[:h], s[:qb.shape[0] - h]]),
                jnp.concatenate([i[:h], i[:qb.shape[0] - h]]))
    return dispatch


def _altered(orig):
    def dispatch(self, qb, *a):
        s, i = orig(self, qb, *a)
        i = np.array(i)
        i[:, -1] = (i[:, -1] + 1) % self.index.data.codes.shape[0]
        return s, jnp.asarray(i)
    return dispatch


def _search_with(**change):
    """A dispatch that runs the engine's own search with ``change`` made to
    its arguments (``k`` maps to the value)."""
    def fault(_orig):
        def dispatch(self, qb, k, mode, nprobe, side):
            fn, kw = self.search_call(k, mode, nprobe, side)
            kw.update({key: f(kw) for key, f in change.items()})
            return fn(self.index.data, qb, **kw)
        return dispatch
    return fault


_half_nprobe = _search_with(nprobe=lambda kw: kw["nprobe"] // 2)
_two_k_candidates = _search_with(rerank=lambda kw: 2 * kw["k"])


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(run_tiny, workload):
    out = run_tiny(workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault", [_stale, _half, _altered, _half_nprobe,
                                   _two_k_candidates])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(run_tiny, monkeypatch, workload,
                                          fault):
    cls = harness.import_program()["AnnServeEngine"]
    monkeypatch.setattr(cls, "_dispatch", fault(cls._dispatch))
    out = run_tiny(workload)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_points_left_out_of_the_build_are_not_correct(run_tiny, monkeypatch,
                                                      workload):
    prog = harness.import_program()
    build = prog["build_streaming"]

    def capped(*a, **kw):
        # every cluster's last live slot emptied, as a capacity cap would
        index = build(*a, **kw)
        ids = np.array(index.ivf.point_ids)
        last = (ids >= 0).sum(axis=1) - 1
        rows = np.nonzero(last >= 0)[0]
        ids[rows, last[rows]] = -1
        ivf = index.ivf._replace(point_ids=jnp.asarray(ids),
                                 valid=jnp.asarray(ids >= 0))
        return index._replace(ivf=ivf)

    monkeypatch.setattr(harness, "import_program",
                        lambda root=harness.ROOT: dict(
                            prog, build_streaming=capped))
    out = run_tiny(workload)
    assert out["checks"]["ids_not_once"]["value"] > 0
    assert not out["correct"], out["checks"]
