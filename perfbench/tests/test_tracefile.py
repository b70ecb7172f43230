"""The trace reduction: idle share, kernel time and the breakdown, on a
hand-made trace with known answers and on a recorded chip trace checked
against a plain sampling of its timeline."""
import json
from pathlib import Path

import numpy as np
import pytest

from benchlib.tracefile import Trace

DATA = Path(__file__).parent / "data"


def _hand_made():
    # window [0, 10] s; device busy [1,3] (two overlapping ops), [5,6],
    # [9.5, 11] (clipped at 10); host spans name the gaps
    ops = {"/device:TPU:0": [
        ("vmap_jit_hit_count_.1", 1.0, 1.5), ("sort.1", 2.0, 1.0),
        ("vmap_jit_hit_count_.1", 5.0, 1.0), ("copy.3", 9.5, 1.5)]}
    spans = [("bench.window", 0.0, 10.0), ("bench.step", 0.6, 6.5),
             ("bench.submit", 6.5, 9.0), ("bench.wait", 3.0, 5.0)]
    return Trace(ops=ops, spans=spans)


def test_busy_and_idle_on_a_hand_made_trace():
    tr = _hand_made()
    assert tr.window_s() == 10.0
    assert tr.busy_s() == pytest.approx(2.0 + 1.0 + 0.5)
    assert tr.op_seconds(r"hit_count") == pytest.approx(2.5)
    assert tr.top_ops(2) == [["vmap_jit_hit_count_.1", 2.5],
                             ["sort.1", 1.0]]
    # gaps: [6, 9.5] under bench.submit (its middle 7.75), [3, 5] under
    # bench.wait (innermost), [0, 1] under no span
    assert tr.idle_gaps() == [["bench.submit", 3.5], ["bench.wait", 2.0],
                              ["no span", 1.0]]


def test_recorded_trace_against_sampling():
    rec = json.loads((DATA / "trace_excerpt.json").read_text())
    tr = Trace(ops={d: [tuple(e) for e in evs]
                    for d, evs in rec["ops"].items()},
               spans=[tuple(s) for s in rec["spans"]])
    lo, hi = tr.window()
    dev = sorted(tr.ops)[0]
    t = np.linspace(lo, hi, 200_001)
    busy = np.zeros_like(t, bool)
    for _, start, dur in tr.ops[dev]:
        busy |= (t >= start) & (t < start + dur)
    step = (hi - lo) / 200_000
    assert tr.busy_s() == pytest.approx(busy.mean() * (hi - lo),
                                        abs=50 * step)
    assert rec["expected"]["kernel_pattern"]
    assert tr.op_seconds(rec["expected"]["kernel_pattern"]) > 0
    gaps = tr.idle_gaps(10)
    assert all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in tr.idle_gaps(10_000)) == pytest.approx(
        tr.window_s() - tr.busy_s(), rel=1e-9, abs=1e-12)
