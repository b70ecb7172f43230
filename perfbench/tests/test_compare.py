"""The comparison's numbers on answers whose gaps are known by hand."""
import numpy as np

from benchlib import compare, harness

INF = np.inf


def test_topk_gap_compares_sorted_scores_position_by_position():
    ref = np.array([[1.0, 2.0, 3.0]])
    # the same scores in another order: nothing selected differs
    assert compare.topk_gap(np.array([[3.0, 1.0, 2.0]]), ref, "l2") == 0.0
    # a worse third answer in place of the reference's 3.0
    assert compare.topk_gap(np.array([[1.0, 2.0, 5.0]]), ref, "l2") == 2 / 3
    # ip sorts best (largest) first
    assert compare.topk_gap(np.array([[3.0, 2.0, 0.5]]), ref, "ip") == 0.5


def test_topk_gap_of_a_missing_answer_is_inf():
    ref = np.array([[1.0, 2.0, 3.0]])
    assert compare.topk_gap(np.array([[1.0, 2.0, INF]]), ref, "l2") == INF


def test_score_gap_of_an_id_the_reference_cannot_score_is_inf():
    got = np.array([[1.0, 2.0]])
    assert compare.score_gap(got, np.array([[1.0, 2.5]])) == 0.5 / 2.5
    assert compare.score_gap(got, np.array([[1.0, np.nan]])) == INF


def test_ids_not_once_counts_missing_doubled_and_foreign_ids():
    layout = np.array([[0, 1, -1], [1, 3, 7]])   # of ids 0..4
    # 1 held twice, 2 and 4 missing, 7 no corpus id
    assert harness.ids_not_once(layout, 5) == 4
    assert harness.ids_not_once(np.array([[0, 2], [1, -1]]), 3) == 0
