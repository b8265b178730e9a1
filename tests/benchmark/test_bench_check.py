"""The comparison's arithmetic: per (leaf, agent) gaps and the leaves
left out."""
import numpy as np
import pytest

from benchmarks.chip import check


def _sq(norms):
    return np.square(np.asarray(norms, np.float64))


def test_agents_swapped_show_where_the_stack_norm_hides_them():
    ref = _sq([[1.0, 2.0, 3.0, 4.0], [2.0, 2.0, 2.0, 2.0]])
    swapped = ref[:, [1, 0, 3, 2]]
    keep = np.ones(2, bool)
    assert np.allclose(swapped.sum(1), ref.sum(1))
    gap, (leaf, agent) = check.worst_leaf_gap(swapped, ref, keep)
    # median of the nonzero norms is 2: agent 0 reads |2 - 1| / max(1, 2)
    assert gap == pytest.approx(0.5) and leaf == 0 and agent == 0
    assert check.worst_leaf_gap(ref, ref, keep)[0] == 0.0


def test_gap_is_over_the_larger_of_own_and_median_norm():
    ref = _sq([[1.0, 0.0], [4.0, 0.0], [0.01, 0.0]])
    prog = _sq([[1.1, 0.0], [4.0, 0.0], [0.02, 0.0]])
    gap, where = check.worst_leaf_gap(prog, ref, np.ones(3, bool))
    # the inactive agent's zeros are left out of the median (1.0 and 4.0
    # and 0.01 -> 1.0); leaf 0 reads 0.1 / 1, leaf 2 0.01 / 1
    assert gap == pytest.approx(0.1) and where == (0, 0)


def test_an_agent_that_moved_where_the_reference_did_not():
    ref = _sq([[1.0, 0.0]])
    prog = _sq([[1.0, 0.5]])
    assert check.worst_leaf_gap(prog, ref, np.ones(1, bool))[0] == (
        pytest.approx(0.5))
    still = np.zeros((1, 2))
    assert check.worst_leaf_gap(prog, still, np.ones(1, bool))[0] == np.inf
    assert check.worst_leaf_gap(still, still, np.ones(1, bool))[0] == 0.0


def test_median_leaf_gap_does_not_follow_one_leaf():
    ref = _sq([[1.0, 2.0], [2.0, 2.0], [3.0, 2.0], [0.01, 0.01]])
    keep = np.ones(4, bool)
    assert check.median_leaf_gap(ref, ref, keep) == 0.0
    # one small leaf moved ten times as far: the worst leaf follows it
    noisy = ref.copy()
    noisy[3, 0] = _sq(0.1)
    assert check.worst_leaf_gap(noisy, ref, keep)[0] == pytest.approx(0.045)
    assert check.median_leaf_gap(noisy, ref, keep) == 0.0
    # every leaf of agent 1 moved 10% further: the median reads its gap
    # (leaf 3 over the median live norm, 2.0: 0.001 / 2)
    wide = ref.copy()
    wide[:, 1] *= 1.1 ** 2
    assert check.median_leaf_gap(wide, ref, keep) == pytest.approx(
        np.median([0.1, 0.1, 0.1, 0.0005]))
    # a leaf left out counts in no agent's median
    noisy[:3, 1] = wide[:3, 1]
    assert check.median_leaf_gap(noisy, ref, keep) == pytest.approx(0.1)
    assert check.median_leaf_gap(noisy, ref, np.array([1, 1, 1, 0], bool)) == (
        pytest.approx(0.1))
    assert check.median_leaf_gap(noisy, ref, np.array([0, 0, 0, 1], bool)) > 1


def test_leaves_left_out_by_the_first_block_that_moved():
    first = _sq([[0.0, 0.0]] * 3)
    later = _sq([[1.0, 1.0], [1e-4, 0.0], [2.0, 0.5]])
    assert check.kept_leaves([first, later]).tolist() == [True, False, True]
    assert check.kept_leaves([first, first]).all()
