"""Property tests for Pareto/PHV machinery (hypothesis)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.pareto import (pareto_mask, pareto_front, hypervolume,
                               hypervolume_mc, dominates_ref,
                               sample_efficiency)

pts3 = st.lists(
    st.tuples(st.floats(0.1, 0.9), st.floats(0.1, 0.9), st.floats(0.1, 0.9)),
    min_size=1, max_size=24)


@given(pts3)
@settings(max_examples=40, deadline=None)
def test_hypervolume_matches_monte_carlo(pts):
    y = np.array(pts)
    ref = np.ones(3)
    hv = hypervolume(y, ref)
    mc = hypervolume_mc(y, ref, lo=np.zeros(3), n=60_000, seed=1)
    assert hv == pytest.approx(mc, abs=0.02)


@given(pts3)
@settings(max_examples=40, deadline=None)
def test_pareto_front_is_nondominated(pts):
    y = np.array(pts)
    front = pareto_front(y)
    for i in range(len(front)):
        dominated = np.all(front <= front[i], axis=1) & \
            np.any(front < front[i], axis=1)
        assert not dominated.any()


@given(pts3, pts3)
@settings(max_examples=30, deadline=None)
def test_hypervolume_monotone_in_points(a, b):
    """Adding points can only grow the hypervolume."""
    ya, yab = np.array(a), np.array(a + b)
    ref = np.ones(3)
    assert hypervolume(yab, ref) >= hypervolume(ya, ref) - 1e-12


@given(pts3)
@settings(max_examples=30, deadline=None)
def test_hypervolume_only_counts_front(pts):
    """Dominated points contribute nothing."""
    y = np.array(pts)
    ref = np.ones(3)
    assert hypervolume(y, ref) == pytest.approx(
        hypervolume(pareto_front(y), ref), rel=1e-9)


def test_hv_known_value_2d():
    y = np.array([[0.5, 0.5]])
    assert hypervolume(y, [1.0, 1.0]) == pytest.approx(0.25)
    y2 = np.array([[0.5, 0.5], [0.25, 0.75]])
    assert hypervolume(y2, [1.0, 1.0]) == pytest.approx(0.25 + 0.25 * 0.25)


def test_hv_known_value_3d():
    y = np.array([[0.5, 0.5, 0.5]])
    assert hypervolume(y, [1, 1, 1]) == pytest.approx(0.125)


def test_sample_efficiency():
    ref = np.array([1.0, 1.0, 1.0])
    y = np.array([[0.5, 0.5, 0.5], [1.5, 0.5, 0.5], [0.9, 0.9, 0.9]])
    assert sample_efficiency(y, ref) == pytest.approx(2 / 3)
    assert dominates_ref(y, ref).tolist() == [True, False, True]
