"""Design-space encode/decode round-trip properties (hypothesis)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.perfmodel.designspace import SPACE, A100_REFERENCE, DESIGN_A


idx_strategy = st.tuples(*[st.integers(0, int(c) - 1)
                           for c in SPACE.cardinalities])


@given(idx_strategy)
@settings(max_examples=100, deadline=None)
def test_flat_roundtrip(idx):
    idx = np.array(idx, dtype=np.int32)
    flat = SPACE.idx_to_flat(idx)
    assert 0 <= flat < SPACE.size
    back = SPACE.flat_to_idx(flat)
    assert np.array_equal(back, idx)


@given(idx_strategy)
@settings(max_examples=50, deadline=None)
def test_decode_members(idx):
    idx = np.array(idx, dtype=np.int32)
    vals = SPACE.decode_np(idx)
    for i, name in enumerate(SPACE.names):
        assert float(vals[name]) in SPACE.choices[i]


def test_encode_decode_design_a():
    idx = SPACE.encode({**DESIGN_A, "gbuf_mb": 32})   # 40MB not in space
    vals = SPACE.decode_np(idx)
    assert int(vals["core_count"]) == 64
    assert int(vals["sa_dim"]) == 32


def test_encode_nearest_a100():
    idx = SPACE.encode_nearest(A100_REFERENCE)
    vals = SPACE.decode_np(idx)
    assert int(vals["core_count"]) == 108
    assert int(vals["gbuf_mb"]) == 32      # nearest member to 40 MB


def test_neighbors_validity():
    idx = SPACE.encode_nearest(A100_REFERENCE)
    nbrs = SPACE.neighbors(idx)
    assert len(nbrs) >= SPACE.n_params      # most params have both directions
    for n in nbrs:
        assert (n >= 0).all() and (n < SPACE.cardinalities).all()
        assert np.abs(n - idx).sum() == 1


def test_sample_shape_and_range():
    rng = np.random.default_rng(0)
    s = SPACE.sample(rng, 1000)
    assert s.shape == (1000, SPACE.n_params)
    assert (s >= 0).all() and (s < SPACE.cardinalities[None, :]).all()
