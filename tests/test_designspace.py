"""Design-space encode/decode round-trip properties (hypothesis)."""
import jax
import jax.numpy as jnp
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


_DECODE_JIT = jax.jit(SPACE.decode)


def _decode_batches(param: int, index: int):
    """Index arrays of shape (8,), (b, 8) and (b1, b2, 8): column ``param``
    holds ``index``; the other columns run over 0..13, past each
    cardinality too."""
    k = SPACE.choice_table().shape[1]
    rows = (np.arange(3 * k)[:, None] + 5 * np.arange(SPACE.n_params)) % k
    rows[:, param] = index
    rows = rows.astype(np.int32)
    return rows[0], rows, rows.reshape(3, k, SPACE.n_params)


@pytest.mark.parametrize("index", range(14))
@pytest.mark.parametrize("param", SPACE.names)
def test_decode_equals_padded_table(param, index):
    """``decode`` gives the padded choice table's values, bit for bit, for
    every choice and for indices past a parameter's cardinality (the last
    choice), in the default float dtype with x64 off and on."""
    tab = SPACE.choice_table()
    assert tab.shape[1] == 14
    for idx in _decode_batches(SPACE.names.index(param), index):
        want = tab[np.arange(SPACE.n_params), idx]
        for x64, dtype in ((False, np.float32), (True, np.float64)):
            with jax.enable_x64(x64):
                for fn in (SPACE.decode, _DECODE_JIT):
                    got = fn(jnp.asarray(idx))
                    assert sorted(got) == sorted(SPACE.names)
                    for i, name in enumerate(SPACE.names):
                        assert got[name].dtype == dtype
                        assert got[name].shape == idx.shape[:-1]
                        np.testing.assert_array_equal(
                            np.asarray(got[name]),
                            want[..., i].astype(dtype), strict=True)


def test_decode_lowers_without_gather():
    """The decode of a batch is elementwise selects, never a gather from
    the choice table (one scalar slice per design and parameter)."""
    idx = jnp.zeros((4096, SPACE.n_params), jnp.int32)
    text = jax.jit(SPACE.decode).lower(idx).as_text()
    assert "gather" not in text
