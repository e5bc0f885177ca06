"""Sweep engine + streaming Pareto machinery.

Covers the PR invariants: the vectorized ``pareto_mask`` is a drop-in for
the historical O(n^2) loop (including duplicate-row degeneracies), the
streaming ``ParetoArchive`` equals the batch front, and a truncated
full-space sweep reproduces brute-force evaluation exactly.
"""
import os
import re

import numpy as np
import pytest

from repro.core.pareto import (ParetoArchive, dominates_ref, hypervolume,
                               pareto_front, pareto_mask)
from repro.perfmodel import get_evaluator, make_evaluator
from repro.perfmodel.designspace import SPACE
from repro.perfmodel.sweep import SweepEngine, _unrank
from repro.perfmodel.workload import zoo_suite

SUBSPACE = 50_000


def _reference_pareto_mask(y):
    """The seed repo's O(n^2) Python-loop implementation (oracle)."""
    y = np.asarray(y, dtype=np.float64)
    n = y.shape[0]
    mask = np.ones(n, dtype=bool)
    for i in range(n):
        if not mask[i]:
            continue
        dominated_by_i = np.all(y >= y[i], axis=1) & np.any(y > y[i], axis=1)
        mask &= ~dominated_by_i
        mask[i] = True
        dominates_i = np.all(y <= y[i], axis=1) & np.any(y < y[i], axis=1)
        if dominates_i.any():
            mask[i] = False
    return mask


@pytest.fixture(scope="module")
def engine():
    return SweepEngine(get_evaluator("proxy"), chunk_size=16_384)


@pytest.fixture(scope="module")
def portfolio_engine():
    wls, scen = zoo_suite(archs=("qwen2-moe-a2.7b", "rwkv6-7b"), smoke=True)
    return SweepEngine(make_evaluator(wls, tier="proxy", scenarios=scen),
                       chunk_size=4_096, stall_topk=2)


# ------------------------------------------------------------ pareto_mask
def test_pareto_mask_matches_reference_random():
    rng = np.random.default_rng(0)
    for trial in range(25):
        n = int(rng.integers(1, 400))
        m = int(rng.integers(2, 5))
        y = rng.random((n, m))
        assert np.array_equal(pareto_mask(y), _reference_pareto_mask(y)), trial


def test_pareto_mask_matches_reference_degenerate():
    rng = np.random.default_rng(1)
    # duplicate rows, constant columns, coarse grids with many exact ties
    cases = []
    y = rng.random((120, 3))
    cases.append(np.concatenate([y, y[:40]], axis=0))        # duplicates
    y = rng.random((100, 3)); y[:, 1] = 0.25                 # constant col
    cases.append(y)
    cases.append(np.round(rng.random((300, 3)), 1))          # tie-heavy grid
    cases.append(np.tile(rng.random((1, 4)), (32, 1)))       # all identical
    cases.append(rng.random((1, 3)))                         # single row
    for i, y in enumerate(cases):
        assert np.array_equal(pareto_mask(y), _reference_pareto_mask(y)), i


def test_pareto_mask_empty():
    assert pareto_mask(np.zeros((0, 3))).shape == (0,)


# ---------------------------------------------------------- ParetoArchive
def test_archive_streaming_equals_batch_front():
    rng = np.random.default_rng(2)
    for trial in range(10):
        n = int(rng.integers(1, 600))
        y = rng.random((n, 3))
        if trial % 3 == 0:
            y = np.concatenate([y, y[: max(1, n // 4)]], axis=0)
        arch = ParetoArchive(3)
        k = 0
        while k < len(y):
            b = int(rng.integers(1, 64))
            arch.insert(y[k:k + b], ids=np.arange(k, min(k + b, len(y))))
            k += b
        front = pareto_front(y)
        got = np.array(sorted(map(tuple, arch.y)))
        want = np.array(sorted(map(tuple, front)))
        assert got.shape == want.shape and np.allclose(got, want), trial
        assert arch.n_seen == len(y)
        # PHV of the streamed front == PHV of the full history
        assert hypervolume(arch.y, np.ones(3)) == pytest.approx(
            hypervolume(y, np.ones(3)), rel=1e-12)


def test_archive_ids_track_points():
    y = np.array([[0.5, 0.5], [0.2, 0.8], [0.6, 0.6], [0.1, 0.9]])
    arch = ParetoArchive(2)
    arch.insert(y, ids=np.arange(4))
    assert sorted(arch.ids.tolist()) == [0, 1, 3]            # row 2 dominated


def test_archive_capacity_prunes_by_crowding():
    rng = np.random.default_rng(3)
    arch = ParetoArchive(3, capacity=16)
    for _ in range(20):
        arch.insert(rng.random((100, 3)))
    assert len(arch) <= 16
    assert arch.truncated
    # extremes per objective must survive crowding pruning
    before = arch.y.copy()
    arch.insert(rng.random((200, 3)))
    for j in range(3):
        assert arch.y[:, j].min() <= before[:, j].min() + 1e-12


# ------------------------------------------------------------ SweepEngine
def test_unrank_matches_flat_to_idx():
    import jax.numpy as jnp
    rng = np.random.default_rng(4)
    flat = rng.integers(0, SPACE.size, size=512)
    cards = tuple(int(c) for c in SPACE.cardinalities)
    got = np.asarray(_unrank(jnp.asarray(flat, jnp.int32), cards))
    assert np.array_equal(got, SPACE.flat_to_idx(flat))


def test_truncated_sweep_matches_brute_force(engine):
    res = engine.run(0, SUBSPACE)
    assert res.n_evaluated == SUBSPACE

    evaluator = get_evaluator("proxy")
    ys = evaluator(SPACE.flat_to_idx(np.arange(SUBSPACE)))

    # exact superior-to-reference count
    assert res.n_superior == int(dominates_ref(ys, res.ref_point).sum())
    # exact Pareto front (ids and objective rows)
    front = pareto_front(ys)
    assert len(res.pareto_ids) == len(front)
    assert np.allclose(np.sort(res.pareto_y, axis=0),
                       np.sort(front, axis=0), rtol=1e-6)
    mask = pareto_mask(ys)
    assert np.array_equal(np.sort(res.pareto_ids), np.flatnonzero(mask))
    # per-objective minima + the ids that achieve them
    for o in range(3):
        assert res.topk_val[o][0] == pytest.approx(ys[:, o].min(), rel=1e-6)
        assert ys[int(res.topk_ids[o][0]), o] == pytest.approx(
            ys[:, o].min(), rel=1e-6)


def test_sweep_objectives_match_evaluator(engine):
    """Sweep-path objectives == the evaluator's public fused path."""
    res = engine.run(0, 4096)
    idx = SPACE.flat_to_idx(res.pareto_ids)
    direct = get_evaluator("proxy").objectives(idx)
    assert np.allclose(res.pareto_y, direct, rtol=1e-6)


def test_sweep_checkpoint_resume(engine, tmp_path):
    ck = os.path.join(tmp_path, "sweep_ck")
    full = engine.run(0, 40_000)
    engine.run(0, 20_000, checkpoint_path=ck)
    res = engine.run(0, 40_000, resume_from=ck)
    assert res.n_evaluated == full.n_evaluated
    assert res.n_superior == full.n_superior
    assert np.array_equal(res.pareto_ids, full.pareto_ids)
    assert np.allclose(res.pareto_y, full.pareto_y)
    assert np.allclose(res.topk_val, full.topk_val)


def test_sweep_checkpoint_rejects_mismatched_config(engine, tmp_path):
    ck = os.path.join(tmp_path, "sweep_ck2")
    engine.run(0, 20_000, checkpoint_path=ck)
    other = SweepEngine(get_evaluator("target"), chunk_size=16_384)
    with pytest.raises(ValueError, match="different"):
        other.run(0, 40_000, resume_from=ck)
    # same config but a different reference point: superiority counts could
    # not be continued, so resume must refuse too
    shifted = SweepEngine(get_evaluator("proxy"), chunk_size=16_384,
                          ref_point=engine.ref_point * 2.0)
    with pytest.raises(ValueError, match="reference point"):
        shifted.run(0, 40_000, resume_from=ck)


_SHARDED_CHECK = """
import numpy as np, jax
from repro.perfmodel import get_evaluator, make_evaluator
from repro.perfmodel.sweep import SweepEngine
assert len(jax.devices()) == 4
ev = get_evaluator("proxy")
one = SweepEngine(ev, stall_topk=4, chunk_size=8192).run(0, 40_000)
eng = SweepEngine(ev, stall_topk=4, chunk_size=8192, shard=True)
res = eng.run(0, 40_000)
assert len(eng._iota.devices()) == 4
assert res.n_superior == one.n_superior
assert np.array_equal(res.pareto_ids, one.pareto_ids)
assert np.array_equal(res.topk_ids, one.topk_ids)
assert np.array_equal(res.stall_topk_ids, one.stall_topk_ids)
"""


def test_sharded_sweep_identical_on_four_devices():
    """shard=True spreads each chunk over every local device and reproduces
    the one-device sweep exactly.  Four virtual CPU devices need a fresh
    process: the device count is fixed when JAX starts."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(repo, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _SHARDED_CHECK], env=env,
                       capture_output=True, text=True, timeout=600, cwd=repo)
    assert r.returncode == 0, r.stderr[-3000:]


# ------------------------------------------------------ spans and scopes
@pytest.mark.parametrize("kind", ["paper", "portfolio"])
def test_sweep_spans_every_chunk_phase(engine, portfolio_engine, profile,
                                       kind):
    """One chunk, filter and wait span per chunk, each inside its chunk;
    the insert spans' rows sum to the rows the archives received."""
    eng = engine if kind == "paper" else portfolio_engine
    stop = 3 * eng.chunk_size + 100                       # four chunks
    eng.run(0, stop)                                      # compile outside
    with profile.trace():
        state = eng._run_range(0, stop)
    events = profile.host_events(("sweep.",))
    names = [e[1] for e in events]
    for name in ("sweep.chunk", "sweep.filter", "sweep.wait"):
        assert names.count(name) == 4, name
    assert names.count("sweep.fetch") == names.count("sweep.insert") >= 1
    chunks = [(s, e) for _, n, s, e, _ in events if n == "sweep.chunk"]
    for _, n, s, e, _ in events:
        assert any(s0 <= s <= e <= e0 for s0, e0 in chunks), n
    rows = sum(st["rows"] for _, n, _, _, st in events
               if n == "sweep.insert")
    assert rows == sum(a.n_seen for a in eng._archives_of(state)) > 0


@pytest.mark.parametrize("kind", ["paper", "portfolio"])
def test_chunk_step_names_its_phases_in_hlo_metadata(engine,
                                                     portfolio_engine, kind):
    eng = engine if kind == "paper" else portfolio_engine
    st = eng._fresh_state(0)
    rows = eng._pf_rows if eng._portfolio else None
    filt = np.stack([eng._filter_from_archive(a, rows)
                     for a in eng._archives_of(st)])
    text = eng._step.lower(
        st["carry"], np.int32(0), np.int32(eng.chunk_size),
        filt if eng._portfolio else filt[0]).compile().as_text()
    for scope in ("sweep.decode", "sweep.op_terms", "sweep.reduce"):
        assert re.search(r'op_name="[^"]*/' + re.escape(scope) + "/",
                         text), scope


# ----------------------------------------------------- run_method plumbing
def test_run_method_incremental_phv_curve():
    from repro.core.baselines import METHODS, run_method
    evaluator = get_evaluator("proxy")
    from repro.perfmodel.designspace import A100_REFERENCE
    ref = evaluator(SPACE.encode_nearest(A100_REFERENCE)[None, :])[0]
    r = run_method(METHODS["GA"], evaluator, budget=100, ref_point=ref,
                   seed=0, batch=8, curve_stride=25)
    # one curve point per stride crossing, final == full-history PHV
    assert len(r.phv_curve) == 4
    assert r.phv == pytest.approx(hypervolume(r.Y, ref), rel=1e-12)
    assert r.phv_curve[0] == pytest.approx(hypervolume(r.Y[:32], ref), rel=1e-12)
    assert np.all(np.diff(r.phv_curve) >= -1e-15)            # monotone
