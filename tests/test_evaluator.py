"""Unified tiered Evaluator API (the PR's redesign invariants).

Covers: the fused multi-workload dispatch is bit-identical to per-model
single-workload dispatches on both fidelity tiers; the batched multi-design
path is bit-identical to N single-design dispatches; one DSE step costs
exactly one fused dispatch; the pre-PR-2 deprecation shims are GONE; the
oracle tier normalizes PHV against the exhaustive front; and the sweep's
per-stall-class top-k matches brute force.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pareto import hypervolume, pareto_front
from repro.perfmodel import (CompassModel, EvalRequest, ModelEvaluator,
                             OracleEvaluator, RooflineModel, attribute_stalls,
                             get_evaluator, make_evaluator,
                             gpt3_layer_prefill, gpt3_layer_decode)
from repro.perfmodel.designspace import SPACE, A100_REFERENCE
from repro.perfmodel.evaluator import (DETAILS, as_evaluator,
                                       evaluator_for_model)
from repro.perfmodel.roofline import (PackedFn, _batch_bucket, _bucketed_call,
                                      _split_sinks)
from repro.perfmodel.sweep import SweepEngine

RNG = np.random.default_rng(11)


@pytest.fixture(scope="module")
def sample_idx():
    return SPACE.sample(RNG, 64)


@pytest.fixture(scope="module", params=["proxy", "target"])
def tier_setup(request):
    cls = {"proxy": RooflineModel, "target": CompassModel}[request.param]
    mt, mp = cls(gpt3_layer_prefill()), cls(gpt3_layer_decode())
    ev = ModelEvaluator({"ttft": mt, "tpot": mp}, tier=request.param)
    return ev, mt, mp


# ------------------------------------------------- fused == single-workload
def test_fused_bit_identical_to_single_workload(tier_setup, sample_idx):
    """The fused two-workload stalls dispatch reproduces each model's
    single-workload evaluation EXACTLY (same traced subgraphs, shared
    decode)."""
    ev, mt, mp = tier_setup
    rep = ev.stalls(sample_idx)
    for name, model in (("ttft", mt), ("tpot", mp)):
        solo = evaluator_for_model(model, name).stalls(sample_idx)
        assert np.array_equal(rep.latency[name], solo.latency[name])
        assert np.array_equal(rep.stall[name], solo.stall[name])
        assert np.array_equal(rep.op_time[name], solo.op_time[name])
        assert np.array_equal(rep.op_class[name], solo.op_class[name])
        assert np.array_equal(rep.area, solo.area)


# ------------------------------------------------- batched == N x single
def test_batched_multi_design_bit_identical_to_singles(tier_setup):
    """The batched multi-design EvalRequest path (one fused dispatch for N
    designs) is bit-identical to N single-design dispatches — the invariant
    behind CampaignRunner's one-dispatch-per-round batching.  N equals the
    smallest bucket size so the padded single-design calls compile to the
    same executable shape."""
    ev, _, _ = tier_setup
    idx = SPACE.sample(np.random.default_rng(5), 8)
    batched = ev.evaluate(EvalRequest(idx, detail="stalls"))
    for i in range(idx.shape[0]):
        single = ev.evaluate(EvalRequest(idx[i], detail="stalls"))
        assert np.array_equal(batched.area[i:i + 1], single.area)
        for w in ev.workloads:
            assert np.array_equal(batched.latency[w][i:i + 1],
                                  single.latency[w])
            assert np.array_equal(batched.stall[w][i:i + 1], single.stall[w])
            assert np.array_equal(batched.op_time[w][i:i + 1],
                                  single.op_time[w])
        # the row() view extracts the same single-design report
        row = batched.row(i)
        assert np.array_equal(row.area, single.area)
        assert row.stall_report("ttft").dominant == \
            single.stall_report("ttft").dominant


def test_detail_levels_and_subsets(tier_setup, sample_idx):
    ev, _, _ = tier_setup
    lean = ev.evaluate(EvalRequest(sample_idx, detail="objectives"))
    assert lean.stall is None and lean.op_time is None
    assert lean.objectives.shape == (64, 3)
    ppa = ev.ppa(sample_idx)
    assert ppa.op_time is not None and ppa.stall is None
    with pytest.raises(ValueError):
        ppa.stall_report("ttft")
    sub = ev.evaluate(EvalRequest(sample_idx[:4], detail="stalls",
                                  workloads=("tpot",)))
    assert sub.workloads == ("tpot",)
    assert sub.stall_report("tpot").latency > 0
    with pytest.raises(KeyError):
        ev.evaluate(EvalRequest(sample_idx, workloads=("nope",)))
    with pytest.raises(ValueError):
        EvalRequest(sample_idx, detail="everything")


def test_stall_report_matches_attribute_stalls(tier_setup):
    ev, mt, _ = tier_setup
    idx = SPACE.encode_nearest(A100_REFERENCE)
    rep = ev.stalls(idx).stall_report("ttft")
    legacy = attribute_stalls(mt, idx)
    assert rep.dominant == legacy.dominant
    assert rep.latency == pytest.approx(legacy.latency, rel=0)
    assert rep.top_ops == legacy.top_ops


# ------------------------------------------------- one-copy packed fetch
@pytest.fixture(scope="module")
def zoo_evaluator():
    """A zoo-portfolio evaluator."""
    from repro.perfmodel.workload import zoo_suite
    wls, scen = zoo_suite(archs=("qwen2-moe-a2.7b", "rwkv6-7b"), smoke=True,
                          batch=4)
    return make_evaluator(wls, tier="proxy", scenarios=scen)


def _per_leaf_report(fn, idx):
    """The report as fetched before packing: the jitted fused function, its
    sinks stripped, one ``np.asarray`` per leaf, sliced to the batch."""
    b = idx.shape[0]
    x = np.concatenate([idx, np.repeat(idx[-1:], _batch_bucket(b) - b, 0)])
    out, _ = _split_sinks(jax.jit(fn)(x))
    return jax.tree_util.tree_map(lambda v: np.asarray(v)[:b], out)


PACK_CASES = ([(t, d, b) for t in ("proxy", "target") for d in DETAILS
               for b in (1, 5, 8, 33)]
              + [("zoo", d, 33) for d in DETAILS])


@pytest.mark.parametrize("kind,detail,b", PACK_CASES,
                         ids=[f"{k}-{d}-b{b}" for k, d, b in PACK_CASES])
def test_packed_fetch_bit_identical_to_per_leaf_copies(kind, detail, b,
                                                       request):
    """The one-transfer packed fetch hands back exactly the tree the
    per-leaf copies did: same structure, every leaf equal bit for bit
    with its dtype, cut to the true batch (b=33 pads to the 64 bucket)."""
    ev = (request.getfixturevalue("zoo_evaluator") if kind == "zoo"
          else get_evaluator(kind))
    idx = SPACE.sample(np.random.default_rng(100 + b), b)
    packed = ev._fused_fn(detail, ev.workloads)
    got = _bucketed_call(packed, idx)
    want = _per_leaf_report(packed.fn, idx)
    g, gt = jax.tree_util.tree_flatten(got)
    w, wt = jax.tree_util.tree_flatten(want)
    assert gt == wt
    for a, r in zip(g, w):
        assert a.dtype == r.dtype and a.shape == r.shape
        assert np.array_equal(a, r)
        assert not a.flags.writeable
    rep = ev.evaluate(EvalRequest(idx, detail=detail))
    per = want["per_workload"]
    assert np.array_equal(rep.area, want["area"])
    for nm in ev.workloads:
        assert np.array_equal(rep.latency[nm], per[nm]["latency"])
        assert not rep.latency[nm].flags.writeable
        if detail == "stalls":
            assert rep.op_class[nm].dtype == np.int32
            assert np.array_equal(rep.op_class[nm], per[nm]["op_class"])
            assert np.array_equal(rep.stall[nm], per[nm]["stall"])


def test_packed_fn_carries_bool_leaves_and_keeps_sinks_on_device():
    fn = PackedFn(lambda x: {"hit": x[:, 0] > 2,
                             "v": x.astype(jnp.float32) * 0.5,
                             "_sink": x * 2})
    idx = SPACE.sample(np.random.default_rng(4), 5)
    got = _bucketed_call(fn, idx)
    assert set(got) == {"hit", "v"}
    assert got["hit"].dtype == np.bool_
    assert np.array_equal(got["hit"], idx[:, 0] > 2)
    assert np.array_equal(got["v"], idx.astype(np.float32) * 0.5)
    assert not got["hit"].flags.writeable
    buf, sinks = fn.jitted(jnp.asarray(idx[:1].repeat(8, 0)))
    assert buf.dtype == jnp.uint32 and buf.ndim == 1
    assert len(sinks) == 1 and sinks[0].shape == (8, SPACE.n_params)


def test_packed_fn_rejects_unpackable_leaves():
    idx = SPACE.sample(np.random.default_rng(4), 3)
    with jax.enable_x64(True):
        wide = PackedFn(lambda x: {"v": x.astype(jnp.float64)})
        with pytest.raises(TypeError, match="4-byte and bool"):
            _bucketed_call(wide, idx)
    flat = PackedFn(lambda x: {"v": x.sum()})
    with pytest.raises(ValueError, match="leading batch axis"):
        _bucketed_call(flat, idx)


# ------------------------------------------------------- oracle base tier
def test_unknown_backend_rejected():
    """The oracle sweeps the proxy or the target tier, and nothing else."""
    with pytest.raises(ValueError, match="oracle_base"):
        get_evaluator("oracle", oracle_base="compass")
    base = get_evaluator("oracle", oracle_base="target", oracle_stop=8_192)
    assert base.base is get_evaluator("target")


# ------------------------------------------------------- dispatch counting
def test_one_fused_dispatch_per_dse_step():
    """Acceptance criterion: each budgeted DSE step issues exactly ONE fused
    jitted evaluation dispatch on the target tier."""
    from repro.core.loop import LuminaDSE
    target = ModelEvaluator(get_evaluator("target").models, tier="target")
    proxy = get_evaluator("proxy")
    d0 = target.dispatches
    res = LuminaDSE(target, proxy=proxy, seed=0).run(budget=8)
    assert len(res.samples) == 8
    # ref eval costs 1 dispatch; step 0 re-reads it from the report cache
    assert target.dispatches - d0 == 8


def test_evaluator_memoized_per_tier():
    assert get_evaluator("proxy") is get_evaluator("proxy")
    assert get_evaluator("proxy") is not get_evaluator("target")


# ------------------------------------------- deprecation shims are GONE
def test_legacy_shims_removed():
    """The one-release deprecation window closed: per-model eval paths and
    the (ttft, tpot) pair signature no longer exist."""
    mt, mp = (get_evaluator("proxy").models[w] for w in ("ttft", "tpot"))
    for attr in ("eval_ppa", "objectives", "latency"):
        assert not hasattr(mt, attr), attr
    with pytest.raises(TypeError):
        as_evaluator(mt, mp)
    with pytest.raises(TypeError):
        SweepEngine(mt, mp, chunk_size=8_192)
    with pytest.raises(TypeError):
        from repro.core.loop import LuminaDSE
        LuminaDSE(mt, mp)
    with pytest.raises(ImportError):
        from repro.perfmodel import make_paper_evaluator  # noqa: F401


def test_single_model_coercion():
    mt = get_evaluator("proxy").models["ttft"]
    ev = as_evaluator(mt)
    assert ev.workloads == ("lat",)
    assert as_evaluator(ev) is ev


# ------------------------------------------------------- oracle tier
SUB = 20_000


@pytest.fixture(scope="module")
def oracle():
    return OracleEvaluator(get_evaluator("proxy"),
                           sweep_kwargs=dict(chunk_size=8_192),
                           stop=SUB)


def test_oracle_front_matches_brute_force(oracle):
    ys = oracle.objectives(SPACE.flat_to_idx(np.arange(SUB)))
    front = pareto_front(ys)
    got = np.sort(oracle.front(), axis=0)
    assert np.allclose(got, np.sort(front, axis=0), rtol=1e-6)


def test_oracle_normalized_phv_bounds(oracle):
    # reference point dominated by the sub-front (ids [0, SUB) are a weak
    # corner of the space, so the A100 point would give zero PHV here)
    ref = oracle.front().max(axis=0) * 2.0
    # any sampled sub-front's PHV normalizes into [0, 1]
    ys = oracle.objectives(SPACE.flat_to_idx(np.arange(0, SUB, 7)))
    phv = hypervolume(ys, ref)
    frac = oracle.normalized_phv(phv, ref)
    assert 0.0 <= frac <= 1.0 + 1e-9
    assert oracle.normalized_phv(oracle.oracle_phv(ref), ref) == pytest.approx(1.0)
    # regret of the oracle's own front is ~zero
    assert np.allclose(oracle.regret(oracle.front()), 0.0, atol=1e-9)


# ------------------------------------------------------- sweep stall top-k
def test_sweep_stall_topk_matches_brute_force():
    ev = get_evaluator("proxy")
    eng = SweepEngine(ev, chunk_size=8_192, stall_topk=8)
    res = eng.run(0, SUB)
    idx = SPACE.flat_to_idx(np.arange(SUB))
    rep = ev.evaluate(EvalRequest(idx, detail="stalls"))
    dom = np.argmax(rep.stall["ttft"], axis=1)
    lat = rep.latency["ttft"]
    for c in range(4):
        lat_c = np.where(dom == c, lat, np.inf)
        want = np.sort(lat_c)[:8]
        got = res.stall_topk_val[c]
        finite = np.isfinite(want)
        assert np.allclose(got[finite], want[finite], rtol=1e-6), c
        # claimed ids really have this dominant class and latency
        for k in np.flatnonzero(np.isfinite(got)):
            fid = int(res.stall_topk_ids[c][k])
            assert fid >= 0
            assert lat[fid] == pytest.approx(got[k], rel=1e-6)
            assert dom[fid] == c
    seeds = res.stall_seeds()
    assert set(seeds) == {"tensor_compute", "vector_compute", "memory_bw",
                          "interconnect"}
    for arr in seeds.values():
        assert arr.ndim == 2 and arr.shape[1] == SPACE.n_params


def test_sweep_stall_topk_checkpoint_roundtrip(tmp_path):
    import os
    ev = get_evaluator("proxy")
    eng = SweepEngine(ev, chunk_size=8_192, stall_topk=4)
    ck = os.path.join(tmp_path, "ck")
    full = eng.run(0, SUB)
    eng.run(0, SUB // 2, checkpoint_path=ck)
    res = eng.run(0, SUB, resume_from=ck)
    assert np.allclose(res.stall_topk_val, full.stall_topk_val, rtol=1e-7)
    assert np.array_equal(res.stall_topk_ids, full.stall_topk_ids)
    # an engine without stall tracking refuses a stall-less checkpoint
    plain = SweepEngine(ev, chunk_size=8_192)
    plain.run(0, SUB // 2, checkpoint_path=ck + "2")
    strict = SweepEngine(ev, chunk_size=8_192, stall_topk=4)
    with pytest.raises(ValueError, match="stall"):
        strict.run(0, SUB, resume_from=ck + "2")
