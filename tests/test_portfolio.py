"""Stacked-workload evaluation + portfolio sweep (PR 5 invariants).

Covers: stacked vs looped bit-identity at every detail level across zoo
workloads (incl. a MoE and an SSM config) on both fidelity tiers; the
WorkloadStack dedup / count-matrix / gather-map correctness vs brute-force
concatenation; ONE compiled executable per (detail, suite) regardless of
the workload count; the zoo-suite evaluator wiring
(``get_evaluator(suite="zoo")``); the portfolio sweep's per-scenario
fronts / top-k / stall seeds and the robust front vs brute force, its
worker sharding and checkpoint resume; archive auto-capacity; and
scenario-class seeded campaigns through ``CampaignRunner``.
"""
import numpy as np
import pytest

from repro.core.campaign import CampaignRunner
from repro.core.pareto import ParetoArchive, dominates_ref, pareto_front
from repro.perfmodel import (CompassModel, EvalRequest, ModelEvaluator,
                             RooflineModel, get_evaluator, make_evaluator)
from repro.perfmodel.designspace import SPACE
from repro.perfmodel.roofline import _JIT_CACHE
from repro.perfmodel.sweep import SweepEngine
from repro.perfmodel.workload import (STACK_KEY_FIELDS, WorkloadStack,
                                      zoo_suite)

RNG = np.random.default_rng(23)

# a MoE, an SSM and a dense config — the families with the most distinct
# operator graphs (satellite requirement: >= 3 zoo workloads incl. MoE+SSM)
TEST_ARCHS = ("qwen2-moe-a2.7b", "rwkv6-7b", "llama3.2-1b")


@pytest.fixture(scope="module")
def suite():
    return zoo_suite(archs=TEST_ARCHS, smoke=True)


@pytest.fixture(scope="module")
def zoo_ev(suite):
    wls, scen = suite
    return make_evaluator(wls, tier="proxy", scenarios=scen)


# --------------------------------------------------- stacked == looped
@pytest.mark.parametrize("cls", [RooflineModel, CompassModel],
                         ids=["proxy", "target"])
@pytest.mark.parametrize("detail", ["objectives", "ppa", "stalls"])
def test_stacked_bit_identical_to_looped(suite, cls, detail):
    """The stacked union pass reproduces the per-workload looped dispatch
    EXACTLY — every field, every detail level, both tiers, across MoE/SSM/
    dense zoo workloads."""
    wls, _ = suite
    models = {nm: cls(wl) for nm, wl in wls.items()}
    stacked = ModelEvaluator(models, stacked=True)
    looped = ModelEvaluator(models, stacked=False)
    idx = SPACE.sample(np.random.default_rng(1), 16)
    a = stacked.evaluate(EvalRequest(idx, detail=detail))
    b = looped.evaluate(EvalRequest(idx, detail=detail))
    assert np.array_equal(a.area, b.area)
    for w in stacked.workloads:
        assert np.array_equal(a.latency[w], b.latency[w]), w
        if detail in ("ppa", "stalls"):
            assert np.array_equal(a.op_time[w], b.op_time[w]), w
        if detail == "stalls":
            assert np.array_equal(a.stall[w], b.stall[w]), w
            assert np.array_equal(a.op_class[w], b.op_class[w]), w


def test_stacked_rejects_heterogeneous_models(suite):
    wls, _ = suite
    names = list(wls)
    models = {names[0]: RooflineModel(wls[names[0]]),
              names[1]: CompassModel(wls[names[1]])}
    with pytest.raises(ValueError, match="stacked"):
        ModelEvaluator(models, stacked=True)
    # auto mode silently falls back to the looped path
    ev = ModelEvaluator(models)
    assert ev.stacked is False


# --------------------------------------------------- WorkloadStack dedup
def test_workload_stack_matches_brute_force_concat(suite):
    """Dedup bookkeeping vs the brute-force concatenated table: gather maps
    reconstruct every workload's op rows exactly, the count matrix equals
    the per-key count sums, and the union is genuinely deduplicated."""
    wls, _ = suite
    stack = WorkloadStack.build(wls)
    assert stack.total_ops == sum(len(w.ops) for w in wls.values())
    assert stack.n_unique < stack.total_ops        # real cross-workload dedup

    def key_of(arrs, i):
        return tuple(arrs[f][i] for f in STACK_KEY_FIELDS)

    union_keys = [key_of(stack.unique, u) for u in range(stack.n_unique)]
    assert len(set(union_keys)) == stack.n_unique  # unique rows ARE unique
    for w, (nm, wl) in enumerate(wls.items()):
        a = wl.arrays()
        # gather map reconstructs the original op table field-for-field
        for i in range(len(wl.ops)):
            assert union_keys[stack.op_map[nm][i]] == key_of(a, i)
        assert np.array_equal(stack.counts[nm], a["count"])
        # count matrix == brute-force per-key count accumulation
        want = np.zeros(stack.n_unique)
        for i in range(len(wl.ops)):
            want[stack.op_map[nm][i]] += a["count"][i]
        assert np.array_equal(stack.count_matrix[w], want)


# --------------------------------------------------- compile counting
def test_one_jit_entry_per_detail_regardless_of_w():
    """Acceptance: evaluating a suite costs exactly ONE compiled executable
    per (detail, suite) — the workload count W never multiplies the
    jit-cache population (a fresh batch=4 suite guarantees fresh keys)."""
    for archs in (TEST_ARCHS[:1], TEST_ARCHS):         # W=2 and W=6
        wls, scen = zoo_suite(archs=archs, smoke=True, batch=4)
        ev = make_evaluator(wls, tier="proxy", scenarios=scen)
        idx = SPACE.sample(np.random.default_rng(2), 8)
        before = set(_JIT_CACHE)
        for detail in ("objectives", "ppa", "stalls"):
            ev.evaluate(EvalRequest(idx, detail=detail))
            ev.evaluate(EvalRequest(idx[:3], detail=detail))  # same exec
        assert len(set(_JIT_CACHE) - before) == 3, len(wls)
        assert ev.dispatches == 6


# --------------------------------------------------- zoo evaluator wiring
def test_get_evaluator_zoo_suite():
    ev = get_evaluator("proxy", suite="zoo")
    assert ev is get_evaluator("proxy", suite="zoo")       # memoized
    assert ev is not get_evaluator("proxy")                # distinct key
    assert ev.stacked
    assert len(ev.scenarios) == 11                         # every arch config
    names = {s.name for s in ev.scenarios}
    assert {"arctic-480b", "rwkv6-7b", "whisper-medium"} <= names
    for s in ev.scenarios:
        assert s.prefill in ev.workloads and s.decode in ev.workloads
    with pytest.raises(ValueError, match="suite"):
        get_evaluator("proxy", suite="menagerie")


# --------------------------------------------------- portfolio sweep
SUB = 24_000


@pytest.fixture(scope="module")
def swept(zoo_ev):
    eng = SweepEngine(zoo_ev, chunk_size=8_192, stall_topk=4)
    return eng, eng.run(0, SUB)


def test_portfolio_per_scenario_matches_brute_force(zoo_ev, swept):
    """Every scenario's front, top-k, superiority count and stall-class
    seeds equal the brute-force reduction of that scenario's objectives."""
    eng, res = swept
    assert res.scenario_names == tuple(s.name for s in zoo_ev.scenarios)
    idx = SPACE.flat_to_idx(np.arange(SUB))
    rep = zoo_ev.evaluate(EvalRequest(idx, detail="stalls"))
    for s in zoo_ev.scenarios:
        ys = np.stack([rep.latency[s.prefill], rep.latency[s.decode],
                       rep.area], axis=1)
        r = res.scenario(s.name)
        front = pareto_front(ys)
        assert len(r.pareto_ids) == len(front)
        assert np.allclose(np.sort(r.pareto_y, axis=0),
                           np.sort(front, axis=0), rtol=1e-5)
        assert r.n_superior == int(dominates_ref(ys, r.ref_point).sum())
        assert np.allclose(r.topk_val[:, 0], ys.min(axis=0), rtol=1e-5)
        dom = np.argmax(rep.stall[s.prefill], axis=1)
        lat = rep.latency[s.prefill]
        for c in range(4):
            want = np.sort(np.where(dom == c, lat, np.inf))[:4]
            got = r.stall_topk_val[c]
            fin = np.isfinite(want)
            assert np.allclose(got[fin], want[fin], rtol=1e-5), (s.name, c)


def test_portfolio_robust_front_matches_brute_force(zoo_ev, swept):
    """The robust front equals the brute-force front of the worst-case
    reference-normalized objectives (float32, like the device path)."""
    eng, res = swept
    assert res.robust == "worst"
    idx = SPACE.flat_to_idx(np.arange(SUB))
    rep = zoo_ev.evaluate(EvalRequest(idx, detail="objectives"))
    ys_s = np.stack(
        [np.stack([rep.latency[s.prefill], rep.latency[s.decode], rep.area],
                  axis=1) for s in zoo_ev.scenarios], axis=1)
    ratio = (ys_s[:, :, :2].astype(np.float32)
             / eng.ref_points[None, :, :2].astype(np.float32))
    ys_r = np.concatenate([ratio.max(axis=1),
                           ys_s[:, 0, 2:3].astype(np.float32)], axis=1)
    front = pareto_front(ys_r)
    assert len(res.pareto_ids) == len(front)
    assert np.allclose(np.sort(res.pareto_y, axis=0),
                       np.sort(front, axis=0), rtol=1e-5)
    # robust superiority = designs beating the reference on EVERY scenario
    assert res.n_superior == int(dominates_ref(ys_r, res.ref_point).sum())


def test_portfolio_workers_and_resume_identical(zoo_ev, swept, tmp_path):
    eng, res = swept
    res2 = eng.run(0, SUB, workers=2)
    assert np.array_equal(res.pareto_ids, res2.pareto_ids)
    assert np.array_equal(res.topk_ids, res2.topk_ids)
    assert np.array_equal(
        res.scenario(res.scenario_names[0]).pareto_ids,
        res2.scenario(res.scenario_names[0]).pareto_ids)
    ck = str(tmp_path / "ck")
    eng.run(0, SUB // 2, checkpoint_path=ck)
    res3 = eng.run(0, SUB, resume_from=ck)
    assert np.array_equal(res.pareto_ids, res3.pareto_ids)
    for nm in res.scenario_names:
        assert np.allclose(res.scenario(nm).stall_topk_val,
                           res3.scenario(nm).stall_topk_val, rtol=1e-7)


def test_portfolio_geomean_and_validation(zoo_ev):
    engg = SweepEngine(zoo_ev, chunk_size=8_192, robust="geomean")
    resg = engg.run(0, 8_192)
    assert resg.robust == "geomean"
    assert len(resg.pareto_ids) > 0
    with pytest.raises(ValueError, match="robust"):
        SweepEngine(zoo_ev, robust="median")
    with pytest.raises(KeyError, match="scenario"):
        resg.stall_seeds(scenario="gpt5")
    with pytest.raises(ValueError, match="roofline"):
        SweepEngine(zoo_ev, backend="pallas")


def test_portfolio_stall_seeds_flatten(zoo_ev, swept):
    """stall_seeds() flattens to '<scenario>:<class>' campaign labels;
    scenario= selects one scenario's classes."""
    _, res = swept
    flat = res.stall_seeds()
    assert len(flat) == 4 * len(res.scenario_names)
    one = res.stall_seeds(scenario=res.scenario_names[0])
    assert set(one) == {"tensor_compute", "vector_compute", "memory_bw",
                        "interconnect"}
    for cls, arr in one.items():
        assert np.array_equal(
            flat[f"{res.scenario_names[0]}:{cls}"], arr)
        assert arr.ndim == 2 and arr.shape[1] == SPACE.n_params


# --------------------------------------------------- archive auto-capacity
def test_archive_auto_capacity_tracks_front_width():
    rng = np.random.default_rng(0)
    arch = ParetoArchive(3, capacity="auto", auto_floor=32)
    for _ in range(20):
        arch.insert(rng.uniform(1, 2, size=(256, 3)))
    assert not arch.truncated                       # auto never truncated it
    assert arch.capacity >= max(32, 2 * len(arch))  # bound trails the width
    # a fixed-capacity run at the auto-derived bound reproduces the front
    fixed = ParetoArchive(3, capacity=arch.capacity)
    rng = np.random.default_rng(0)
    for _ in range(20):
        fixed.insert(rng.uniform(1, 2, size=(256, 3)))
    assert np.array_equal(np.sort(fixed.y, axis=0), np.sort(arch.y, axis=0))


def test_sweep_accepts_auto_archive_capacity(zoo_ev):
    eng = SweepEngine(get_evaluator("proxy"), chunk_size=8_192,
                      archive_capacity="auto")
    res = eng.run(0, 20_000)
    assert not res.archive_truncated
    assert res.archive_capacity >= 2_048            # the default floor
    ref = SweepEngine(get_evaluator("proxy"), chunk_size=8_192,
                      archive_capacity=None).run(0, 20_000)
    assert np.array_equal(res.pareto_ids, ref.pareto_ids)
    with pytest.raises(ValueError, match="archive_capacity"):
        SweepEngine(get_evaluator("proxy"), archive_capacity="huge")


# --------------------------------------------------- scenario campaigns
def test_campaign_runner_per_scenario_class(zoo_ev, swept):
    """A scenario campaign: the runner optimizes ONE zoo scenario's
    (prefill, decode) pair, seeded from that scenario's sweep stall
    classes, at the usual ~B/K fused dispatch cost."""
    _, res = swept
    scen = zoo_ev.scenarios[0]
    runner = CampaignRunner(zoo_ev, proxy=zoo_ev, scenario=scen.name, seed=0)
    assert runner.ee.workload_pair == (scen.prefill, scen.decode)
    assert np.allclose(runner.ref_point,
                       res.scenario(scen.name).ref_point, rtol=1e-5)
    out = runner.run(budget=6, seeds=res.stall_seeds(scenario=scen.name))
    assert len(out.samples) == 6
    assert len({tuple(s.idx) for s in out.samples}) == 6
    # rounds stay fused: <= 1 dispatch/round + 1 per seed class + the ref
    k = len(out.per_campaign)
    assert out.dispatches <= out.rounds + k + 1
    with pytest.raises(KeyError, match="scenario"):
        CampaignRunner(zoo_ev, scenario="imaginary-arch")
