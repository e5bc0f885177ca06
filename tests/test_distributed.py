"""Distributed evaluation service layer.

Covers the PR invariants: a ShardedEvaluator's reassembled PPAReport is
bit-identical to the local ModelEvaluator on the same EvalRequest (both
fidelity tiers, every pool mode incl. the workers=1 in-process fallback
and spawned processes); shard failures retry and stragglers re-dispatch;
an N-worker SweepEngine run reproduces the single-process Pareto front,
top-k tables and stall seeds EXACTLY (and multi-worker checkpoints refuse
mismatched spans); chunk_size="auto" picks a candidate by timed probe;
the EvalService coalesces K concurrent clients' requests into ONE fused
dispatch per tick with a shared cross-client cache; and a CampaignRunner
driven through the service keeps the ~1-dispatch-per-round invariant
without owning the batching.
"""
from concurrent.futures import Future

import numpy as np
import pytest

from repro.core.campaign import CampaignRunner
from repro.core.loop import LuminaDSE
from repro.distributed import EvalService, ShardedEvaluator
from repro.distributed.sharded import _InlinePool
from repro.perfmodel import (EvalRequest, ModelEvaluator, get_evaluator,
                             as_evaluator)
from repro.perfmodel.designspace import SPACE
from repro.perfmodel.sweep import SweepEngine

RNG = np.random.default_rng(3)


def _fresh(tier: str = "proxy") -> ModelEvaluator:
    """A fresh evaluator (own dispatch counter) over the memoized models."""
    return ModelEvaluator(get_evaluator(tier).models, tier=tier)


def _assert_reports_identical(a, b):
    assert a.workloads == b.workloads and a.detail == b.detail
    assert np.array_equal(a.area, b.area)
    for w in a.workloads:
        assert np.array_equal(a.latency[w], b.latency[w])
        if a.detail in ("ppa", "stalls"):
            assert np.array_equal(a.op_time[w], b.op_time[w])
            assert a.op_names[w] == b.op_names[w]
        if a.detail == "stalls":
            assert np.array_equal(a.stall[w], b.stall[w])
            assert np.array_equal(a.op_class[w], b.op_class[w])


# ------------------------------------------------------- sharded evaluator
@pytest.mark.parametrize("tier", ["proxy", "target"])
def test_sharded_bit_identical_to_local(tier):
    """Acceptance: ShardedEvaluator(workers=N) reassembles a PPAReport
    bit-identical to the local fused path, on both fidelity tiers."""
    idx = SPACE.sample(RNG, 23)                  # odd size: uneven shards
    local = _fresh(tier)
    sharded = ShardedEvaluator(_fresh(tier), workers=3)
    for detail in ("objectives", "stalls"):
        req = EvalRequest(idx, detail=detail)
        _assert_reports_identical(sharded.evaluate(req), local.evaluate(req))
    assert np.array_equal(sharded.objectives(idx), local.objectives(idx))
    sharded.close()


def test_sharded_workers1_inline_fallback():
    idx = SPACE.sample(RNG, 9)
    local = _fresh()
    sharded = ShardedEvaluator(_fresh(), workers=1, mode="auto")
    assert sharded.mode == "inline"
    _assert_reports_identical(sharded.evaluate(EvalRequest(idx, "stalls")),
                              local.evaluate(EvalRequest(idx, "stalls")))
    assert sharded.dispatches == 1               # one logical fused request
    assert sharded.worker_dispatches == 1        # served on-thread


def test_sharded_small_batch_stays_on_one_worker():
    sharded = ShardedEvaluator(_fresh(), workers=4, min_shard_rows=8)
    sharded.evaluate(EvalRequest(SPACE.sample(RNG, 5), "objectives"))
    assert sharded.worker_dispatches == 1        # below min_shard_rows x 2
    sharded.close()


def test_sharded_process_mode_bit_identical():
    """Spawned-process workers rebuild the evaluator from its pickled spec
    and still reproduce the local result exactly."""
    idx = SPACE.sample(RNG, 12)
    local = _fresh()
    sharded = ShardedEvaluator(_fresh(), workers=2, mode="process")
    try:
        _assert_reports_identical(
            sharded.evaluate(EvalRequest(idx, "stalls")),
            local.evaluate(EvalRequest(idx, "stalls")))
        assert sharded.worker_dispatches == 2
    finally:
        sharded.close()


def test_process_mode_refuses_when_parent_holds_tpu(monkeypatch):
    """A chip belongs to one process: on a TPU host the process pool
    refuses instead of spawning children that cannot reach the chip."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="one process"):
        ShardedEvaluator(_fresh(), workers=2, mode="process")


class _FlakyPool:
    """Fails the first `fail_first` shard submissions, then delegates."""
    mode = "thread"

    def __init__(self, base, fail_first: int):
        self._inner = _InlinePool(base)
        self.workers = 3
        self._fails = fail_first

    def submit(self, payload):
        if self._fails > 0:
            self._fails -= 1
            fut: Future = Future()
            fut.set_exception(RuntimeError("worker died"))
            return fut
        return self._inner.submit(payload)

    def close(self):
        pass


def test_sharded_retries_failed_workers():
    idx = SPACE.sample(RNG, 21)
    local = _fresh()
    sharded = ShardedEvaluator(_fresh(), workers=3, retries=2)
    sharded._pool = _FlakyPool(sharded.base, fail_first=2)
    rep = sharded.evaluate(EvalRequest(idx, "stalls"))
    _assert_reports_identical(rep, local.evaluate(EvalRequest(idx, "stalls")))
    assert sharded.retried == 2


def test_sharded_raises_after_retry_budget():
    sharded = ShardedEvaluator(_fresh(), workers=3, retries=1)
    sharded._pool = _FlakyPool(sharded.base, fail_first=100)
    with pytest.raises(RuntimeError, match="failed after"):
        sharded.evaluate(EvalRequest(SPACE.sample(RNG, 9), "objectives"))


class _HangOnePool:
    """First submission of shard `hang_nth` returns a future that never
    resolves; everything else (incl. its backup) evaluates inline."""
    mode = "thread"

    def __init__(self, base, hang_nth: int):
        self._inner = _InlinePool(base)
        self.workers = 3
        self._hang_nth = hang_nth
        self._n = 0

    def submit(self, payload):
        n = self._n
        self._n += 1
        if n == self._hang_nth:
            return Future()                      # pending forever
        return self._inner.submit(payload)

    def close(self):
        pass


def test_sharded_straggler_redispatch():
    """A shard whose worker hangs is speculatively re-dispatched; the twin's
    result is used and the report stays identical."""
    idx = SPACE.sample(RNG, 21)
    local = _fresh()
    sharded = ShardedEvaluator(_fresh(), workers=3, straggler_min_s=0.01)
    sharded._pool = _HangOnePool(sharded.base, hang_nth=1)
    rep = sharded.evaluate(EvalRequest(idx, "stalls"))
    _assert_reports_identical(rep, local.evaluate(EvalRequest(idx, "stalls")))
    assert sharded.straggler_redispatches == 1


def test_get_evaluator_workers_knob():
    ev = get_evaluator("proxy", workers=2)
    assert isinstance(ev, ShardedEvaluator) and ev.workers == 2
    assert get_evaluator("proxy", workers=2) is ev         # memoized
    assert isinstance(get_evaluator("proxy"), ModelEvaluator)
    # inert knobs collapse onto the memoized base instance; bad modes raise
    assert get_evaluator("proxy", workers=1, mode="thread") \
        is get_evaluator("proxy")
    with pytest.raises(ValueError, match="mode"):
        get_evaluator("proxy", workers=2, mode="procss")
    assert as_evaluator(ev) is ev                          # protocol member
    idx = SPACE.sample(RNG, 6)
    assert np.array_equal(ev.objectives(idx),
                          get_evaluator("proxy").objectives(idx))


# ------------------------------------------------------- multi-worker sweep
@pytest.fixture(scope="module")
def sweep_engine():
    return SweepEngine(get_evaluator("proxy"), chunk_size=8_192,
                       stall_topk=4, stall_rank="ref")


def test_n_worker_sweep_identical_to_single(sweep_engine):
    """Acceptance: the N-worker sweep reproduces the single-process Pareto
    front, top-k tables and stall_seeds() exactly."""
    single = sweep_engine.run(0, 60_000)
    multi = sweep_engine.run(0, 60_000, workers=3)
    assert multi.n_evaluated == single.n_evaluated
    assert multi.n_superior == single.n_superior
    assert np.array_equal(multi.pareto_ids, single.pareto_ids)
    assert np.array_equal(multi.pareto_y, single.pareto_y)
    assert np.array_equal(multi.topk_val, single.topk_val)
    assert np.array_equal(multi.topk_ids, single.topk_ids)
    assert np.array_equal(multi.stall_topk_val, single.stall_topk_val)
    assert np.array_equal(multi.stall_topk_ids, single.stall_topk_ids)
    ss, ms = single.stall_seeds(), multi.stall_seeds()
    assert set(ss) == set(ms)
    for k in ss:
        assert np.array_equal(ss[k], ms[k])


def test_worker_checkpoints_roundtrip_and_span_guard(sweep_engine, tmp_path):
    ck = str(tmp_path / "wsweep")
    full = sweep_engine.run(0, 32_768, workers=2, checkpoint_path=ck)
    resumed = sweep_engine.run(0, 32_768, workers=2, resume_from=ck)
    assert np.array_equal(resumed.pareto_ids, full.pareto_ids)
    assert np.array_equal(resumed.topk_val, full.topk_val)
    assert resumed.n_evaluated == full.n_evaluated
    # a different range re-spans the workers; stale checkpoints must refuse
    with pytest.raises(ValueError, match="different"):
        sweep_engine.run(0, 65_536, workers=2, resume_from=ck)


def test_chunk_autotune_picks_candidate():
    cands = (8_192, 16_384)
    eng = SweepEngine(get_evaluator("proxy"), chunk_size="auto",
                      chunk_candidates=cands)
    assert eng.chunk_size in cands
    # memoized per process: an identical engine skips the probe
    eng2 = SweepEngine(get_evaluator("proxy"), chunk_size="auto",
                       chunk_candidates=cands)
    assert eng2.chunk_size == eng.chunk_size
    with pytest.raises(ValueError, match="auto"):
        SweepEngine(get_evaluator("proxy"), chunk_size="fastest")


# ------------------------------------------------------------- EvalService
def test_service_coalesces_k_clients_into_one_dispatch():
    """Acceptance: K concurrent clients' requests fuse into ONE dispatch
    per tick, each future resolving to the same report a direct evaluation
    would produce."""
    ev = _fresh()
    svc = EvalService(ev)
    local = _fresh()
    reqs = [EvalRequest(SPACE.sample(RNG, 3), detail="stalls")
            for _ in range(3)]
    reqs.append(EvalRequest(reqs[0].idx[:2], detail="objectives"))  # overlap
    d0 = ev.dispatches
    futs = [svc.submit(r) for r in reqs]
    rows = svc.tick()
    assert ev.dispatches - d0 == 1               # ONE fused dispatch
    assert rows == 9                             # overlapping rows deduped
    for r, f in zip(reqs, futs):
        _assert_reports_identical(f.result(), local.evaluate(r))
    assert svc.fused_dispatches == 1
    assert svc.coalesced_requests == len(reqs)


def test_service_shared_cache_across_clients():
    ev = _fresh()
    svc = EvalService(ev)
    idx = SPACE.sample(RNG, 5)
    svc.submit(EvalRequest(idx, detail="stalls"))
    svc.tick()
    d0 = ev.dispatches
    # a second client asking for any subset/detail of those rows resolves
    # at submit time, no queue, no dispatch
    fut = svc.submit(EvalRequest(idx[2:4], detail="objectives"))
    assert fut.done() and svc.cache_hits == 1
    assert svc.tick() == 0                       # nothing left to dispatch
    assert ev.dispatches == d0
    _assert_reports_identical(fut.result(),
                              _fresh().evaluate(EvalRequest(idx[2:4],
                                                            "objectives")))


def test_service_detail_promotion_reevaluates():
    """Rows cached at a lower detail than requested are re-dispatched at
    the higher detail (and upgraded in the cache)."""
    ev = _fresh()
    svc = EvalService(ev)
    idx = SPACE.sample(RNG, 4)
    svc.submit(EvalRequest(idx, detail="objectives"))
    assert svc.tick() == 4
    fut = svc.submit(EvalRequest(idx, detail="stalls"))
    assert not fut.done()                        # cached too shallow
    assert svc.tick() == 4                       # re-dispatched at "stalls"
    _assert_reports_identical(fut.result(),
                              _fresh().evaluate(EvalRequest(idx, "stalls")))
    fut2 = svc.submit(EvalRequest(idx, detail="objectives"))
    assert fut2.done()                           # upgraded entries serve all


def test_service_dispatch_failure_lands_on_futures():
    """An evaluator failure during tick() must resolve the drained futures
    with the exception — never orphan them (clients would hang forever)."""
    svc = EvalService(_fresh())
    fut = svc.submit(EvalRequest(SPACE.sample(RNG, 3), "objectives"))

    class _Broken:
        def evaluate(self, request):
            raise RuntimeError("backend down")

    svc.evaluator = _Broken()
    assert svc.tick() == 0
    with pytest.raises(RuntimeError, match="backend down"):
        fut.result(timeout=1)
    assert svc.fused_dispatches == 0


def test_service_is_a_drop_in_evaluator():
    """The service satisfies the Evaluator protocol: the single-campaign
    DSE loop runs through it unchanged (self-ticking synchronous calls)."""
    svc = EvalService(_fresh())
    assert as_evaluator(svc) is svc
    res = LuminaDSE(svc, proxy=get_evaluator("proxy"), seed=0).run(budget=4)
    assert len(res.samples) == 4


def test_campaign_runner_through_service_one_dispatch_per_round():
    """Acceptance: K campaigns driven through the EvalService issue ONE
    fused dispatch per round (the PR 3 ~B/K + O(1) invariant) with the
    SERVICE owning the batching, not the runner."""
    ev = _fresh()
    svc = EvalService(ev)
    runner = CampaignRunner(svc, proxy=get_evaluator("proxy"), seed=0)
    assert runner._service is svc
    budget = 12
    seeds = {"memory_bw": SPACE.sample(RNG, 2),
             "tensor_compute": SPACE.sample(RNG, 2)}
    res = runner.run(budget=budget, seeds=seeds)
    k = len(res.per_campaign)
    assert k >= 3
    assert len(res.samples) == budget
    assert res.rounds <= -(-budget // k) + 1
    # one fused dispatch per round + O(1) setup (reference eval + per-class
    # seed scoring), far below one dispatch per evaluation
    assert res.dispatches <= res.rounds + k + 2
    assert res.dispatches < budget
    assert svc.fused_dispatches <= res.rounds + k + 2


def test_service_round_robin_fairness_no_starvation():
    """A chatty client flooding the queue cannot starve a quiet one: with a
    per-tick row cap, the round-robin drain serves EVERY client's first
    request before any client's second — the quiet client's future resolves
    on the very next tick, not after the flood drains."""
    svc = EvalService(_fresh(), max_rows_per_tick=4)
    chatty = [svc.submit(EvalRequest(SPACE.sample(RNG, 1), "objectives"),
                         client="chatty") for _ in range(24)]
    quiet = svc.submit(EvalRequest(SPACE.sample(RNG, 1), "objectives"),
                       client="quiet")
    svc.tick()
    assert quiet.done()                          # served in the FIRST tick
    assert not all(f.done() for f in chatty)     # the flood keeps queueing
    ticks = 1
    while not all(f.done() for f in chatty):
        assert svc.tick() >= 0
        ticks += 1
        assert ticks < 50
    assert ticks > 2                             # the cap really paced it
    assert all(f.result().n == 1 for f in chatty)


def test_service_fair_drain_rotates_between_clients():
    """Unbounded ticks still resolve everything at once (the CampaignRunner
    invariant), and leftovers preserve per-client FIFO order under a cap."""
    svc = EvalService(_fresh())
    futs = [svc.submit(EvalRequest(SPACE.sample(RNG, 2), "objectives"),
                       client=f"c{i % 3}") for i in range(9)]
    svc.tick()
    assert all(f.done() for f in futs)           # one tick, everyone served
    # capped: client order within a lane stays FIFO
    svc2 = EvalService(_fresh(), max_rows_per_tick=1)
    a1 = svc2.submit(EvalRequest(SPACE.sample(RNG, 1), "objectives"),
                     client="a")
    a2 = svc2.submit(EvalRequest(SPACE.sample(RNG, 1), "objectives"),
                     client="a")
    svc2.tick()
    assert a1.done() and not a2.done()           # FIFO within the lane
    svc2.tick()
    assert a2.done()


def test_service_composes_with_sharded_evaluator():
    """EvalService(ShardedEvaluator(...)): coalesce across clients, then
    shard the fused batch across workers — reports stay bit-identical."""
    sharded = ShardedEvaluator(_fresh(), workers=2)
    svc = EvalService(sharded)
    idx = SPACE.sample(RNG, 8)
    futs = [svc.submit(EvalRequest(idx[:5], "stalls")),
            svc.submit(EvalRequest(idx[3:], "stalls"))]
    svc.tick()
    assert sharded.dispatches == 1               # one fused, sharded dispatch
    local = _fresh()
    _assert_reports_identical(futs[0].result(),
                              local.evaluate(EvalRequest(idx[:5], "stalls")))
    _assert_reports_identical(futs[1].result(),
                              local.evaluate(EvalRequest(idx[3:], "stalls")))
    sharded.close()

# -------------------------------------------------------- worker liveness
def test_worker_registry_heartbeat_roundtrip():
    """Heartbeat expiry -> eviction -> re-registration, on a fake clock."""
    from repro.distributed import WorkerRegistry
    clock = {"t": 0.0}
    reg = WorkerRegistry(timeout_s=10.0, now=lambda: clock["t"])
    for w in (0, 1, 2):
        reg.register(w)
    assert reg.live() == [0, 1, 2] and len(reg) == 3
    clock["t"] = 8.0
    reg.beat(1)                                  # only worker 1 stays fresh
    clock["t"] = 12.0                            # 0 and 2 expire (12 >= 10)
    assert reg.live() == [1]
    assert reg.alive(1) and not reg.alive(0)
    assert reg.evict_dead() == [0, 2]
    assert reg.evictions == 2 and len(reg) == 1
    # explicit death attribution beats the passive clock
    reg.mark_dead(1)
    assert not reg.alive(1)
    assert reg.evict_dead() == [1]
    # the worker comes back: same id, counted as a RE-registration
    reg.register(1)
    assert reg.reregistrations == 1
    assert reg.alive(1) and reg.live() == [1]
    # beating an unknown id is a no-op, not a resurrection
    reg.beat(7)
    assert not reg.alive(7)


def test_sharded_resize_rewires_pool_and_registry():
    """resize() changes the live pool fan-out and the liveness registry
    in lock-step, clamped to [1, max_workers]."""
    ev = ShardedEvaluator(_fresh(), workers=4, mode="thread", max_workers=4)
    try:
        idx = SPACE.sample(RNG, 12)
        before = ev.evaluate(EvalRequest(idx, "ppa"))
        assert sorted(ev.registry.live()) == [0, 1, 2, 3]
        ev.resize(2)
        assert ev.workers == 2 and ev._pool.workers == 2
        assert sorted(ev.registry.live()) == [0, 1]
        assert ev.resizes == 1
        after = ev.evaluate(EvalRequest(idx, "ppa"))
        _assert_reports_identical(before, after)  # size never changes results
        ev.resize(99)                             # clamped to max_workers
        assert ev.workers == 4
        ev.resize(0)                              # clamped to 1
        assert ev.workers == 1
        assert sorted(ev.registry.live()) == [0]
    finally:
        ev.close()
