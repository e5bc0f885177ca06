"""repro.obs: the unified metrics registry + causal tracing.

Covers: instrument semantics (typed counters/gauges/histograms, label
series, conflict rejection), tracer causality (nesting, detached spans,
adoption, loss), Perfetto export schema + tree completeness, the frozen
pre-registry telemetry() key sets (the bit-for-bit back-compat the
migration promised — checked under chaos), heartbeat RTT capture, and
one cross-machine causal tree over a 2-worker loopback socket fleet.
"""
import json

import numpy as np
import pytest

from repro.core.campaign import (TELEMETRY_VERSION, CampaignRunner,
                                 load_telemetry)
from repro.distributed import EvalService, ShardedEvaluator
from repro.distributed.faults import FaultEvent, FaultPlan
from repro.distributed.service import DEGRADE_RUNGS, QOS_TIERS
from repro.obs import (ManualClock, MetricsRegistry, NOOP, Span, Tracer,
                       completeness_errors, render_tree, trace_events,
                       validate_trace_events)
from repro.obs.metrics import Counter, CounterView
from repro.obs.report import fleet_report
from repro.perfmodel.evaluator import (EvalRequest, ModelEvaluator,
                                       get_evaluator)
from repro.perfmodel.designspace import SPACE
from repro.serve import Gateway, SocketPool, WorkerServer

RNG = np.random.default_rng(7)


def _fresh(tier: str = "proxy") -> ModelEvaluator:
    return ModelEvaluator(get_evaluator(tier).models, tier=tier)


@pytest.fixture(scope="module")
def servers():
    s1, s2 = WorkerServer(), WorkerServer()
    s1.start()
    s2.start()
    yield s1, s2
    s1.close()
    s2.close()


# ------------------------------------------------------------------ metrics
def test_counter_gauge_histogram_basics():
    m = MetricsRegistry()
    c = m.counter("reqs", "requests", labelnames=("tier",))
    c.inc(tier="fast")
    c.inc(2, tier="slow")
    assert c.value(tier="fast") == 1 and c.value(tier="slow") == 2
    assert c.total() == 3
    with pytest.raises(ValueError):
        c.inc(-1, tier="fast")                 # counters are monotonic
    with pytest.raises(ValueError):
        c.inc()                                # label schema enforced

    g = m.gauge("depth")
    g.set(4)
    g.set(2)
    assert g.value() == 2                      # last write wins

    h = m.histogram("lat", reservoir=100)
    assert h.stats()["p50"] is None            # empty -> None, not 0
    for v in range(1, 101):
        h.observe(v / 100)
    st = h.stats()
    assert st["count"] == 100 and st["min"] == 0.01 and st["max"] == 1.0
    assert abs(st["p50"] - 0.505) < 1e-9
    assert h.percentile(99) == pytest.approx(st["p99"])


def test_registry_get_or_create_and_conflicts():
    m = MetricsRegistry()
    c1 = m.counter("n", "first")
    assert m.counter("n") is c1                # same schema -> same object
    with pytest.raises(ValueError):
        m.gauge("n")                           # kind conflict
    with pytest.raises(ValueError):
        m.counter("n", labelnames=("x",))      # label-schema conflict
    assert m.get("n") is c1 and m.get("missing") is None


def test_counter_view_is_a_faithful_mapping():
    c = Counter("served", labelnames=("tier",))
    with pytest.raises(ValueError):
        CounterView(Counter("plain"))          # needs exactly one label
    view = CounterView(c)
    c.touch(tier="batch")
    c.inc(3, tier="interactive")
    assert view["interactive"] == 3 and view["batch"] == 0
    assert isinstance(view["batch"], int)
    assert dict(view) == {"batch": 0, "interactive": 3}
    assert sum(view.values()) == 3
    with pytest.raises(KeyError):
        view["never-touched"]


def test_flat_csv_and_snapshot_roundtrip():
    m = MetricsRegistry()
    m.counter("a", labelnames=("k",)).inc(k="x")
    m.histogram("h").observe(0.5)
    flat = m.flat()
    assert flat["a{k=x}"] == 1.0
    assert flat["h_count"] == 1.0 and flat["h_p50"] == 0.5
    lines = m.csv_lines()
    assert lines[0] == "metric,value" and any(
        line.startswith("a{k=x},") for line in lines)
    # snapshot is pure JSON (the gateway persists it verbatim)
    snap = json.loads(m.to_json())
    assert snap["a"]["type"] == "counter"
    assert snap["h"]["series"][0]["count"] == 1


def test_manual_clock_drives_deterministic_timing():
    clk = ManualClock()
    tr = Tracer(clock=clk, proc="t")
    with tr.span("op"):
        clk.advance(1.5)
    (sp,) = tr.spans()
    assert sp.duration_s == 1.5


# ------------------------------------------------------------------ tracer
def test_tracer_nests_and_marks_errors():
    tr = Tracer(clock=ManualClock(), proc="p")
    with tr.span("outer") as outer:
        with tr.span("inner", rows=3) as inner:
            assert tr.current() is inner
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("no")
    spans = {s.name: s for s in tr.spans()}
    assert spans["inner"].parent_id == outer.span_id
    assert spans["inner"].trace_id == outer.trace_id
    assert spans["inner"].attrs == {"rows": 3}
    assert spans["boom"].status == "error"
    assert "RuntimeError" in spans["boom"].attrs["error"]
    assert spans["outer"].parent_id is None


def test_detached_activate_adopt_and_lose():
    tr = Tracer(clock=ManualClock(), proc="client")
    root = tr.start("root", detached=True)
    assert tr.current() is None                # detached: not on the stack
    with tr.activate(root):
        child = tr.start("child", detached=True)
    assert child.parent_id == root.span_id

    # a remote tracer parents under the shipped ctx and ships dicts back
    remote = Tracer(clock=ManualClock(), proc="worker:h:1")
    with remote.span("remote.eval", parent=root.ctx):
        pass
    assert tr.adopt(s.as_dict() for s in remote.drain()) == 1

    tr.lose(child, "worker died")
    tr.finish(root)
    tr.finish(root, status="error")            # idempotent: first wins
    by_name = {s.name: s for s in tr.spans()}
    assert by_name["root"].status == "ok"
    assert by_name["child"].status == "lost"
    assert by_name["child"].attrs["lost_reason"] == "worker died"
    assert by_name["remote.eval"].trace_id == root.trace_id
    assert completeness_errors(tr.spans()) == []


def test_noop_tracer_is_inert():
    assert NOOP.enabled is False
    with NOOP.span("x") as sp:
        sp.attrs["y"] = 1                      # harmless, unrecorded
    assert NOOP.current_ctx() is None
    assert NOOP.adopt([{"name": "z"}]) == 0
    assert NOOP.spans() == [] and NOOP.drain() == []


# -------------------------------------------------------- profiler bridge
def _by_name(events):
    return {name: (line, s, e, stats) for line, name, s, e, stats in events}


@pytest.mark.parametrize("kind", ["noop", "recording"])
def test_spans_land_on_the_profiler_host_plane(profile, kind):
    """Every span() block is a profiler annotation with its name, nesting
    and integer attributes, whether or not the tracer records."""
    tr = NOOP if kind == "noop" else Tracer(proc="p")
    with profile.trace():
        with tr.span("t.outer", rows=17, label="a"):
            with tr.span("t.inner", leaves=3):
                pass
    ev = _by_name(profile.host_events(("t.",)))
    line_o, s_o, e_o, st_o = ev["t.outer"]
    line_i, s_i, e_i, st_i = ev["t.inner"]
    assert line_o == line_i and s_o <= s_i <= e_i <= e_o
    assert st_o["rows"] == 17 and isinstance(st_o["rows"], int)
    assert st_o["label"] == "a"
    assert st_i["leaves"] == 3
    if kind == "recording":                    # its own buffer as before
        got = {s.name: s for s in tr.spans()}
        assert got["t.outer"].attrs == {"rows": 17, "label": "a"}
        assert got["t.inner"].parent_id == got["t.outer"].span_id


def test_detached_span_is_not_on_the_profiler(profile):
    tr = Tracer(proc="p")
    with profile.trace():
        sp = tr.start("t.detached", detached=True, rows=1)
        with tr.span("t.attached"):
            pass
        tr.finish(sp)
    names = {e[1] for e in profile.host_events(("t.",))}
    assert names == {"t.attached"}
    assert {s.name for s in tr.spans()} == {"t.detached", "t.attached"}


def test_evaluate_spans_its_phases_and_counts_fetched_leaves(profile):
    """A stalls report of the paper pair hands back 15 leaves: area plus
    seven per workload, unpacked from one device-to-host copy."""
    ev = _fresh("target")
    idx = SPACE.sample(RNG, 5)
    ev.evaluate(EvalRequest(idx, detail="stalls"))         # compile outside
    with profile.trace():
        ev.evaluate(EvalRequest(idx, detail="stalls"))
    events = profile.host_events(("eval.",))
    got = _by_name(events)
    assert [e[1] for e in events] == ["eval.call", "eval.upload",
                                      "eval.launch", "eval.fetch"]
    assert got["eval.call"][3] == {"rows": 5, "bucket": 8}
    assert got["eval.fetch"][3] == {"leaves": 15, "copies": 1}
    _, s0, e0, _ = got["eval.call"]
    assert all(s0 <= s <= e <= e0 for _, s, e, _ in got.values())


# ------------------------------------------------------------------ export
def test_trace_events_schema_and_tree_checks():
    tr = Tracer(clock=ManualClock(), proc="main")
    with tr.span("a"):
        with tr.span("b"):
            pass
    obj = trace_events(tr.spans())
    assert validate_trace_events(obj) == []
    assert obj["otherData"]["schema_version"] == 1
    phases = {e["ph"] for e in obj["traceEvents"]}
    assert phases == {"M", "X"}
    # the renderer shows the nesting and the validator catches breakage
    txt = render_tree(tr.spans())
    assert "a" in txt and "`-- " in txt
    assert validate_trace_events({"traceEvents": [{"ph": "Q"}]})
    dangling = Span("x", "t1", "s9", "missing", "p", "th", 0.0, t_end=None)
    errs = completeness_errors([dangling])
    assert any("dangling" in e for e in errs)
    assert any("never finished" in e for e in errs)


# ------------------------------------------- frozen telemetry key sets
SERVICE_KEYS = frozenset({"submits", "cache_hits", "fused_dispatches",
                          "coalesced_requests", "degraded", "tiers"})
EVALUATOR_KEYS = frozenset(
    f"evaluator_{n}" for n in ("dispatches", "worker_dispatches", "retried",
                               "straggler_redispatches", "timeouts",
                               "corrupt_rejected", "resizes"))
TIER_KEYS = frozenset({"weight", "served", "queued", "p50_ms", "p99_ms"})
TENANT_KEYS = frozenset({"rows_per_window", "used_rows", "admitted",
                         "admitted_rows", "rejected_budget",
                         "rejected_backpressure"})
ADMISSION_KEYS = frozenset({"admitted", "rejected", "max_queued_rows",
                            "rows_per_window", "window_s",
                            "observed_rows_per_s"})


def test_service_telemetry_keys_frozen_under_chaos():
    """The registry migration preserves every pre-registry telemetry()
    key, including while retries/timeouts are actually firing."""
    plan = FaultPlan([FaultEvent(0, 0, "crash"), FaultEvent(1, 1, "crash")])
    sharded = ShardedEvaluator(_fresh(), workers=2, mode="thread",
                               fault_plan=plan, speculate=False)
    svc = EvalService(sharded)
    svc.evaluate(EvalRequest(SPACE.sample(RNG, 8), detail="stalls"))
    tel = svc.telemetry()
    assert frozenset(tel) == SERVICE_KEYS | EVALUATOR_KEYS
    assert frozenset(tel["degraded"]) == {"deadline"} | set(DEGRADE_RUNGS)
    assert frozenset(tel["tiers"]) == frozenset(QOS_TIERS)
    for t in QOS_TIERS:
        assert frozenset(tel["tiers"][t]) == TIER_KEYS
    assert tel["evaluator_retried"] >= 2       # the chaos really happened
    assert all(isinstance(tel[k], int)
               for k in ("submits", "cache_hits", "fused_dispatches",
                         "coalesced_requests"))
    svc.close()


def test_gateway_telemetry_keys_frozen():
    gw = Gateway(_fresh(), rows_per_window=100, max_queued_rows=10_000)
    gw.evaluate(EvalRequest(SPACE.sample(RNG, 3)), tenant="acme")
    with pytest.raises(Exception):
        gw.submit(EvalRequest(SPACE.sample(RNG, 200)), tenant="acme")
    tel = gw.telemetry()
    assert frozenset(tel) == {"service", "tenants", "admission"}
    assert frozenset(tel["admission"]) == ADMISSION_KEYS
    assert frozenset(tel["tenants"]["acme"]) == TENANT_KEYS
    assert tel["tenants"]["acme"]["admitted"] == 1
    assert tel["tenants"]["acme"]["rejected_budget"] == 1
    assert tel["admission"] == gw.telemetry()["admission"]  # stable view
    gw.close()


def test_gateway_snapshot_merges_component_registries(tmp_path):
    sharded = ShardedEvaluator(_fresh(), workers=2, mode="thread")
    gw = Gateway(EvalService(sharded))
    gw.evaluate(EvalRequest(SPACE.sample(RNG, 4)))
    snap = gw.snapshot()
    assert frozenset(snap) == {"telemetry", "metrics"}
    assert {"gateway", "service", "evaluator"} <= set(snap["metrics"])
    assert snap["metrics"]["evaluator"]["sharded_dispatches"]["type"] \
        == "counter"
    path = tmp_path / "snap.json"
    gw.save_snapshot(path)
    loaded = json.loads(path.read_text())
    # the fleet dashboard renders straight off the persisted snapshot
    txt = fleet_report(loaded)
    assert "traffic" in txt and "gateway_admitted" not in txt
    gw.close()


# ---------------------------------------------------- heartbeat RTT
def test_heartbeat_rtt_histogram_per_worker(servers):
    s1, s2 = servers
    import time
    pool = SocketPool(_fresh(), 2,
                      addresses=[(s1.host, s1.port), (s2.host, s2.port)],
                      heartbeat_s=0.05)
    try:
        deadline = time.monotonic() + 5.0
        h = pool.metrics.get("heartbeat_rtt")
        while time.monotonic() < deadline:
            keys = set(h.series_keys())
            if keys == {("0",), ("1",)} and all(
                    h.count(worker=k[0]) >= 2 for k in keys):
                break
            time.sleep(0.02)
        assert set(h.series_keys()) == {("0",), ("1",)}
        for slot in ("0", "1"):
            st = h.stats(worker=slot)
            assert st["count"] >= 2
            assert 0 <= st["min"] <= st["max"] < 5.0
    finally:
        pool.close()


def test_gateway_surfaces_fleet_heartbeat_rtt(servers):
    s1, s2 = servers
    ev = ShardedEvaluator(_fresh(), mode="socket",
                          addresses=[(s1.host, s1.port), (s2.host, s2.port)])
    gw = Gateway(EvalService(ev))
    # deterministic: feed the registered histogram directly rather than
    # waiting out the 1 s heartbeat period
    ev.metrics.get("heartbeat_rtt").observe(0.002, worker="0")
    fleet = gw.telemetry()["fleet"]
    assert fleet["heartbeat_rtt"]["0"]["count"] == 1
    assert fleet["heartbeat_rtt"]["0"]["p50_ms"] == pytest.approx(2.0)
    assert fleet["heartbeat_rtt"]["0"]["p99_ms"] == pytest.approx(2.0)
    gw.close()


# ------------------------------------------- cross-machine causal tree
def _one_tree(spans, root_name):
    """Assert the spans form exactly one complete tree rooted at
    root_name and return {span name -> [spans]}."""
    roots = [s for s in spans if s.parent_id is None]
    assert [r.name for r in roots] == [root_name]
    assert completeness_errors(spans, trace_id=roots[0].trace_id) == []
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    return by_name


def test_socket_fleet_exports_single_causal_tree(servers):
    """Acceptance: one Gateway.evaluate against a 2-worker socket fleet
    exports ONE causal span tree spanning client and worker processes."""
    s1, s2 = servers
    tr = Tracer(proc="client")
    ev = ShardedEvaluator(_fresh(), mode="socket",
                          addresses=[(s1.host, s1.port), (s2.host, s2.port)],
                          tracer=tr)
    gw = Gateway(EvalService(ev, tracer=tr), tracer=tr)
    gw.evaluate(EvalRequest(SPACE.sample(RNG, 23), detail="stalls"),
                tenant="trace-me")
    spans = tr.spans()
    by_name = _one_tree(spans, "gateway.evaluate")
    for expected in ("service.tick", "service.dispatch", "sharded.evaluate",
                     "shard", "wire.dispatch", "worker.eval",
                     "sharded.reassemble"):
        assert expected in by_name, f"missing {expected} spans"
    # worker spans were minted in the worker process lane and adopted
    assert all(w.proc.startswith("worker:") for w in by_name["worker.eval"])
    assert len(by_name["worker.eval"]) >= 2    # really fanned out
    # wire span -> shard attempt -> sharded.evaluate chain holds
    shard_ids = {s.span_id for s in by_name["shard"]}
    assert all(w.parent_id in shard_ids for w in by_name["wire.dispatch"])
    wire_ids = {s.span_id for s in by_name["wire.dispatch"]}
    assert all(w.parent_id in wire_ids for w in by_name["worker.eval"])
    # and the whole thing round-trips through the Perfetto exporter
    obj = trace_events(spans)
    assert validate_trace_events(obj) == []
    gw.close()


def test_chaos_faults_close_spans_as_error_or_lost(servers):
    """Crash + hang chaos: the tree stays complete — failed attempts are
    closed error/lost, never left dangling."""
    s1, s2 = servers
    tr = Tracer(proc="client")
    plan = FaultPlan([FaultEvent(0, 0, "crash"), FaultEvent(1, 1, "hang")])
    ev = ShardedEvaluator(_fresh(), mode="socket",
                          addresses=[(s1.host, s1.port), (s2.host, s2.port)],
                          fault_plan=plan, shard_timeout_s=1.0,
                          speculate=False, tracer=tr)
    ev.evaluate(EvalRequest(SPACE.sample(RNG, 16), detail="stalls"))
    spans = tr.spans()
    by_name = _one_tree(spans, "sharded.evaluate")
    statuses = {s.status for s in by_name["shard"]}
    assert "ok" in statuses                    # the retries succeeded
    assert statuses & {"error", "lost"}        # and the faults left a mark
    ev.close()


# ------------------------------------------- campaign telemetry format
def test_campaign_result_carries_metrics_and_v4_loads(tmp_path):
    runner = CampaignRunner(_fresh(), seed=3)
    res = runner.run(budget=3)
    tel = res.telemetry_dict()
    assert tel["version"] == TELEMETRY_VERSION == 5
    assert tel["metrics"]["campaign_rounds"]["series"][0]["value"] >= 1
    obs = tel["metrics"]["campaign_observations"]["series"]
    assert sum(s["value"] for s in obs) == len(res.telemetry)
    path = tmp_path / "tel.json"
    res.save_telemetry(path)
    assert load_telemetry(path)["version"] == TELEMETRY_VERSION

    # a v4 file (pre-metrics) upgrades in memory
    v4 = dict(tel)
    v4.pop("metrics")
    v4["version"] = 4
    p4 = tmp_path / "v4.json"
    p4.write_text(json.dumps(v4))
    up = load_telemetry(p4)
    assert up["version"] == TELEMETRY_VERSION and up["metrics"] is None

    # a FUTURE format refuses to load
    v9 = dict(v4, version=TELEMETRY_VERSION + 1)
    p9 = tmp_path / "v9.json"
    p9.write_text(json.dumps(v9))
    with pytest.raises(ValueError):
        load_telemetry(p9)
