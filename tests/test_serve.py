"""repro.serve: the cross-machine DSE-as-a-service layer.

Covers the PR invariants: the length-prefixed pickle wire round-trips
every message type and rejects oversized frames before allocation; the
pickled worker spec rides pickle.HIGHEST_PROTOCOL and rebuilds a
bit-identical evaluator; a ShardedEvaluator over a 2-worker loopback
socket pool is bit-identical to the local ModelEvaluator on both
fidelity tiers, under chaos injection, and across a worker SIGKILL
mid-stream (eviction -> elastic resize -> retry); dead connections
reconnect and re-register; the QoS weighted-deficit drain keeps
scavenger throughput > 0 under saturating interactive load while tier
weights shape relative throughput; the Gateway enforces per-tenant row
budgets and queue-depth backpressure with reject-with-retry-after; and
the persistent oracle store turns a repeat OracleEvaluator into an O(1)
artifact load with corrupt artifacts quarantined, never trusted.
"""
import os
import socket as socket_mod
import threading
import time

import numpy as np
import pytest

from repro.distributed import (EvalService, ShardedEvaluator, ShardPayload,
                               WorkerFault)
from repro.distributed.faults import FaultEvent, FaultPlan
from repro.distributed.sharded import _worker_spec, evaluator_from_spec
from repro.perfmodel import (EvalRequest, ModelEvaluator, OracleEvaluator,
                             get_evaluator)
from repro.perfmodel.designspace import SPACE
from repro.distributed.faults import QuotaExceeded
from repro.serve import (Gateway, Keyring, RetryAfter, SocketPool,
                         WIRE_VERSION, WorkerOptions, WorkerServer,
                         start_worker_process, wire)
from repro.serve import codec as codec_mod

RNG = np.random.default_rng(7)


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


@pytest.fixture(scope="module")
def servers():
    """Two in-process worker daemons on loopback ephemeral ports."""
    s1, s2 = WorkerServer(), WorkerServer()
    s1.start()
    s2.start()
    yield s1, s2
    s1.close()
    s2.close()


# ---------------------------------------------------------------- wire
def test_wire_roundtrip_every_message_type():
    a, b = socket_mod.socketpair()
    try:
        for msg in (wire.Hello(b"spec"), wire.Ready("digest", ("lat",)),
                    wire.Dispatch(3, "payload"), wire.ResultMsg(3, "rep"),
                    wire.ErrorMsg(3, "boom"), wire.Ping(1), wire.Pong(1),
                    wire.Bye("done")):
            wire.send_msg(a, msg)
            assert wire.recv_msg(b) == msg
    finally:
        a.close()
        b.close()


def test_wire_rejects_oversized_frames_before_allocation():
    a, b = socket_mod.socketpair()
    try:
        wire.send_msg(a, wire.Dispatch(0, b"x" * 4096))
        with pytest.raises(wire.WireError, match="exceeds"):
            wire.recv_msg(b, max_bytes=64)
    finally:
        a.close()
        b.close()


def test_wire_eof_raises_connection_closed():
    a, b = socket_mod.socketpair()
    a.close()
    try:
        with pytest.raises(wire.ConnectionClosed):
            wire.recv_msg(b)
    finally:
        b.close()


def test_check_hello_gates_type_and_version():
    with pytest.raises(wire.WireError, match="expected Hello"):
        wire.check_hello(wire.Ping(0))
    with pytest.raises(wire.WireError, match="version"):
        wire.check_hello(wire.Hello(b"", wire_version=WIRE_VERSION + 1))
    hello = wire.Hello(b"spec")
    assert wire.check_hello(hello) is hello


# ---------------------------------------------------------------- spec
def test_spec_highest_protocol_and_roundtrip():
    """The worker spec rides pickle.HIGHEST_PROTOCOL and rebuilds an
    evaluator bit-identical to its source."""
    import pickle
    spec = _worker_spec(_fresh())
    assert spec[0] == 0x80                      # pickle protocol opcode
    assert spec[1] == pickle.HIGHEST_PROTOCOL
    rebuilt = evaluator_from_spec(spec)
    local = _fresh()
    idx = SPACE.sample(RNG, 9)
    for detail in ("objectives", "stalls"):
        req = EvalRequest(idx, detail=detail)
        _assert_reports_identical(rebuilt.evaluate(req), local.evaluate(req))


# -------------------------------------------------------- socket fabric
def test_socket_mode_argument_validation():
    with pytest.raises(ValueError, match="addresses"):
        ShardedEvaluator(_fresh(), mode="socket")
    with pytest.raises(ValueError, match="socket"):
        ShardedEvaluator(_fresh(), workers=2, addresses=[("h", 1)])


@pytest.mark.parametrize("tier", ["proxy", "target"])
def test_socket_sharded_bit_identical_to_local(servers, tier):
    """Acceptance: a 2-worker loopback socket pool reassembles reports
    bit-identical to the in-process evaluator, on both fidelity tiers."""
    s1, s2 = servers
    idx = SPACE.sample(RNG, 23)                 # odd size: uneven shards
    local = _fresh(tier)
    ev = ShardedEvaluator(_fresh(tier), mode="socket",
                          addresses=[(s1.host, s1.port), (s2.host, s2.port)])
    assert ev.mode == "socket" and ev.workers == 2
    for detail in ("objectives", "stalls"):
        req = EvalRequest(idx, detail=detail)
        _assert_reports_identical(ev.evaluate(req), local.evaluate(req))
    assert ev.worker_dispatches >= 2            # really fanned out
    snap = ev.registry.snapshot()
    assert sorted(snap["live"]) == [0, 1]
    ev.close()


def test_socket_chaos_crash_hang_bit_identical(servers):
    """FaultPlan chaos composes with the socket pool: a crashed dispatch
    retries and a hung one times out + retries, bit-identical result."""
    s1, s2 = servers
    idx = SPACE.sample(RNG, 16)
    local = _fresh().evaluate(EvalRequest(idx, "stalls"))
    plan = FaultPlan([FaultEvent(0, 0, "crash"), FaultEvent(1, 1, "hang")])
    ev = ShardedEvaluator(_fresh(), mode="socket",
                          addresses=[(s1.host, s1.port), (s2.host, s2.port)],
                          fault_plan=plan, shard_timeout_s=1.0,
                          speculate=False)
    rep = ev.evaluate(EvalRequest(idx, "stalls"))
    _assert_reports_identical(rep, local)
    assert ev.retried >= 2                      # crash + hang both retried
    assert ev.timeouts >= 1
    assert len(plan) == 0                       # every event consumed
    ev.close()


def test_socket_remote_evaluation_error_is_not_fatal(servers):
    """A worker-side evaluation failure surfaces as WorkerFault WITHOUT
    tearing the connection down — the next dispatch reuses it."""
    s1, _ = servers
    pool = SocketPool(_fresh(), addresses=[(s1.host, s1.port)])
    bad = ShardPayload(SPACE.sample(RNG, 2), "nonsense_detail", None)
    # the worker's EvalRequest validation rejects the detail remotely
    with pytest.raises(WorkerFault, match="remote evaluation"):
        pool.submit(bad).result(timeout=60)
    idx = SPACE.sample(RNG, 4)
    rep = pool.submit(ShardPayload(idx, "objectives", None)).result(timeout=60)
    _assert_reports_identical(rep, _fresh().evaluate(
        EvalRequest(idx, "objectives")))
    assert pool.live_workers() == 1 and pool.reconnects == 0
    pool.close()


def test_socket_pool_reconnect_reregisters(servers):
    """A dead connection fails in-flight work, is evicted from the
    registry, and the next submit redials + re-registers the slot."""
    s1, _ = servers
    pool = SocketPool(_fresh(), addresses=[(s1.host, s1.port)],
                      reconnect_cooldown_s=0.0)
    payload = ShardPayload(SPACE.sample(RNG, 4), "objectives", None)
    rep = pool.submit(payload).result(timeout=60)
    assert pool.registry.alive(0)
    pool._conns[0].die("simulated network partition")
    assert not pool.registry.alive(0)
    assert pool.registry.evictions >= 1
    rep2 = pool.submit(payload).result(timeout=60)
    _assert_reports_identical(rep, rep2)
    assert pool.reconnects == 1
    assert pool.registry.reregistrations >= 1
    assert pool.registry.alive(0)
    pool.close()


def test_start_worker_process_refuses_when_parent_holds_tpu(monkeypatch):
    """No worker daemon is spawned on a TPU host: the child would need the
    chip this process holds."""
    import jax
    import multiprocessing as mp
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = len(mp.active_children())
    with pytest.raises(RuntimeError, match="one process"):
        start_worker_process()
    assert len(mp.active_children()) == before


def test_socket_worker_sigkill_mid_stream_bit_identical():
    """Acceptance: SIGKILL a worker process while a stream of requests is
    in flight — the dead slot is evicted (elastic resize included) and
    every reassembled report stays bit-identical."""
    w1 = start_worker_process()
    w2 = start_worker_process()
    ev = None
    try:
        idx = SPACE.sample(RNG, 64)
        want = _fresh().evaluate(EvalRequest(idx, "stalls"))
        ev = ShardedEvaluator(_fresh(), mode="socket",
                              addresses=[w1.address, w2.address],
                              elastic=True)
        reports, errors = [], []

        def stream():
            try:
                for _ in range(30):
                    reports.append(ev.evaluate(EvalRequest(idx, "stalls")))
            except Exception as exc:            # noqa: BLE001 — reraised
                errors.append(exc)

        t = threading.Thread(target=stream)
        t.start()
        while len(reports) < 3 and t.is_alive():
            time.sleep(0.01)
        w2.kill()                               # SIGKILL, no goodbye
        t.join(timeout=300)
        assert not t.is_alive()
        assert not errors, errors
        assert len(reports) == 30
        for rep in reports:
            _assert_reports_identical(rep, want)
        snap = ev.registry.snapshot()
        assert snap["evictions"] >= 1           # the dead slot was noticed
        assert 0 in snap["live"]                # the survivor serves on
    finally:
        if ev is not None:
            ev.close()
        for w in (w1, w2):
            if w.alive():
                w.kill()


# ------------------------------------------------------------ QoS tiers
def test_service_tier_validation():
    ev = _fresh()
    with pytest.raises(ValueError, match="tier"):
        EvalService(ev).submit(EvalRequest(SPACE.sample(RNG, 1)),
                               tier="bulk")
    with pytest.raises(ValueError, match="unknown QoS tiers"):
        EvalService(ev, tier_weights={"bulk": 1.0})
    with pytest.raises(ValueError, match="> 0"):
        EvalService(ev, tier_weights={"batch": 0.0})


def test_qos_scavenger_never_starved_under_interactive_flood():
    """Acceptance: with a saturating interactive backlog and a row-capped
    tick, the anti-starvation floor keeps scavenger throughput > 0."""
    svc = EvalService(_fresh(), max_rows_per_tick=4)
    idx = SPACE.sample(RNG, 66)
    inter = [svc.submit(EvalRequest(idx[i:i + 1]), client=f"i{i}",
                        tier="interactive") for i in range(60)]
    scav = [svc.submit(EvalRequest(idx[60 + j:61 + j]), client="bg",
                       tier="scavenger") for j in range(6)]
    ticks = 0
    while not all(f.done() for f in scav):
        svc.tick()
        ticks += 1
        assert ticks <= 10                      # floor: >= 1 scavenger/tick
    assert svc.tier_served["scavenger"] == 6
    assert any(not f.done() for f in inter)     # the flood is still queued
    svc.close()


def test_qos_tier_weights_shape_throughput():
    """Equal offered load per tier + a row-capped tick: throughput orders
    by weight (8:3:1) and the cap is spent exactly every tick."""
    svc = EvalService(_fresh(), max_rows_per_tick=13)
    idx = SPACE.sample(RNG, 240)
    k = 0
    for t in ("interactive", "batch", "scavenger"):
        for _ in range(80):
            svc.submit(EvalRequest(idx[k:k + 1]), client=t, tier=t)
            k += 1
    for _ in range(8):
        svc.tick()
    served = dict(svc.tier_served)
    assert sum(served.values()) == 8 * 13       # cap spent exactly
    assert served["scavenger"] >= 8             # the floor, every tick
    assert served["interactive"] > 1.5 * served["batch"]
    assert served["batch"] > 1.5 * served["scavenger"]
    svc.close()


def test_service_tier_telemetry_percentiles():
    svc = EvalService(_fresh())
    idx = SPACE.sample(RNG, 2)
    svc.submit(EvalRequest(idx[:1]), tier="interactive")
    svc.submit(EvalRequest(idx[1:]), tier="batch")
    svc.tick()
    tiers = svc.telemetry()["tiers"]
    assert set(tiers) == {"interactive", "batch", "scavenger"}
    assert tiers["interactive"]["served"] == 1
    assert tiers["interactive"]["p50_ms"] is not None
    assert tiers["interactive"]["p99_ms"] >= tiers["interactive"]["p50_ms"]
    assert tiers["batch"]["weight"] == 3.0
    assert tiers["scavenger"]["served"] == 0
    assert tiers["scavenger"]["p50_ms"] is None
    svc.close()


# ------------------------------------------------------------- gateway
def test_gateway_budget_exhaustion_and_window_roll():
    clock = [0.0]
    gw = Gateway(_fresh(), rows_per_window=10, window_s=60.0,
                 now=lambda: clock[0])
    idx = SPACE.sample(RNG, 13)
    fut = gw.submit(EvalRequest(idx[:10]), tenant="acme")
    gw.tick()
    assert fut.done()
    with pytest.raises(RetryAfter) as ei:
        gw.submit(EvalRequest(idx[10:11]), tenant="acme")
    assert 0 < ei.value.retry_after_s <= 60.0
    tel = gw.telemetry()
    assert tel["tenants"]["acme"]["rejected_budget"] == 1
    assert tel["tenants"]["acme"]["used_rows"] == 10   # rejects cost nothing
    assert tel["admission"]["rejected"] == 1
    clock[0] += 61.0                            # the window rolls
    fut2 = gw.submit(EvalRequest(idx[10:12]), tenant="acme")
    gw.tick()
    assert fut2.done()
    assert gw.telemetry()["tenants"]["acme"]["used_rows"] == 2
    gw.close()


def test_gateway_backpressure_rejects_with_drain_eta():
    gw = Gateway(_fresh(), max_queued_rows=4)
    idx = SPACE.sample(RNG, 6)
    for i in range(4):                          # fill the backlog, no ticks
        gw.submit(EvalRequest(idx[i:i + 1]), tenant=f"t{i}")
    with pytest.raises(RetryAfter) as ei:
        gw.submit(EvalRequest(idx[4:5]), tenant="late")
    assert ei.value.retry_after_s > 0
    assert gw.telemetry()["tenants"]["late"]["rejected_backpressure"] == 1
    gw.tick()                                   # the backlog drains
    fut = gw.submit(EvalRequest(idx[4:5]), tenant="late")
    gw.tick()
    assert fut.done()
    gw.close()


def test_gateway_per_tenant_quota_overrides():
    gw = Gateway(_fresh(), rows_per_window=100, tenants={"small": 2})
    idx = SPACE.sample(RNG, 5)
    gw.submit(EvalRequest(idx[:2]), tenant="small")
    with pytest.raises(RetryAfter):
        gw.submit(EvalRequest(idx[2:3]), tenant="small")
    # unknown tenants get the default quota — config, not an allow-list
    gw.submit(EvalRequest(idx[:3]), tenant="unheard_of")
    gw.tick()
    gw.close()


def test_gateway_validation_and_tier_pass_through():
    with pytest.raises(ValueError, match="default_tier"):
        Gateway(_fresh(), default_tier="bulk")
    gw = Gateway(_fresh(), default_tier="scavenger")
    gw.submit(EvalRequest(SPACE.sample(RNG, 1)), tenant="t")
    gw.tick()
    assert gw.service.tier_served["scavenger"] == 1
    gw.close()


def test_gateway_is_drop_in_evaluator_with_fleet_telemetry():
    """The gateway implements the Evaluator protocol, and telemetry
    merges service counters, tenant ledgers and the fleet registry."""
    sharded = ShardedEvaluator(_fresh(), workers=2)
    gw = Gateway(EvalService(sharded))
    idx = SPACE.sample(RNG, 7)
    assert np.array_equal(gw.objectives(idx), _fresh().objectives(idx))
    tel = gw.telemetry()
    assert tel["service"]["submits"] >= 1
    assert tel["fleet"]["workers"] == 2
    assert sorted(tel["fleet"]["live"]) == [0, 1]
    assert tel["tenants"]["default"]["admitted"] == 1
    gw.close()
    sharded.close()


# --------------------------------------------------------- oracle store
SUB = 6_000


def test_oracle_store_repeat_is_o1_load(tmp_path, monkeypatch):
    from repro.perfmodel.sweep import SweepEngine
    calls = {"n": 0}
    orig = SweepEngine.run

    def counting(self, *a, **kw):
        calls["n"] += 1
        return orig(self, *a, **kw)

    monkeypatch.setattr(SweepEngine, "run", counting)
    store = str(tmp_path / "oracle")
    kw = dict(sweep_kwargs=dict(chunk_size=4_096), stop=SUB,
              oracle_store=store)
    r1 = OracleEvaluator(get_evaluator("proxy"), **kw).sweep_result()
    assert calls["n"] == 1
    assert len(os.listdir(store)) == 1
    r2 = OracleEvaluator(get_evaluator("proxy"), **kw).sweep_result()
    assert calls["n"] == 1                      # loaded, not re-swept
    assert r1.n_evaluated == r2.n_evaluated
    assert np.array_equal(r1.pareto_y, r2.pareto_y)
    assert np.array_equal(r1.pareto_ids, r2.pareto_ids)
    assert np.array_equal(r1.topk_val, r2.topk_val)
    assert np.array_equal(r1.topk_ids, r2.topk_ids)
    # a different sweep config is a different key -> fresh artifact
    OracleEvaluator(get_evaluator("proxy"),
                    sweep_kwargs=dict(chunk_size=4_096), stop=SUB - 1_000,
                    oracle_store=store).sweep_result()
    assert calls["n"] == 2
    assert len(os.listdir(store)) == 2


def test_oracle_store_corrupt_artifact_quarantined(tmp_path):
    store = str(tmp_path / "oracle")
    kw = dict(sweep_kwargs=dict(chunk_size=4_096), stop=SUB,
              oracle_store=store)
    r1 = OracleEvaluator(get_evaluator("proxy"), **kw).sweep_result()
    (fname,) = os.listdir(store)
    path = os.path.join(store, fname)
    with open(path, "wb") as f:
        f.write(b"not an npz artifact")
    with pytest.warns(RuntimeWarning, match="quarantined"):
        r2 = OracleEvaluator(get_evaluator("proxy"), **kw).sweep_result()
    assert np.array_equal(r1.pareto_y, r2.pareto_y)
    assert os.path.exists(path + ".quarantined")
    assert os.path.exists(path)                 # re-swept artifact rewritten


def test_sweep_result_save_load_guards(tmp_path):
    from repro.perfmodel.sweep import (SweepEngine, load_sweep_result,
                                       save_sweep_result)
    res = SweepEngine(get_evaluator("proxy"),
                      chunk_size=4_096).run(0, 3_000)
    path = str(tmp_path / "art.npz")
    save_sweep_result(path, res, key="k1")
    back = load_sweep_result(path, key="k1")
    assert np.array_equal(back.pareto_y, res.pareto_y)
    assert np.array_equal(back.topk_val, res.topk_val)
    with pytest.raises(ValueError, match="different"):
        load_sweep_result(path, key="some-other-study")
    with pytest.raises(FileNotFoundError):
        load_sweep_result(str(tmp_path / "missing.npz"))


# ------------------------------------------------- trusted wire (PR 10)
KEYS = {"k1": b"alpha-secret", "k2": b"beta-secret"}


def _keyring(active="k1"):
    return Keyring(KEYS, active=active)


def test_codec_value_roundtrip_restricted_types():
    """The binary codec round-trips exactly the frame vocabulary's types,
    arrays bit-identically across the dtype allowlist."""
    cases = [
        None, True, False, 0, -1, 2**40, -(2**70), 1.5, float("inf"),
        "héllo", b"\x00\xff raw", (1, "two", None), [1.0, [2, 3]],
        {"k": (1, 2), "nested": {"x": b"y"}}, (),
    ]
    for v in cases:
        assert codec_mod.decode_value(codec_mod.encode_value(v)) == v
    for dtype in sorted(codec_mod.ALLOWED_DTYPES):
        arr = (RNG.random((3, 4)) * 100).astype(dtype)
        back = codec_mod.decode_value(codec_mod.encode_value(arr))
        assert back.dtype == arr.dtype and np.array_equal(back, arr)
    # NaN payloads survive bit-exactly too (array path is raw bytes)
    arr = np.array([np.nan, 1.0, -np.inf])
    back = codec_mod.decode_value(codec_mod.encode_value(arr))
    assert arr.tobytes() == back.tobytes()


def test_codec_rejects_offschema():
    """Anything outside the schema is a typed CodecError, never an
    object: bad dtypes, non-str dict keys, arbitrary classes, trailing
    or truncated bytes, unknown tags."""
    with pytest.raises(codec_mod.CodecError, match="dtype"):
        codec_mod.encode_value(np.array([object()]))
    with pytest.raises(codec_mod.CodecError, match="keys"):
        codec_mod.encode_value({1: "x"})
    with pytest.raises(codec_mod.CodecError, match="not wire-encodable"):
        codec_mod.encode_value(Keyring(KEYS))
    with pytest.raises(codec_mod.CodecError, match="unknown value tag"):
        codec_mod.decode_value(b"Z")
    with pytest.raises(codec_mod.CodecError, match="truncated"):
        codec_mod.decode_value(codec_mod.encode_value("hello")[:-2])
    with pytest.raises(codec_mod.CodecError, match="trailing"):
        codec_mod.decode_value(codec_mod.encode_value(1) + b"junk")


def test_codec_bounds_nesting_depth():
    """A hostile frame of stacked container headers is a typed
    CodecError, never a RecursionError that would escape the reader
    threads' typed except clauses."""
    import struct as struct_mod
    one = struct_mod.pack(">I", 1)
    # schema-depth structures stay well inside the bound
    v = {"a": [( {"b": [1]}, )]}
    assert codec_mod.decode_value(codec_mod.encode_value(v)) == v
    for header in (b"L" + one, b"U" + one,
                   b"M" + one + struct_mod.pack(">I", 1) + b"k"):
        hostile = header * (codec_mod.MAX_NESTING_DEPTH + 8) + b"N"
        with pytest.raises(codec_mod.CodecError, match="nesting deeper"):
            codec_mod.decode_value(hostile)


def test_codec_message_roundtrip_every_type():
    idx = SPACE.sample(RNG, 5)
    payload = ShardPayload(idx, "stalls", ("ttft", "tpot"))
    report = _fresh().evaluate(EvalRequest(idx, "stalls"))
    span = {"name": "worker.eval", "trace_id": "t", "span_id": "s",
            "parent_id": None, "proc": "w:1", "thread": "serve-eval",
            "t_start": 0.1, "t_end": 0.2, "status": "ok",
            "attrs": {"rows": 5}}
    msgs = [wire.Hello(b"spec-bytes"), wire.Ready("digest", ("a", "b")),
            wire.Dispatch(7, payload, ("tid", "sid")),
            wire.ResultMsg(7, report, (span,)),
            wire.ErrorMsg(7, "boom", (), "quota.rows"),
            wire.ErrorMsg(-1, "fatal"),
            wire.Ping(3), wire.Pong(3), wire.Bye("done"),
            wire.Announce(("10.0.0.7", 9707), ("d1", "d2"), 4),
            wire.LeaseAck(2.5)]
    for msg in msgs:
        back = codec_mod.decode_msg(codec_mod.encode_msg(msg))
        assert type(back) is type(msg)
        if isinstance(msg, wire.Dispatch):
            assert back.seq == msg.seq and back.trace_ctx == msg.trace_ctx
            assert np.array_equal(back.payload.idx, payload.idx)
            assert back.payload.detail == payload.detail
            assert back.payload.workloads == payload.workloads
        elif isinstance(msg, wire.ResultMsg):
            _assert_reports_identical(back.report, report)
            assert back.spans == (span,)
        else:
            assert back == msg


def test_auth_sign_verify_rotation_and_rejects():
    """Frames are HMAC-signed with the key id in the header (so rings
    rotate without downtime); unsigned / unknown-key / tampered /
    replayed frames raise typed AuthErrors before any decoding."""
    ring = _keyring("k1")
    body = codec_mod.encode_msg(wire.Ping(1))
    # signing key rotates per-frame via key_id; both verify on one ring
    for kid in ("k1", "k2"):
        frame = codec_mod.seal_frame(body, ring, seq=0, key_id=kid)
        assert codec_mod.open_frame(frame, ring, expected_seq=0) == body
    # unsigned frame against a keyed receiver
    with pytest.raises(codec_mod.AuthError, match="unsigned"):
        codec_mod.open_frame(codec_mod.seal_frame(body, None, 0), ring, 0)
    # unknown key id
    other = Keyring({"k9": b"stranger"})
    with pytest.raises(codec_mod.AuthError, match="unknown_key"):
        codec_mod.open_frame(codec_mod.seal_frame(body, other, 0), ring, 0)
    # tampered body (bit flip after sealing)
    frame = bytearray(codec_mod.seal_frame(body, ring, 0))
    frame[-1] ^= 0x01
    with pytest.raises(codec_mod.AuthError, match="tamper"):
        codec_mod.open_frame(bytes(frame), ring, 0)
    # replay: stale sequence number, valid MAC
    frame = codec_mod.seal_frame(body, ring, seq=0)
    assert codec_mod.open_frame(frame, ring, 0) == body
    with pytest.raises(codec_mod.AuthError, match="replay"):
        codec_mod.open_frame(frame, ring, 1)
    # session binding: a frame sealed under one connection's nonces
    # never verifies under another's (cross-connection replay)
    frame = codec_mod.seal_frame(body, ring, seq=0, binding=b"sess-A")
    assert codec_mod.open_frame(frame, ring, 0, binding=b"sess-A") == body
    with pytest.raises(codec_mod.AuthError, match="tamper"):
        codec_mod.open_frame(frame, ring, 0, binding=b"sess-B")
    with pytest.raises(codec_mod.AuthError, match="tamper"):
        codec_mod.open_frame(frame, ring, 0)


def test_restricted_loads_blocks_gadgets_allows_spec():
    """The allowlisted constructor table rebuilds real evaluator specs
    but refuses pickle gadgets before construction."""
    import pickle
    spec = _worker_spec(_fresh())
    rebuilt = evaluator_from_spec(spec, loads=codec_mod.restricted_loads)
    idx = SPACE.sample(RNG, 6)
    _assert_reports_identical(
        rebuilt.evaluate(EvalRequest(idx, "objectives")),
        _fresh().evaluate(EvalRequest(idx, "objectives")))

    class Gadget:                       # classic reduce-to-call payload
        def __reduce__(self):
            return (os.system, ("true",))

    evil = pickle.dumps(Gadget())
    with pytest.raises(codec_mod.CodecError, match="not allowlisted"):
        codec_mod.restricted_loads(evil)
    evil2 = pickle.dumps(pytest.raises)  # callable outside repro/numpy
    with pytest.raises(codec_mod.CodecError, match="not allowlisted"):
        codec_mod.restricted_loads(evil2)


def test_restricted_loads_blocks_module_attribute_traversal():
    """Hand-crafted pickles cannot laterally escape the allowlist: a
    repro module's re-exported ``os`` resolves to a module (not a
    class) and is refused, and ``builtins.getattr`` — the gadget that
    would turn any such module into ``os.system`` — is not allowlisted
    at all."""
    def su(s):                       # SHORT_BINUNICODE opcode
        b = s.encode("utf-8")
        return b"\x8c" + bytes([len(b)]) + b

    PROTO, STACK_GLOBAL, STOP = b"\x80\x04", b"\x93", b"."
    TUPLE2, REDUCE = b"\x86", b"R"
    # STACK_GLOBAL('repro.runtime.fault', 'os'): an allowlisted module's
    # top-level `import os` must not resolve through find_class
    evil = (PROTO + su("repro.runtime.fault") + su("os")
            + STACK_GLOBAL + STOP)
    with pytest.raises(codec_mod.CodecError, match="not a class"):
        codec_mod.restricted_loads(evil)
    # the full traversal chain: getattr(<module os>, 'system')('true')
    evil = (PROTO
            + su("builtins") + su("getattr") + STACK_GLOBAL
            + su("repro.runtime.fault") + su("os") + STACK_GLOBAL
            + su("system") + TUPLE2 + REDUCE
            + su("true") + b"\x85" + REDUCE       # TUPLE1 + call
            + STOP)
    with pytest.raises(codec_mod.CodecError, match="not allowlisted"):
        codec_mod.restricted_loads(evil)
    # plain pickled specs still cannot smuggle getattr either
    import pickle
    with pytest.raises(codec_mod.CodecError, match="not allowlisted"):
        codec_mod.restricted_loads(pickle.dumps(getattr))


@pytest.mark.parametrize("tier", ["proxy", "target"])
def test_secure_socket_bit_identical_both_tiers(tier):
    """Acceptance: codec + HMAC end-to-end — a keyed 2-worker fleet is
    bit-identical to in-process on both fidelity tiers, with zero auth
    or quota noise."""
    s1 = WorkerServer(options=WorkerOptions(keys=KEYS))
    s2 = WorkerServer(options=WorkerOptions(keys=KEYS))
    s1.start()
    s2.start()
    ev = None
    try:
        idx = SPACE.sample(RNG, 23)
        local = _fresh(tier)
        ev = ShardedEvaluator(_fresh(tier), mode="socket",
                              addresses=[(s1.host, s1.port),
                                         (s2.host, s2.port)],
                              keyring=_keyring())
        for detail in ("objectives", "stalls"):
            req = EvalRequest(idx, detail=detail)
            _assert_reports_identical(ev.evaluate(req), local.evaluate(req))
        assert s1.auth_rejected() == 0 and s2.auth_rejected() == 0
        assert ev.quota_rerouted == 0
    finally:
        if ev is not None:
            ev.close()
        s1.close()
        s2.close()


def test_secure_worker_refuses_legacy_pickle_and_unsigned():
    """A hardened worker refuses the pickle codec outright and, when
    keyed, refuses unsigned binary frames — both counted, neither
    evaluated."""
    srv = WorkerServer(options=WorkerOptions(keys=KEYS))
    srv.start()
    try:
        # legacy pickle client (insecure pool) against a secure worker
        with pytest.raises(RuntimeError, match="binary codec"):
            SocketPool(_fresh(), addresses=[(srv.host, srv.port)],
                       insecure=True)
        assert srv.auth_rejected("pickle_codec") == 1
        # unsigned binary client against a keyed worker
        with pytest.raises(RuntimeError, match="no repro.serve worker"):
            SocketPool(_fresh(), addresses=[(srv.host, srv.port)])
        assert srv.auth_rejected("unsigned") >= 1
        assert srv.dispatches_served == 0
    finally:
        srv.close()


def test_insecure_flag_restores_legacy_pickle_mode():
    """insecure=True on both ends keeps the PR 7 single-trust-domain
    transport working (explicitly opted into, never default)."""
    srv = WorkerServer(options=WorkerOptions(insecure=True))
    srv.start()
    ev = None
    try:
        idx = SPACE.sample(RNG, 8)
        ev = ShardedEvaluator(_fresh(), mode="socket",
                              addresses=[(srv.host, srv.port)],
                              insecure=True)
        _assert_reports_identical(
            ev.evaluate(EvalRequest(idx, "objectives")),
            _fresh().evaluate(EvalRequest(idx, "objectives")))
    finally:
        if ev is not None:
            ev.close()
        srv.close()


def test_wire_tamper_and_replay_counted_never_evaluated():
    """Acceptance: a tampered or replayed frame on a live connection is
    rejected + counted by the worker and the dispatch never evaluates."""
    srv = WorkerServer(options=WorkerOptions(keys=KEYS))
    srv.start()
    try:
        ring = _keyring()
        # --- tampered Dispatch ------------------------------------------
        sock = wire.connect((srv.host, srv.port))
        ch = codec_mod.Channel(sock, keyring=ring)
        ch.client_handshake()
        ch.send(wire.Hello(_worker_spec(_fresh())))
        assert isinstance(ch.recv(), wire.Ready)
        dispatch = wire.Dispatch(0, ShardPayload(SPACE.sample(RNG, 2),
                                                 "objectives", None))
        frame = bytearray(codec_mod.seal_frame(
            codec_mod.encode_msg(dispatch), ring, seq=1,
            binding=ch.binding))
        frame[-3] ^= 0xFF                        # corrupt the body
        wire.send_frame(sock, bytes(frame))
        reply = ch.recv()
        assert isinstance(reply, wire.ErrorMsg) and reply.code == "auth.tamper"
        sock.close()
        deadline = time.monotonic() + 10
        while srv.auth_rejected("tamper") < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.auth_rejected("tamper") == 1
        # --- replayed Dispatch ------------------------------------------
        sock = wire.connect((srv.host, srv.port))
        ch = codec_mod.Channel(sock, keyring=ring)
        ch.client_handshake()
        ch.send(wire.Hello(_worker_spec(_fresh())))
        assert isinstance(ch.recv(), wire.Ready)
        good = codec_mod.seal_frame(codec_mod.encode_msg(dispatch), ring,
                                    seq=1, binding=ch.binding)
        wire.send_frame(sock, good)
        first = ch.recv()
        assert isinstance(first, wire.ResultMsg)  # the original lands
        wire.send_frame(sock, good)               # verbatim replay
        reply = ch.recv()
        assert isinstance(reply, wire.ErrorMsg) and reply.code == "auth.replay"
        sock.close()
        deadline = time.monotonic() + 10
        while srv.auth_rejected("replay") < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.auth_rejected("replay") == 1
        assert srv.dispatches_served == 1         # replay never evaluated
    finally:
        srv.close()


class _RecordingSocket:
    """Socket proxy that keeps a copy of every outbound chunk — the
    network attacker's tape recorder."""

    def __init__(self, sock):
        self._sock = sock
        self.sent = []

    def sendall(self, data):
        self.sent.append(bytes(data))
        self._sock.sendall(data)

    def recv(self, n):
        return self._sock.recv(n)

    def close(self):
        self._sock.close()


def test_recorded_session_replayed_on_new_connection_is_rejected():
    """Cross-connection replay: record an entire valid signed session,
    replay it verbatim over a fresh TCP connection — the worker's fresh
    session nonce changes every expected MAC, so nothing verifies,
    nothing evaluates, and the reject is counted."""
    srv = WorkerServer(options=WorkerOptions(keys=KEYS))
    srv.start()
    try:
        ring = _keyring()
        rec = _RecordingSocket(wire.connect((srv.host, srv.port)))
        ch = codec_mod.Channel(rec, keyring=ring)
        ch.client_handshake()
        ch.send(wire.Hello(_worker_spec(_fresh())))
        assert isinstance(ch.recv(), wire.Ready)
        ch.send(wire.Dispatch(0, ShardPayload(SPACE.sample(RNG, 2),
                                              "objectives", None)))
        assert isinstance(ch.recv(), wire.ResultMsg)
        rec.close()
        deadline = time.monotonic() + 10
        # the reply races the worker-side counter inc: wait it out
        while srv.dispatches_served < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv.dispatches_served == 1
        # the attacker replays the recorded byte stream on a new socket
        replay_sock = wire.connect((srv.host, srv.port))
        for chunk in rec.sent:
            try:
                replay_sock.sendall(chunk)
            except OSError:
                break                 # server already dropped the replay
        deadline = time.monotonic() + 10
        while srv.auth_rejected() < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        replay_sock.close()
        # replayed Hello fails its MAC under the fresh server nonce
        assert srv.auth_rejected("tamper") >= 1
        assert srv.dispatches_served == 1     # nothing re-evaluated
    finally:
        srv.close()


def test_signed_frames_without_session_handshake_are_rejected():
    """A keyed endpoint refuses signed traffic outside a nonce-bound
    session (the window a handshake-stripping replay would need)."""
    srv = WorkerServer(options=WorkerOptions(keys=KEYS))
    srv.start()
    try:
        ring = _keyring()
        sock = wire.connect((srv.host, srv.port))
        body = codec_mod.encode_msg(wire.Hello(b"spec"))
        wire.send_frame(sock, codec_mod.seal_frame(body, ring, seq=0))
        deadline = time.monotonic() + 10
        while srv.auth_rejected("replay") < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        sock.close()
        assert srv.auth_rejected("replay") == 1
        assert srv.dispatches_served == 0
    finally:
        srv.close()


def test_pickle_channel_serializes_concurrent_sends():
    """The legacy pickle path locks the socket write like the binary
    path: many threads sharing one channel (reader Pong, eval Result,
    deadline timer) never interleave the length-prefixed stream."""
    a, b = socket_mod.socketpair()
    try:
        ch = codec_mod.Channel(a, codec=codec_mod.CODEC_PICKLE)
        peer = codec_mod.Channel(b, codec=codec_mod.CODEC_PICKLE)
        n_threads, per_thread = 8, 40
        pad = "x" * 4096            # big enough to straddle sendall calls
        got, errs = [], []

        def reader():
            try:
                for _ in range(n_threads * per_thread):
                    got.append(peer.recv().seq)
            except Exception as exc:     # noqa: BLE001 — test harness
                errs.append(exc)

        def blast(t):
            for i in range(per_thread):
                ch.send(wire.ErrorMsg(t * per_thread + i, pad))

        rt = threading.Thread(target=reader)
        rt.start()
        threads = [threading.Thread(target=blast, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rt.join(timeout=30)
        assert not errs and not rt.is_alive()
        assert sorted(got) == list(range(n_threads * per_thread))
    finally:
        a.close()
        b.close()


def test_worker_prunes_idle_peer_rate_buckets():
    """Per-peer token buckets are evicted once fully refilled, so the
    worker does not grow one bucket per client IP forever."""
    from repro.obs.metrics import ManualClock
    clk = ManualClock()
    srv = WorkerServer(options=WorkerOptions(rate_limit=10.0), clock=clk)
    try:
        msg = wire.Dispatch(0, ShardPayload(SPACE.sample(RNG, 1),
                                            "objectives", None))
        for i in range(50):
            assert srv._check_quota(msg, f"10.0.0.{i}") is None
        assert len(srv._buckets) == 50
        clk.advance(60.0)              # every bucket refills (burst/rate=2s)
        assert srv._check_quota(msg, "10.1.0.1") is None
        assert set(srv._buckets) == {"10.1.0.1"}
        # a still-active peer is never pruned out from under its debit
        clk.advance(0.05)
        assert srv._check_quota(msg, "10.1.0.1") is None
        assert "10.1.0.1" in srv._buckets
    finally:
        srv.close()


# ------------------------------------------------- frame-size satellite
def test_max_frame_bytes_oversized_dispatch_integration():
    """The frame bound is configurable end to end: an oversized Dispatch
    is refused client-side BEFORE it hits the wire (loud, connection
    intact), and small dispatches keep flowing."""
    srv = WorkerServer(options=WorkerOptions(keys=KEYS))
    srv.start()
    try:
        pool = SocketPool(_fresh(), addresses=[(srv.host, srv.port)],
                          keyring=_keyring(), max_frame_bytes=1 << 15)
        with pytest.raises(codec_mod.FrameTooLarge, match="frame bound"):
            pool.submit(ShardPayload(SPACE.sample(RNG, 3000),
                                     "objectives", None))
        idx = SPACE.sample(RNG, 4)               # small one still flows
        rep = pool.submit(ShardPayload(idx, "objectives", None)) \
            .result(timeout=60)
        _assert_reports_identical(
            rep, _fresh().evaluate(EvalRequest(idx, "objectives")))
        assert pool.live_workers() == 1 and pool.reconnects == 0
        pool.close()
    finally:
        srv.close()


# ---------------------------------------------------------- worker quotas
def test_quota_rows_rerouted_not_hammered():
    """A worker refusing shards by rows-quota gets rerouted around, not
    retried-at: the merged report stays bit-identical, the refusal is
    counted on both ends, and the refusing worker is NOT evicted."""
    tight = WorkerServer(options=WorkerOptions(
        keys=KEYS, max_rows_per_dispatch=4))
    open_ = WorkerServer(options=WorkerOptions(keys=KEYS))
    tight.start()
    open_.start()
    ev = None
    try:
        idx = SPACE.sample(RNG, 30)             # 15-row shards: over quota
        ev = ShardedEvaluator(_fresh(), mode="socket",
                              addresses=[(tight.host, tight.port),
                                         (open_.host, open_.port)],
                              keyring=_keyring(), retries=1)
        rep = ev.evaluate(EvalRequest(idx, "stalls"))
        _assert_reports_identical(
            rep, _fresh().evaluate(EvalRequest(idx, "stalls")))
        assert tight.quota_rejected("rows") >= 1
        assert ev.quota_rerouted >= 1
        assert ev.retried == 0                  # reroute consumed NO budget
        snap = ev.registry.snapshot()
        assert sorted(snap["live"]) == [0, 1]   # refusing worker not evicted
    finally:
        if ev is not None:
            ev.close()
        tight.close()
        open_.close()


def test_quota_rate_limit_token_bucket():
    """Per-peer token bucket: burst dispatches above the rate come back
    as typed QuotaExceeded, worker healthy throughout."""
    srv = WorkerServer(options=WorkerOptions(
        keys=KEYS, rate_limit=0.001, rate_burst=2))
    srv.start()
    try:
        pool = SocketPool(_fresh(), addresses=[(srv.host, srv.port)],
                          keyring=_keyring())
        payload = ShardPayload(SPACE.sample(RNG, 2), "objectives", None)
        futs = [pool.submit(payload) for _ in range(4)]
        outcomes = []
        for f in futs:
            try:
                f.result(timeout=60)
                outcomes.append("ok")
            except QuotaExceeded as exc:
                assert exc.code == "quota.rate"
                outcomes.append("quota")
        assert outcomes.count("ok") == 2        # the burst allowance
        assert outcomes.count("quota") == 2
        assert srv.quota_rejected("rate") == 2
        assert pool.quota_rejected == 2
        assert pool.live_workers() == 1         # refusals keep the wire up
        pool.close()
    finally:
        srv.close()


def test_quota_deadline_rejects_long_dispatch():
    """A dispatch past the wall-clock deadline answers with
    quota.deadline (typed, counted) instead of hanging the client."""
    srv = WorkerServer(options=WorkerOptions(keys=KEYS, deadline_s=1e-4))
    srv.start()
    try:
        pool = SocketPool(_fresh(), addresses=[(srv.host, srv.port)],
                          keyring=_keyring())
        fut = pool.submit(ShardPayload(SPACE.sample(RNG, 64),
                                       "stalls", None))
        with pytest.raises(QuotaExceeded, match="deadline"):
            fut.result(timeout=60)
        assert srv.quota_rejected("deadline") == 1
        assert pool.live_workers() == 1
        pool.close()
    finally:
        srv.close()


def test_quota_concurrency_admission_is_checked_before_eval():
    """max_concurrent_evals admits on the reader thread: the semaphore
    refuses the N+1th in-flight dispatch deterministically."""
    srv = WorkerServer(options=WorkerOptions(max_concurrent_evals=1))
    payload = ShardPayload(SPACE.sample(RNG, 2), "objectives", None)
    d1, d2 = wire.Dispatch(0, payload), wire.Dispatch(1, payload)
    assert srv._check_quota(d1, "peer") is None          # takes the slot
    kind, detail = srv._check_quota(d2, "peer")
    assert kind == "concurrency" and "max_concurrent_evals=1" in detail
    srv._eval_slots.release()                            # eval finished
    assert srv._check_quota(d2, "peer") is None
    srv._eval_slots.release()
    srv.close()


# ------------------------------------------------------------------ TLS
def _make_tls_certs(tmp_path):
    import shutil
    import subprocess
    if shutil.which("openssl") is None:
        pytest.skip("openssl CLI not available for test certs")
    cert, key = str(tmp_path / "cert.pem"), str(tmp_path / "key.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", cert, "-days", "1", "-subj",
         "/CN=127.0.0.1"],
        check=True, capture_output=True)
    return cert, key


def test_tls_wrapped_socket_bit_identical(tmp_path):
    """Optional TLS: worker wraps its accept loop, client wraps its
    dials, reports stay bit-identical over the encrypted wire."""
    import ssl
    cert, key = _make_tls_certs(tmp_path)
    srv = WorkerServer(options=WorkerOptions(keys=KEYS, certfile=cert,
                                             keyfile=key))
    srv.start()
    ev = None
    try:
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        ctx.check_hostname = False
        ctx.verify_mode = ssl.CERT_NONE         # self-signed test cert
        idx = SPACE.sample(RNG, 10)
        ev = ShardedEvaluator(_fresh(), mode="socket",
                              addresses=[(srv.host, srv.port)],
                              keyring=_keyring(), ssl_context=ctx)
        _assert_reports_identical(
            ev.evaluate(EvalRequest(idx, "stalls")),
            _fresh().evaluate(EvalRequest(idx, "stalls")))
    finally:
        if ev is not None:
            ev.close()
        srv.close()


def test_secure_fabric_survives_chaos_and_sigkill():
    """Acceptance: the full hardened stack (codec + HMAC, spawned worker
    processes) stays bit-identical through chaos crash/hang and a
    SIGKILL mid-stream."""
    opts = WorkerOptions(keys=KEYS)
    w1 = start_worker_process(options=opts)
    w2 = start_worker_process(options=opts)
    ev = None
    try:
        idx = SPACE.sample(RNG, 32)
        want = _fresh().evaluate(EvalRequest(idx, "stalls"))
        plan = FaultPlan([FaultEvent(0, 0, "crash"),
                          FaultEvent(1, 1, "hang")])
        ev = ShardedEvaluator(_fresh(), mode="socket",
                              addresses=[w1.address, w2.address],
                              keyring=_keyring(), fault_plan=plan,
                              shard_timeout_s=5.0, speculate=False,
                              elastic=True)
        reports, errors = [], []

        def stream():
            try:
                for _ in range(12):
                    reports.append(ev.evaluate(EvalRequest(idx, "stalls")))
            except Exception as exc:            # noqa: BLE001 — reraised
                errors.append(exc)

        t = threading.Thread(target=stream)
        t.start()
        while len(reports) < 2 and t.is_alive():
            time.sleep(0.01)
        w2.kill()                               # SIGKILL, no goodbye
        t.join(timeout=300)
        assert not t.is_alive()
        assert not errors, errors
        assert len(reports) == 12
        for rep in reports:
            _assert_reports_identical(rep, want)
        assert ev.registry.snapshot()["evictions"] >= 1
    finally:
        if ev is not None:
            ev.close()
        for w in (w1, w2):
            if w.alive():
                w.kill()
