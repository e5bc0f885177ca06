"""Tests of the span and scope reduction and the span metrics, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests

They cover ``harness.spans`` on a synthetic nested trace, that the wider
annotation list leaves the reduction's existing keys as they were on a
recorded profile, the span readers on records without spans (a program
that opens none), and every span metric on a traced tiny run of each cell
through ``trace_spans.py``.
"""
import json
import math
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import trace_spans  # noqa: E402
from harness import device, spec, spans, trace, xplane  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

BENCH_JSON = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH_JSON["workloads"] if w["chips"] == 1]
PENDING = trace_spans.pending_metrics()


def _ev(name, start, end, **stats):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start,
                                 stats=list(stats.items()))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=evs) for ln, evs in lines])


# ------------------------------------------------------------ reduction
def test_spans_on_a_synthetic_nested_trace():
    host = _plane("/host:CPU", [
        ("python", [
            _ev("pb.window", 0, 1000),
            _ev("sweep.chunk", 100, 500),
            _ev("sweep.filter", 110, 150),
            _ev("other", 160, 200),               # not listed: not nested
            _ev("sweep.insert", 300, 450, rows=7, tag="x"),
            _ev("sweep.chunk", 600, 900),
            _ev("sweep.insert", 700, 750, rows=5),
            _ev("sweep.chunk", 1200, 1300)]),     # outside the window
        ("worker", [_ev("sweep.insert", 800, 850, rows=1)])])
    sp = spans.reduce_spans(types.SimpleNamespace(planes=[host]), (0, 1000),
                            spans.PREFIXES)
    assert set(sp) == {"pb.window", "sweep.chunk", "sweep.filter",
                       "sweep.insert"}
    c = sp["sweep.chunk"]
    assert c["count"] == 2
    assert c["s"] == pytest.approx(700e-9)
    assert c["self_s"] == pytest.approx((400 - 40 - 150 + 300 - 50) * 1e-9)
    ins = sp["sweep.insert"]
    assert ins["count"] == 3 and ins["stats"] == {"rows": 13}
    assert ins["self_s"] == pytest.approx(250e-9)
    assert sp["pb.window"]["self_s"] == pytest.approx((1000 - 700) * 1e-9)


def test_scopes_on_a_synthetic_device_trace():
    """TPU form: an operation's program is the ``XLA Modules`` event around
    it, its instruction the start of its event name."""
    mods = ("XLA Modules", [_ev("jit__step_impl(123)", 0, 100),
                            _ev("jit__step_impl(123)", 200, 300),
                            _ev("jit_other(7)", 400, 500)])
    d0 = _plane("/device:TPU:0", [mods, ("XLA Ops", [
        _ev("%fusion = f32[8] fusion(f32[8,14] %p)", 0, 60),
        _ev("%fusion.1 = f32[8] fusion(...)", 60, 80),
        _ev("%sort = (f32[8]) sort(...)", 80, 95),
        _ev("%copy = f32[8] copy(...)", 95, 100),
        _ev("%fusion = f32[8] fusion(f32[8,14] %p)", 200, 260),
        _ev("%fusion = f32[8] fusion(...)", 400, 440)])])
    d1 = _plane("/device:TPU:1", [mods, ("XLA Ops", [
        _ev("%sort = (f32[8]) sort(...)", 0, 10)])])
    names = {"jit__step_impl": {
        "fusion": "jit(_step_impl)/jit(main)/sweep.decode/gather",
        "fusion.1": "jit(_step_impl)/jit(main)/sweep.op_terms/mul",
        "sort": "jit(_step_impl)/jit(main)/sweep.reduce/sort",
        "copy": "jit(_step_impl)/jit(main)/copy"},
        "jit_other": {"fusion": "jit(other)/mul"}}
    sc = spans.reduce_scopes(types.SimpleNamespace(planes=[d0, d1]),
                             (0, 450), names)
    assert sc == pytest.approx({"sweep.decode": 120e-9,
                                "sweep.op_terms": 20e-9,
                                "sweep.reduce": 15e-9, "unscoped": 45e-9})
    rec = {"window": {"kind": "sweep", "chunks": 2},
           "trace": {"scopes": sc}}
    assert spec.metric_reader("decode_device_ms.sweep").read(rec) == \
        pytest.approx(120e-9 / 2 * 1e3)


def test_scopes_from_the_trace_files_hlo(tmp_path):
    """The trace file's HLO names each instruction's scope; on the CPU the
    operations name their instruction and program in stats."""
    f = jax.jit(_scoped)
    x = jnp.ones(1 << 16)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("pb.window"):
            for _ in range(3):
                f(x).block_until_ready()
    names = xplane.load_op_names(str(tmp_path))
    mod = names["jit__scoped"]
    assert {spans.SCOPE.search(v).group(1) for v in mod.values()
            if spans.SCOPE.search(v)} == {"sweep.decode", "sweep.reduce"}
    pd = trace.load(str(tmp_path))
    win = trace.window_from_annotation(pd, "pb.window")
    sc = spans.reduce_scopes(pd, win, names, trace.cpu_op_lines)
    # the CPU profiler does not record every operation of every call
    assert set(sc) <= {"sweep.decode", "sweep.reduce", spans.UNSCOPED}
    assert sc.get("sweep.decode", 0) + sc.get("sweep.reduce", 0) > 0


def _scoped(x):
    with jax.named_scope("sweep.decode"):
        y = jnp.sin(x) * 2
    with jax.named_scope("sweep.reduce"):
        return jnp.sort(y)[:3].sum()


def test_wider_annotation_list_keeps_the_existing_keys(tmp_path):
    """A recorded profile reduced with ``("pb.",)`` and with the program's
    prefixes too: window, busy time and device operations are the same."""
    from repro.obs import NOOP
    f = jax.jit(lambda x: (jnp.sin(x) @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("pb.window"):
            for _ in range(3):
                with NOOP.span("sweep.chunk"):
                    f(x).block_until_ready()
                    with NOOP.span("sweep.insert", rows=2):
                        time.sleep(0.01)
    pd = trace.load(str(tmp_path))
    win = trace.window_from_annotation(pd, "pb.window")
    old = trace.reduce_profile(pd, win, ("pb.",),
                               op_lines=trace.cpu_op_lines)
    new = spans.add(trace.reduce_profile(pd, win, spans.PREFIXES,
                                         op_lines=trace.cpu_op_lines),
                    pd, win, str(tmp_path), spans.PREFIXES,
                    trace.cpu_op_lines)
    for k in ("window_s", "busy_s", "device_ops"):
        assert new[k] == old[k], k
    assert dict(new["idle_gaps"]).get("sweep.insert", 0) >= 0.02
    assert new["spans"]["sweep.chunk"]["count"] == 3
    assert new["spans"]["sweep.insert"]["stats"] == {"rows": 6}


@pytest.mark.parametrize("name", [m["name"] for m in PENDING])
def test_span_readers_report_nothing_without_spans(name):
    """A program that opens no spans (or an untraced run) reads as None."""
    reader = spec.metric_reader(name)
    for kind in ("sweep", "campaign"):
        w = {"kind": kind, "chunks": 4}
        assert reader.read({"window": w}) is None
        assert reader.read({"window": w, "trace": {
            "spans": {"pb.window": {"count": 1, "s": 1.0, "self_s": 1.0,
                                    "stats": {}}},
            "scopes": {"unscoped": 1.0}}}) is None


def test_pending_metrics_follow_the_benchmark_form():
    names = {m["name"] for m in BENCH_JSON["per_layer"]}
    layers = {m["layer"] for m in BENCH_JSON["per_layer"]}
    e2e = {m["name"]: m for m in BENCH_JSON["end_to_end"]}
    for m in PENDING:
        assert m["name"] not in names
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in layers and m["better"] == "lower"
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


# ----------------------------------------------------------- whole runs
@pytest.fixture
def cpu_cell(monkeypatch):
    """``trace_spans.measure`` on the CPU: no look for a chip, tiny
    sweeps."""
    monkeypatch.setattr(device, "require", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(device, "memory_peak", lambda chips: 0)
    real_unit = spec.unit

    def unit(kind, *a):
        u = real_unit(kind, *a)
        if kind == "sweep":
            u.STOP = 1 << 16
        return u

    monkeypatch.setattr(spec, "unit", unit)


@pytest.mark.parametrize("cell", CELLS)
def test_every_span_metric_on_a_traced_tiny_run(cpu_cell, cell):
    out = trace_spans.measure(cell, 2 ** 31 + 99, 0.4,
                              op_lines=trace.cpu_op_lines)
    assert out["correct"], out["checks"]
    want = {m["name"] for m in PENDING if cell in m["workloads"]}
    assert want and want <= set(out["metrics"])
    for name in want:
        assert math.isfinite(out["metrics"][name]["value"]), name
    cc = out["crosscheck"]
    assert cc["idle_by_program_span"] > 0
    if "sweep" in cell:
        assert cc["chunk_spans_over_wall"] == pytest.approx(1, abs=0.05)
        assert out["metrics"]["survivor_rows.sweep"]["value"] > 0
        assert set(out["scopes"]) >= {"sweep.decode", "sweep.reduce"}
    else:
        assert 0.5 < cc["eval_call_over_dispatch"] <= 1.0
        assert out["metrics"]["eval_calls_per_step.campaign"]["value"] >= 1
        assert 3 <= out["metrics"]["eval_leaves_per_call.campaign"][
            "value"] <= 15
    json.dumps(out)                               # the line is JSON
