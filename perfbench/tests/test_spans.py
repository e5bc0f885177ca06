"""Tests of the span and scope reduction and the span metrics, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests

They cover ``harness.spans`` on a synthetic nested trace, the one name
rule for spans and scopes (JAX's own events stay out, a name no harness
file lists gets in), that the rule gives on a recorded profile what the
fixed lists of names gave, the span readers on records without spans (a
program that opens none), and every span metric on a traced tiny run of
each cell through ``trace_spans.py``.
"""
import json
import math
import os
import re
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
#: the per-layer metrics whose readers read the span and scope reduction
SPAN_METRICS = [m for m in BENCH_JSON["per_layer"]
                if {"program_spans", "scope_ms_per_chunk"}
                & set(vars(spec.metric_reader(m["name"])))]
#: the names the reduction took before it had one rule: fixed prefixes of
#: host annotations, and ``sweep.*`` scopes alone
OLD_PREFIXES = ("pb.", "sweep.", "eval.", "dse.")
OLD_SCOPE = re.compile(r"(?:^|/)(sweep\.[A-Za-z_]+)(?=/|$)")


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
            _ev("other", 160, 200),               # not a name: not nested
            _ev("fusion.3", 170, 180),            # JAX's own events
            _ev("$core.py:12 bind", 182, 184),
            _ev("PjitFunction(step)", 185, 190),
            _ev("sweep.insert", 300, 450, rows=7, tag="x"),
            _ev("sweep.chunk", 600, 900),
            _ev("sweep.insert", 700, 750, rows=5),
            _ev("sweep.chunk", 1200, 1300)]),     # outside the window
        ("worker", [_ev("sweep.insert", 800, 850, rows=1),
                    _ev("mla.absorb", 860, 870, heads=4)])])
    sp = spans.reduce_spans(types.SimpleNamespace(planes=[host]), (0, 1000))
    assert set(sp) == {"pb.window", "sweep.chunk", "sweep.filter",
                       "sweep.insert", "mla.absorb"}
    assert sp["mla.absorb"]["stats"] == {"heads": 4}
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


def test_scopes_nest_and_follow_the_name_rule():
    """An operation counts under every scope of its path, a scope no
    harness file lists included; JAX's own path parts are no scopes."""
    mods = ("XLA Modules", [_ev("jit_step(1)", 0, 100)])
    d0 = _plane("/device:TPU:0", [mods, ("XLA Ops", [
        _ev("%a = f32[8] fusion(...)", 0, 30),
        _ev("%b = f32[8] fusion(...)", 30, 40),
        _ev("%c = f32[8] sort(...)", 40, 100)])])
    names = {"jit_step": {
        "a": "jit(step)/jit(main)/sweep.op_terms/mla.absorb/dot_general",
        "b": "jit(step)/jit(main)/sweep.op_terms/mul",
        "c": "jit(step)/jit(main)/jit(jax.numpy.sort)/sort"}}
    sc = spans.reduce_scopes(types.SimpleNamespace(planes=[d0]), (0, 100),
                             names)
    assert sc == pytest.approx({"sweep.op_terms": 40e-9,
                                "mla.absorb": 30e-9, "unscoped": 60e-9})


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
    assert {c for v in mod.values() for c in v.split("/")
            if trace.is_name(c)} == {"sweep.decode", "sweep.reduce"}
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


def test_wider_annotation_list_keeps_the_existing_keys(tmp_path,
                                                      monkeypatch):
    """A recorded profile reduced by the one name rule and by the fixed
    lists it replaced: window, busy time, device operations, spans and
    scopes are the same; idle gaps now name the program's spans too."""
    from repro.obs import NOOP
    f = jax.jit(_scoped)
    x = jnp.ones(1 << 16)
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

    def reduce():
        return spans.add(trace.reduce_profile(pd, win,
                                              op_lines=trace.cpu_op_lines),
                         pd, win, str(tmp_path), trace.cpu_op_lines)

    new = reduce()
    with monkeypatch.context() as m:
        m.setattr(trace, "is_name", lambda n: n.startswith(OLD_PREFIXES))
        m.setattr(spans, "_scopes", lambda op: [
            OLD_SCOPE.search(op).group(1) if OLD_SCOPE.search(op)
            else spans.UNSCOPED])
        old = reduce()
        m.setattr(trace, "is_name", lambda n: n.startswith("pb."))
        harness_only = trace.reduce_profile(pd, win,
                                            op_lines=trace.cpu_op_lines)
    for k in ("window_s", "busy_s", "device_ops", "idle_gaps", "spans",
              "scopes"):
        assert new[k] == old[k], k
    for k in ("window_s", "busy_s", "device_ops"):
        assert new[k] == harness_only[k], k
    assert dict(new["idle_gaps"]).get("sweep.insert", 0) >= 0.02
    assert "sweep.insert" not in dict(harness_only["idle_gaps"])
    assert new["spans"]["sweep.chunk"]["count"] == 3
    assert new["spans"]["sweep.insert"]["stats"] == {"rows": 6}
    # the CPU profiler does not record every operation of every call
    assert {"sweep.decode", "sweep.reduce"} & set(new["scopes"])


@pytest.mark.parametrize("name", [m["name"] for m in SPAN_METRICS])
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
    """The span metrics sit in ``BENCHMARK.json`` beside the others: each
    under a layer the other metrics name, for cells that report the
    end-to-end metric it moves, with its reader."""
    others = [m for m in BENCH_JSON["per_layer"] if m not in SPAN_METRICS]
    layers = {m["layer"] for m in others}
    e2e = {m["name"]: m for m in BENCH_JSON["end_to_end"]}
    assert len(SPAN_METRICS) == 15
    for m in SPAN_METRICS:
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
    want = {m["name"] for m in SPAN_METRICS if cell in m["workloads"]}
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
        assert out["metrics"]["eval_copies_per_call.campaign"]["value"] == 1
        assert 3 <= out["metrics"]["eval_leaves_per_call.campaign"][
            "value"] <= 15
    json.dumps(out)                               # the line is JSON
