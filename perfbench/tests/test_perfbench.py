"""Tests of the benchmark harness, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests

They cover the trace reduction, the rate and percentile arithmetic,
discovery of configurations, mixes and metrics by name, every cell's run at
a tiny size, the control (the reference in bfloat16, which must read as not
correct), and faults planted under the timed path (each must read as not
correct).  The look for a chip is skipped by replacing it in the test.
"""
import json
import os
import shutil
import sys
import time
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import run  # noqa: E402
from harness import device, spec, trace  # noqa: E402

STOP = 1 << 16          # ids per sweep in these tests


@pytest.fixture
def cpu_run(monkeypatch):
    """``run.measure`` on the CPU: no look for a chip, tiny sweeps."""
    monkeypatch.setattr(device, "require", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(device, "memory_peak", lambda chips: 0)
    units = {}
    real_unit = spec.unit

    def unit(kind, *a):
        if kind not in units:
            units[kind] = real_unit(kind, *a)
            if kind == "sweep":
                units[kind].STOP = STOP
        return units[kind]

    monkeypatch.setattr(spec, "unit", unit)
    return types.SimpleNamespace(unit=unit, units=units)


BENCH_JSON = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH_JSON["workloads"] if w["chips"] == 1]


# ------------------------------------------------------------------ trace
def test_union_length_merges_overlaps():
    total, merged = trace.union_length([(5, 9), (0, 2), (1, 3), (9, 10)])
    assert total == 3 + 5
    assert merged == [(0, 3), (5, 10)]


def test_trace_reduction_on_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: (jnp.sin(x) @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    from jax.profiler import TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("pb.window"):
            for _ in range(4):
                with TraceAnnotation("pb.unit"):
                    f(x).block_until_ready()
                with TraceAnnotation("pb.host"):
                    time.sleep(0.02)
    pd = trace.load(str(tmp_path))
    win = trace.window_from_annotation(pd, "pb.window")
    red = trace.reduce_profile(pd, win, ("pb.",), op_lines=trace.cpu_op_lines)
    assert red["window_s"] >= 0.08
    (busy,) = red["busy_s"].values()
    assert 0 < busy < red["window_s"]
    labels = dict(red["idle_gaps"])
    assert labels.get("pb.host", 0) >= 0.06      # the sleeps are idle
    assert sum(labels.values()) == pytest.approx(red["window_s"] - busy)
    assert red["device_ops"] and red["device_ops"][0][1] > 0
    rec = {"window": {"kind": "sweep", "chunks": 4}, "trace": red}
    share = spec.metric_reader("idle_share.sweep").read(rec)
    assert share == pytest.approx((1 - busy / red["window_s"]) * 100)
    per = spec.metric_reader("step_device_ms.sweep").read(rec)
    assert per == pytest.approx(busy / 4 * 1e3)


# ------------------------------------------------------------ arithmetic
def test_sweep_rate_counts_the_sweep_that_crosses_the_window():
    sweep = spec.unit("sweep")

    class Engine:
        def __init__(self):
            self.calls = 0

        def telemetry(self):
            return {"chunks": self.calls, "chunk_s": {"count": self.calls,
                                                      "sum": 0.3 * self.calls}}

        def run(self, start, stop):
            self.calls += 1
            time.sleep(0.3)
            return types.SimpleNamespace(n_evaluated=1000)

    from contextlib import nullcontext
    w = sweep.window({"engine": Engine()}, 0.5, lambda n: nullcontext())
    assert w["units"] == 2 and w["work"] == 2000
    assert w["elapsed_s"] >= 0.6
    rate = spec.metric_reader("sweep_designs_per_s").read({"window": w})
    assert rate == pytest.approx(2000 / w["elapsed_s"])
    assert rate < 2000 / 0.5
    wall = spec.metric_reader("chunk_wall_ms.sweep").read({"window": w})
    assert wall == pytest.approx(300.0)


def test_p95_is_over_every_step():
    steps = list(np.linspace(0.001, 0.1, 100))
    w = {"kind": "campaign", "step_s": steps, "step_eval_s": [0.0] * 100,
         "units": 100, "elapsed_s": 5.0, "dispatch_calls": 10,
         "dispatch_s": 0.05}
    read = lambda n: spec.metric_reader(n).read({"window": w})  # noqa: E731
    assert read("campaign_step_p95_ms") == pytest.approx(
        np.percentile(steps, 95) * 1e3)
    assert read("campaign_steps_per_s") == pytest.approx(20.0)
    assert read("dispatch_ms.campaign") == pytest.approx(5.0)
    assert read("campaign_host_ms") == pytest.approx(np.mean(steps) * 1e3)


# --------------------------------------------------------------- discovery
def test_cells_metrics_and_files_are_found_by_name():
    for w in BENCH_JSON["workloads"]:
        assert spec.config(BENCH_JSON, w["config"])["suite"]
        mix = spec.traffic(w["traffic"])
        assert spec.unit(mix["kind"])
        for traced in (False, True):
            for m in spec.cell_metrics(BENCH_JSON, w["name"], traced):
                assert callable(spec.metric_reader(m["name"]).read)


def test_a_new_cell_is_picked_up_without_editing_a_file(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH_JSON))
    bd = root / "perfbench"
    (bd / "configs" / "gpt3-small-batch.json").write_text(json.dumps(
        dict(spec.config(BENCH_JSON, "gpt3-pair"), name="gpt3-small-batch")))
    (bd / "traffic" / "sweep-stall2.json").write_text(json.dumps(
        dict(spec.traffic("sweep-stall8"), stall_topk=2)))
    (bd / "metrics" / "sweeps_in_window.py").write_text(
        "def read(rec):\n    return rec['window']['units']\n")
    bench["configs"].append({"name": "gpt3-small-batch", "source": "x",
                             "file": "perfbench/configs/gpt3-small-batch.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-cell",
                               "config": "gpt3-small-batch",
                               "traffic": "sweep-stall2", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "sweeps_in_window", "unit": "n",
                               "better": "higher", "source": "host_clock",
                               "layer": "sweep host loop",
                               "moves": "sweep_designs_per_s",
                               "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    b = spec.load_benchmark(str(root))
    c = spec.cell(b, "new-cell")
    assert spec.config(b, c["config"], str(root))["name"] == "gpt3-small-batch"
    assert spec.traffic(c["traffic"], str(bd))["stall_topk"] == 2
    names = [m["name"] for m in spec.cell_metrics(b, "new-cell", True)]
    assert "sweeps_in_window" in names
    reader = spec.metric_reader("sweeps_in_window", str(bd))
    assert reader.read({"window": {"units": 7}}) == 7


def test_no_tpu_exits_nonzero_with_no_result(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1", "--trace", "0"]) == 2
    assert "{" not in capsys.readouterr().out


# ----------------------------------------------------------- whole runs
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct_at_a_tiny_size(cpu_run, cell):
    out = run.measure(cell, 2 ** 31 + 12345, 0.5, False)
    assert out["correct"], out["checks"]
    assert out["compiles_in_window"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in spec.cell_metrics(BENCH_JSON, cell, False)}
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cpu_run, cell, monkeypatch):
    real = trace.reduce_profile
    monkeypatch.setattr(trace, "reduce_profile", lambda pd, w, a, **k: real(
        pd, w, a, op_lines=trace.cpu_op_lines))
    out = run.measure(cell, 7, 0.3, True)
    assert out["correct"], out["checks"]
    want = {m["name"] for m in spec.cell_metrics(BENCH_JSON, cell, True)}
    assert set(out["metrics"]) == want
    assert out["device"]["busy_s"] > 0
    assert len(out["breakdown"]["device_ops"]) <= 10


# ------------------------------------------------------------- control
def _limits_broken(numbers, limits):
    return [k for k, v in numbers.items() if v > limits[k]]


@pytest.mark.parametrize("cell", [c for c in CELLS
                                  if spec.traffic(spec.cell(BENCH_JSON, c)
                                                  ["traffic"])["kind"]
                                  == "sweep"])
def test_sweep_control_in_bfloat16_is_not_correct(cpu_run, cell):
    c = spec.cell(BENCH_JSON, cell)
    mix = spec.traffic(c["traffic"])
    unit = cpu_run.unit("sweep")
    nums = unit.control(spec.config(BENCH_JSON, c["config"]), mix)
    assert _limits_broken(nums, mix["limits"])


def test_campaign_control_in_bfloat16_is_not_correct(cpu_run):
    cell = spec.cell(BENCH_JSON, "paper-campaign")
    cfg = spec.config(BENCH_JSON, cell["config"])
    mix = spec.traffic(cell["traffic"])
    unit = cpu_run.unit("campaign")
    state = unit.setup(cfg, mix, 3)
    from contextlib import nullcontext
    unit.window(state, 0.2, lambda n: nullcontext())
    nums = unit.control(state, cfg, mix)
    assert _limits_broken(nums, mix["limits"])


# --------------------------------------------------------------- faults
def _sweep_fault(kind):
    """Wrap an engine's jitted chunk step with a planted fault."""
    def plant(eng):
        step, chunk = eng._step, eng.chunk_size

        def faulty(carry, start, stop, filt):
            if kind == "state_unchanged":
                fresh = jax.tree_util.tree_map(jnp.copy, carry)
                _, surv, ys, ids = step(fresh, start, stop, filt)
                return carry, surv, ys, ids
            if kind == "half_batch":
                end = jnp.minimum(stop, start + chunk)
                return step(carry, start, start + (end - start) // 2, filt)
            carry, surv, ys, ids = step(carry, start, stop, filt)
            # an answer altered where it is produced: every surviving
            # design's first objective off by one part in a thousand
            return carry, surv, ys.at[..., 0].multiply(1.001), ids
        eng._step = faulty
    return plant


class _FaultyEvaluator:
    def __init__(self, inner, kind):
        self._inner, self._kind, self._last = inner, kind, {}

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def evaluate(self, request):
        rep = self._inner.evaluate(request)
        if self._kind == "state_unchanged":
            prev = self._last.get((request.detail, rep.n))
            self._last[(request.detail, rep.n)] = rep
            return prev if prev is not None else rep
        lat = {k: v.copy() for k, v in rep.latency.items()}
        for k in lat:
            if self._kind == "half_batch" and rep.n > 1:
                h = rep.n // 2
                lat[k][h:] = lat[k][:rep.n - h].mean()
            elif self._kind == "answer_altered":
                lat[k][0] *= 1.001
        rep.latency = lat
        return rep

    def objectives(self, idx):
        from repro.perfmodel.evaluator import EvalRequest
        return self.evaluate(EvalRequest(idx, "objectives")).objectives


FAULTS = ("state_unchanged", "half_batch", "answer_altered")


@pytest.mark.parametrize("kind", FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_reads_as_not_correct(cpu_run, monkeypatch, cell,
                                            kind):
    c = spec.cell(BENCH_JSON, cell)
    mix = spec.traffic(c["traffic"])
    unit = cpu_run.unit(mix["kind"])
    if mix["kind"] == "sweep":
        real_setup = unit.setup

        def setup(cfg, mix, seed):
            state = real_setup(cfg, mix, seed)
            _sweep_fault(kind)(state["engine"])
            return state
        monkeypatch.setattr(unit, "setup", setup)
    else:
        from harness import program
        real_eval = program.evaluator
        monkeypatch.setattr(program, "evaluator", lambda cfg, tier:
                            _FaultyEvaluator(real_eval(cfg, tier), kind))
    out = run.measure(cell, 11, 0.3, False)
    assert not out["correct"], (kind, out["checks"])

