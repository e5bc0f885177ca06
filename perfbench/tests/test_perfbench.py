"""Tests of the benchmark harness, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests

They cover the trace reduction, the rate and percentile arithmetic,
discovery of configurations, mixes, metrics and architecture families by
name (a whole run of a cell made of new files only), the reference's
operator tables, every cell's run at a tiny size, the control (the
reference in bfloat16, which must read as not correct), and faults planted
under the timed path (each must read as not correct).  The look for a chip
is skipped by replacing it in the test.
"""
import ast
import glob
import hashlib
import json
import os
import shutil
import subprocess
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
from harness import reference as R  # noqa: E402

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
    red = trace.reduce_profile(pd, win, op_lines=trace.cpu_op_lines)
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


#: a family the reference does not restate itself, restated by its own
#: file: the dense transformer layers, as the program builds them
FAMILY_FILE = """from harness.reference import attention, ffn


def layers(g, a, batch, q_len, kv_len, tp, decode):
    d, L = a["d_model"], a["n_layers"]
    M = batch * q_len
    g.vector(2 * M * d, 8.0, count=L)
    attention(g, batch, q_len, kv_len, d, a["n_heads"], a["n_kv_heads"],
              a["head_dim"], tp, L, decode)
    ffn(g, M, d, a["d_ff"], tp, a["gated_mlp"], L)
"""
#: the same file with one layer's op (the norms) left out
FAMILY_FILE_MISSING_OP = FAMILY_FILE.replace(
    "    g.vector(2 * M * d, 8.0, count=L)\n", "")

#: ``run.measure`` of a copied checkout in a child process: no look for a
#: chip, tiny sweeps, the CPU's op lines, and a program that opens a span
#: no harness file lists around each sweep
CHILD = """import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import run
from harness import device, spec, trace
from repro.obs import NOOP
from repro.perfmodel.sweep import SweepEngine
device.require = lambda chips: {"platform": "cpu", "kind": "cpu", "count": 1}
device.memory_peak = lambda chips: 0
real_unit = spec.unit


def unit(kind, *a):
    u = real_unit(kind, *a)
    u.STOP = %d
    return u


def sweep_run(self, *a, real=SweepEngine.run, **k):
    with NOOP.span("probe.sweep", calls=1):
        return real(self, *a, **k)


spec.unit, SweepEngine.run = unit, sweep_run
out = run.measure("new-cell", 2 ** 31 + 5, 0.3, True,
                  op_lines=trace.cpu_op_lines)
out.pop("trace")
print(json.dumps(out))
""" % STOP


def _new_checkout(tmp_path, family_file):
    """A checkout of the benchmark plus only new files and new entries: a
    configuration whose arch has a family the reference does not restate
    itself, that family's file, a mix, a cell, and per-layer metrics (one
    of them reading a program span no harness file lists)."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH_JSON))
    bd = root / "perfbench"
    zoo = spec.config(BENCH_JSON, "zoo9-portfolio")
    archs = {nm: dict(zoo["archs"][nm]) for nm in ("codeqwen1.5-7b",
                                                    "internvl2-2b")}
    archs["codeqwen1.5-7b"]["family"] = "dense-restated"
    (bd / "configs" / "codeqwen-restated.json").write_text(json.dumps(
        dict(zoo, name="codeqwen-restated", reduced=[], archs=archs)))
    (bd / "harness" / "families" / "dense_restated.py").write_text(
        family_file)
    (bd / "traffic" / "sweep-stall2.json").write_text(json.dumps(
        dict(spec.traffic("sweep-stall4"), stall_topk=2)))
    (bd / "metrics" / "sweeps_in_window.py").write_text(
        "def read(rec):\n    return rec['window']['units']\n")
    (bd / "metrics" / "probed_sweeps.py").write_text(
        "from harness.spans import program_spans\n\n\n"
        "def read(rec):\n"
        "    sp = program_spans(rec, 'sweep', 'probe.sweep')\n"
        "    return None if sp is None else sp['probe.sweep']['count']\n")
    bench["configs"].append({"name": "codeqwen-restated", "source": "x",
                             "file": "perfbench/configs/"
                                     "codeqwen-restated.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-cell",
                               "config": "codeqwen-restated",
                               "traffic": "sweep-stall2", "chips": 1,
                               "why": "x"})
    for name in ("sweeps_in_window", "probed_sweeps"):
        bench["per_layer"].append({"name": name, "unit": "n",
                                   "better": "higher",
                                   "source": "program_span",
                                   "layer": "sweep host loop",
                                   "moves": "sweep_designs_per_s",
                                   "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _measure_in_checkout(root) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(root / "perfbench"),
         os.path.join(ROOT, "src")],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_new_cell_is_picked_up_without_editing_a_file(tmp_path):
    root = _new_checkout(tmp_path, FAMILY_FILE)
    bd = root / "perfbench"
    b = spec.load_benchmark(str(root))
    c = spec.cell(b, "new-cell")
    assert spec.config(b, c["config"], str(root))["name"] == \
        "codeqwen-restated"
    assert spec.traffic(c["traffic"], str(bd))["stall_topk"] == 2
    names = [m["name"] for m in spec.cell_metrics(b, "new-cell", True)]
    assert {"sweeps_in_window", "probed_sweeps"} <= set(names)
    reader = spec.metric_reader("sweeps_in_window", str(bd))
    assert reader.read({"window": {"units": 7}}) == 7
    assert spec.family("dense-restated", str(bd)).layers
    out = _measure_in_checkout(root)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == set(names)
    assert out["metrics"]["probed_sweeps"]["value"] == out["attempted"]


def test_a_family_file_that_leaves_out_an_op_reads_as_not_correct(
        tmp_path):
    out = _measure_in_checkout(_new_checkout(tmp_path,
                                             FAMILY_FILE_MISSING_OP))
    assert not out["correct"]
    assert out["checks"]["objective_rel_err"]["value"] > \
        out["checks"]["objective_rel_err"]["limit"]


def test_an_unknown_family_with_no_file_raises():
    a = dict(spec.config(BENCH_JSON, "zoo9-portfolio")["archs"]
             ["codeqwen1.5-7b"], family="no-such-family")
    with pytest.raises(FileNotFoundError, match=r"families/no_such_family"):
        R.arch_graph(a, 8, 2048, 8, False, 2048)


#: sha256 of every scenario's prefill and decode operator tables, taken on
#: the tree before family files existed: the reference's graphs of the
#: configurations it restates itself must stay byte-identical
TABLE_SHA256 = {
    "gpt3-pair":
        "cae0c95fd1cd1302bf9ad7a0ec0d2606e8f256f18fc727e141aca620de909d24",
    "zoo9-portfolio":
        "d288ce096bba1dc15f56c41e6704582d80ffd7d7749529ab2c5a2357dbd99fa0",
}


@pytest.mark.parametrize("config", sorted(TABLE_SHA256))
def test_reference_operator_tables_are_unchanged(config):
    h = hashlib.sha256()
    for name, pre, dec in R.scenarios(spec.config(BENCH_JSON, config)):
        h.update(name.encode())
        for g in (pre, dec):
            t = g.table()
            for k in R.Graph.FIELDS:
                h.update(k.encode())
                h.update(t[k].tobytes())
    assert h.hexdigest() == TABLE_SHA256[config]


def _imports_the_program(source: str) -> bool:
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        else:
            continue
        if any(m == "repro" or m.startswith("repro.") for m in mods):
            return True
    return False


def test_family_files_import_nothing_of_the_program():
    files = glob.glob(os.path.join(BENCH, "harness", "families", "*.py"))
    assert files
    for f in files:
        with open(f) as fh:
            assert not _imports_the_program(fh.read()), f
    assert not _imports_the_program(FAMILY_FILE)
    assert _imports_the_program("from repro.perfmodel import workload\n")
    assert _imports_the_program("import repro.configs as c\n")


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
def test_traced_run_reports_per_layer_metrics(cpu_run, cell):
    cache = jax.config.jax_enable_compilation_cache
    out = run.measure(cell, 7, 0.3, True, op_lines=trace.cpu_op_lines)
    assert jax.config.jax_enable_compilation_cache == cache
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

