"""Tests of the ``dsv3-sweep`` and ``paper-sweep-4chip`` cells, on the CPU
at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests

``dsv3-sweep`` reads correct on a shrunk space, and two faults planted in
the program's DeepSeek-V3 graph read as not correct: the latent cache
divided over the tensor-parallel ranks (it is whole on each), and one
expert's weights a rank in place of the distinct experts its tokens hit.
``paper-sweep-4chip`` runs on four virtual CPU devices in a child process
(the device count is fixed before JAX starts): the engine's mesh spans
the cell's four devices and the run reads correct; keeping only the first
device's shard of each chunk reads as not correct.  The new per-layer
readers report nothing on records without the program's spans.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
from harness import device, spec, trace  # noqa: E402

STOP = 1 << 16          # ids per sweep in these tests
NEW_METRICS = ("op_rows.sweep", "op_terms_ns_per_row.sweep")


@pytest.fixture
def cpu_run(monkeypatch):
    """``run.measure`` on the CPU: no look for a chip, tiny sweeps."""
    monkeypatch.setattr(device, "require", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(device, "memory_peak", lambda chips: 0)
    real_unit = spec.unit

    def unit(kind, *a):
        u = real_unit(kind, *a)
        if kind == "sweep":
            u.STOP = STOP
        return u

    monkeypatch.setattr(spec, "unit", unit)


def test_dsv3_sweep_is_correct_and_reports_its_metrics(cpu_run):
    out = run.measure("dsv3-sweep", 2 ** 31 + 4321, 0.5, True,
                      op_lines=trace.cpu_op_lines)
    assert out["correct"], out["checks"]
    assert out["metrics"]["op_rows.sweep"]["value"] == 58
    assert out["metrics"]["op_terms_ns_per_row.sweep"]["value"] >= 0


def _latent_cache_divided(wl):
    for o in wl.ops:
        if o.name == "mla.latent_read":
            o.bytes /= wl.tp


def _one_expert_per_rank(wl, moe_layers=58):
    """All of a rank's routed rows through one expert's weights, as the
    ``moe`` family's block counts them."""
    for o in wl.ops:
        if o.name in ("moe.exp_up", "moe.exp_down"):
            distinct = o.count / moe_layers
            o.m, o.flops = o.m * distinct, o.flops * distinct
            o.bytes = (o.m * o.k + o.k * o.n + o.m * o.n) * 2
            o.count = moe_layers


@pytest.mark.parametrize("fault", [_latent_cache_divided,
                                   _one_expert_per_rank])
def test_dsv3_graph_faults_read_as_not_correct(cpu_run, monkeypatch, fault):
    from repro.perfmodel import workload as W
    real = W.from_arch

    def from_arch(cfg, *a, **k):
        wl = real(cfg, *a, **k)
        fault(wl)
        return wl

    monkeypatch.setattr(W, "from_arch", from_arch)
    out = run.measure("dsv3-sweep", 2 ** 31 + 17, 0.3, False)
    assert not out["correct"], out["checks"]
    assert out["checks"]["objective_rel_err"]["value"] > \
        out["checks"]["objective_rel_err"]["limit"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_report_nothing_without_spans(name):
    reader = spec.metric_reader(name)
    for kind in ("sweep", "campaign"):
        w = {"kind": kind, "chunks": 4, "work": 100}
        assert reader.read({"window": w}) is None
        assert reader.read({"window": w, "trace": {
            "busy_s": {"/device:TPU:0": 1.0},
            "spans": {"pb.window": {"count": 1, "s": 1.0, "self_s": 1.0,
                                    "stats": {}}},
            "scopes": {"sweep.op_terms": 1.0}}}) is None


def test_op_terms_per_row_counts_every_busy_chip():
    rec = {"window": {"kind": "sweep", "chunks": 10, "work": 1000},
           "trace": {"busy_s": {"a": 1.0, "b": 0.9, "c": 0.9, "d": 0.0},
                     "scopes": {"sweep.op_terms": 2e-6},
                     "spans": {"sweep.run": {"count": 2, "s": 1.0,
                                             "self_s": 0.0,
                                             "stats": {"op_rows": 40}}}}}
    assert spec.metric_reader("op_rows.sweep").read(rec) == 20
    ns = spec.metric_reader("op_terms_ns_per_row.sweep").read(rec)
    assert ns == pytest.approx(2e-6 * 3 / (1000 * 20) * 1e9)


#: ``run.measure`` of ``paper-sweep-4chip`` on four virtual CPU devices,
#: optionally with each chunk cut to its first device's shard
CHILD = """import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
import run
from harness import device, spec
device.require = lambda chips: {"platform": "cpu", "kind": "cpu",
                                "count": len(jax.devices())}
device.memory_peak = lambda chips: 0
real_unit = spec.unit
FAULT = sys.argv[3] == "first_shard"
MESH = []


def unit(kind, *a):
    u = real_unit(kind, *a)
    u.STOP = %d
    real_setup = u.setup

    def setup(cfg, mix, seed):
        state = real_setup(cfg, mix, seed)
        eng = state["engine"]
        MESH.append(eng._sharding.mesh.devices.size)
        if FAULT:
            step, shard = eng._step, eng.chunk_size // len(jax.devices())

            def first_shard(carry, start, stop, filt):
                return step(carry, start, min(int(stop), int(start) + shard),
                            filt)
            eng._step = first_shard
        return state
    u.setup = setup
    return u


spec.unit = unit
out = run.measure("paper-sweep-4chip", 2 ** 31 + 4, 0.3, False)
print(json.dumps({"correct": out["correct"], "checks": out["checks"],
                  "mesh": MESH, "devices": len(jax.devices())}))
""" % STOP


def _four_device_run(mode: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, BENCH, os.path.join(ROOT, "src"),
         mode], capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_paper_sweep_4chip_on_four_virtual_devices():
    cell = spec.cell(spec.load_benchmark(), "paper-sweep-4chip")
    assert cell["chips"] == 4 and spec.traffic(cell["traffic"])["shard"]
    out = _four_device_run("clean")
    assert out["devices"] == 4 and out["mesh"] == [4]
    assert out["correct"], out["checks"]


def test_first_chips_shard_only_reads_as_not_correct():
    out = _four_device_run("first_shard")
    assert not out["correct"], out["checks"]
