"""The evaluator against the benchmark's independent float64 reference.

Every benchmark configuration at both analytical tiers and every detail
level: the program's evaluator (built as the benchmark builds it,
``perfbench/harness/program.py``) scores 64 sampled designs, and the
reference of ``perfbench/harness/reference.py`` scores the same designs in
float64.  The limits are the benchmark's own (``harness.compare.TIE``):

* area and every latency within a relative error of 1e-5;
* at "ppa", every per-op time within 1e-5 of the workload's latency;
* at "stalls", ``compare_reports``' ``report_rel_err`` (per-op times and
  per-class stall sums against the workload's latency) within 1e-5, and
  no per-op class that differs from the reference's without a near tie.

The harness is used read-only.
"""
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import compare, program, spec  # noqa: E402
from harness import reference as R  # noqa: E402
from repro.perfmodel import EvalRequest  # noqa: E402
from repro.perfmodel.designspace import SPACE  # noqa: E402

CONFIGS = ("gpt3-pair", "zoo9-portfolio", "deepseek-v3-tp8-32k")
N = 64


@pytest.fixture(scope="module")
def case(config, tier):
    """The program's evaluator of (config, tier), the sampled designs, and
    the reference's full reports of them, made once per pair."""
    cfg = spec.config(spec.load_benchmark(ROOT), config, ROOT)
    ev = program.evaluator(cfg, tier)
    idx = SPACE.sample(np.random.default_rng(19), N)
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        model = R.Model(cfg, tier, "float64")
        ref = model.reports(idx, ev.workloads)
    return ev, idx, model, ref


def _call(rep, idx, fields):
    return dict(idx=idx, names=rep.workloads, area=rep.area,
                **{f: getattr(rep, f) for f in fields})


@pytest.mark.parametrize("detail", ["objectives", "ppa", "stalls"])
@pytest.mark.parametrize("tier", ["proxy", "target"], scope="module")
@pytest.mark.parametrize("config", CONFIGS, scope="module")
def test_evaluator_matches_reference(case, detail):
    ev, idx, model, ref = case
    rep = ev.evaluate(EvalRequest(idx, detail=detail))
    assert rep.workloads == ev.workloads and rep.n == N
    fields = ("latency",)
    if detail == "stalls":
        fields += ("op_time", "stall", "op_class")
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        worst, _ = compare.compare_reports(model, [_call(rep, idx, fields)])
    assert worst["report_rel_err"] <= compare.TIE, worst
    assert worst["op_class_unexplained"] == 0, worst
    if detail == "ppa":
        for nm in rep.workloads:
            r = ref["per"][nm]
            err = (np.abs(rep.op_time[nm] - r["op_time"])
                   / r["latency"][:, None])
            assert err.max() <= compare.TIE, (nm, float(err.max()))
    else:
        assert (rep.op_time is None) == (detail == "objectives")
