"""Analytical accelerator PPA models behind ONE service boundary (the
paper's "simulation environment").

Every consumer — the Lumina DSE loop, QualE/QuanE acquisition, the five
black-box baselines, the full-space sweep, the DSE Benchmark generator and
every ``benchmarks/*`` module — evaluates designs through the **unified
tiered Evaluator API** of :mod:`repro.perfmodel.evaluator`:

* :class:`~repro.perfmodel.evaluator.EvalRequest` — design-index batch +
  workload subset + detail level (``objectives`` | ``ppa`` | ``stalls``);
* :class:`~repro.perfmodel.evaluator.PPAReport` — the structured result
  (per-workload latencies, area, stall attribution, per-op breakdown);
* :func:`~repro.perfmodel.evaluator.get_evaluator` — the paper's GPT-3
  workload evaluator per fidelity **tier**:

  =========  ==========================================================
  ``proxy``   fast roofline models (paper Fig. 1/4/5) — acquisition tier
  ``target``  LLMCompass-calibrated models (paper §5.3, Table 4) — the
              budgeted high-fidelity tier
  ``oracle``  the exhaustive 4.7M-point sweep front of the proxy tier
              (or ``oracle_base="target"``)
              (:class:`~repro.perfmodel.evaluator.OracleEvaluator`) for
              exact regret / PHV normalization
  =========  ==========================================================

* **workload suites** (``get_evaluator(suite="paper" | "zoo")``): the
  GPT-3 pair, or every assigned architecture config as a
  :class:`~repro.perfmodel.workload.Scenario` — all workloads stacked
  into one deduped op union (:class:`~repro.perfmodel.workload.
  WorkloadStack`) so a single dispatch (and a single sweep pass) scores
  the whole zoo.

There is one evaluation path: the tier picks the model class, and the
evaluator's traced path is *fused*: one jitted dispatch decodes the
batch, derives hardware once, and evaluates every workload (TTFT + TPOT +
stall attribution) in one op-term pass over the deduped op union.  The
request is batched end to end — K parallel campaigns' candidates ride one
dispatch (see
:class:`~repro.core.campaign.CampaignRunner`).  The pre-PR-2 per-model
shims (``eval_ppa`` / ``objectives`` / pair signatures) have been removed.

Supporting pieces:

* :mod:`repro.perfmodel.roofline`   — roofline op-term model (shared core).
* :mod:`repro.perfmodel.compass`    — LLMCompass-style per-op-overhead tier.
* :mod:`repro.perfmodel.designspace` — the 4.7M-point design space (Table 1).
* :mod:`repro.perfmodel.hardware`    — design point -> derived hardware spec
  (throughputs, bandwidths, area), calibrated against NVIDIA A100.
* :mod:`repro.perfmodel.workload`    — operator graphs (GPT-3 layer and every
  assigned architecture) for TTFT / TPOT evaluation.
* :mod:`repro.perfmodel.critical_path` — per-op stall attribution (the
  paper's critical-path extension of LLMCompass).
* :mod:`repro.perfmodel.sweep`       — streaming full-space sweep engine
  (the oracle tier's substrate; also emits per-stall-class seed designs;
  ``run(workers=N)`` shards the id range with an exact host merge).
* :mod:`repro.distributed`           — the service layer above this one:
  :class:`~repro.distributed.sharded.ShardedEvaluator` fans one request
  across worker pools (``get_evaluator(..., workers=N)``) and
  :class:`~repro.distributed.service.EvalService` coalesces concurrent
  clients into one fused dispatch per tick.
"""

from repro.perfmodel.designspace import DesignSpace, A100_REFERENCE
from repro.perfmodel.hardware import derive_hardware, area_mm2
from repro.perfmodel.workload import (Workload, Op, WorkloadStack, Scenario,
                                      gpt3_layer_prefill, gpt3_layer_decode,
                                      from_arch, paper_suite, zoo_suite)
from repro.perfmodel.roofline import RooflineModel, stacked_workload_batches
from repro.perfmodel.compass import CompassModel
from repro.perfmodel.critical_path import attribute_stalls, STALL_CLASSES
from repro.perfmodel.evaluator import (Evaluator, EvalRequest, PPAReport,
                                       ModelEvaluator, OracleEvaluator,
                                       RowCache, get_evaluator,
                                       make_evaluator, as_evaluator,
                                       pair_view, TIERS, DETAILS, SUITES)
from repro.perfmodel.sweep import SweepEngine, SweepResult

__all__ = [
    "DesignSpace", "A100_REFERENCE", "derive_hardware", "area_mm2",
    "Workload", "Op", "WorkloadStack", "Scenario",
    "gpt3_layer_prefill", "gpt3_layer_decode", "from_arch",
    "paper_suite", "zoo_suite",
    "RooflineModel", "CompassModel", "stacked_workload_batches",
    "attribute_stalls", "STALL_CLASSES",
    "Evaluator", "EvalRequest", "PPAReport", "ModelEvaluator",
    "OracleEvaluator", "RowCache", "get_evaluator", "make_evaluator",
    "as_evaluator", "pair_view", "TIERS", "DETAILS", "SUITES",
    "SweepEngine", "SweepResult",
]
