"""Unified tiered Evaluator API: ONE PPA contract for every consumer.

The paper's whole pipeline — QualE/QuanE acquisition, bottleneck analysis,
the 20-step DSE loop, the Table 2/3 baselines and the DSE Benchmark — hangs
off a single notion: *evaluate a batch of designs under a workload set at
some fidelity tier*.  This module is that service boundary:

* :class:`EvalRequest`  — design-index batch + workload subset + detail
  level (``objectives`` | ``ppa`` | ``stalls``);
* :class:`PPAReport`    — the structured result pytree (per-workload
  latencies, area, stall attribution, per-op breakdown) with
  :meth:`PPAReport.stall_report` bridging to the Strategy Engine;
* :class:`ModelEvaluator` — the analytical-model implementation with a
  **fused multi-workload traced path**: TTFT, TPOT (and stall attribution)
  are evaluated in ONE jitted dispatch per step — the space decode and
  hardware derivation run once per batch and every workload's op terms are
  computed inside the same XLA executable, instead of two-to-four separate
  model calls.  Compiled executables live in the same workload-keyed jit
  cache the models use, so every evaluator in a process shares them.
* **tiers**: ``proxy`` (cheap roofline acquisition tier), ``target``
  (LLMCompass-calibrated budgeted tier) and ``oracle`` — the exhaustive
  :class:`~repro.perfmodel.sweep.SweepEngine` front wrapped as
  :class:`OracleEvaluator`, serving exact regret / PHV normalization.

The request shape is batched end to end: ``EvalRequest.idx`` may carry any
number of designs — K parallel campaigns' candidates ride ONE fused
dispatch and :meth:`PPAReport.stall_report` extracts any row's
critical-path view (the multi-design path behind
:class:`~repro.core.campaign.CampaignRunner`).

The pre-PR-2 per-model shims (``eval_ppa`` / ``objectives`` / the
``(ttft_model, tpot_model)`` pair threading) are gone after their
one-release deprecation window.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Callable, Dict, Mapping, Optional, Protocol, Tuple

import jax.numpy as jnp
import numpy as np

from repro.obs.trace import NOOP
from repro.perfmodel.compass import CompassModel
from repro.perfmodel.critical_path import StallReport, build_report
from repro.perfmodel.designspace import DesignSpace, SPACE
from repro.perfmodel.hardware import derive_hardware
from repro.perfmodel.roofline import (PackedFn, RooflineModel, _JIT_CACHE,
                                      _batch_bucket, _bucketed_call,
                                      _space_key,
                                      _workload_fingerprint,
                                      stacked_workload_batches)
from repro.perfmodel.workload import Scenario, WorkloadStack

DETAILS = ("objectives", "ppa", "stalls")
TIERS = ("proxy", "target", "oracle")
SUITES = ("paper", "zoo")

_DETAIL_LEVEL = {name: i for i, name in enumerate(DETAILS)}


# ---------------------------------------------------------------------------
# request / report
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EvalRequest:
    """One evaluation call: design-index batch, workload subset, detail.

    idx:       (n, n_params) int32 choice-index vectors (or a single vector).
    detail:    "objectives" (latency per workload + area, lean traced path),
               "ppa" (adds the per-op time breakdown),
               "stalls" (adds per-stall-class attribution + per-op classes).
    workloads: subset of the evaluator's workload names; None = all.
    """
    idx: np.ndarray
    detail: str = "objectives"
    workloads: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        if self.detail not in DETAILS:
            raise ValueError(f"detail must be one of {DETAILS}, "
                             f"got {self.detail!r}")


@dataclasses.dataclass
class PPAReport:
    """Structured PPA result: a host-side pytree of numpy arrays.

    objectives follow the repo convention ``[*latencies, area]`` in workload
    order — for the paper workloads that is ``[ttft, tpot, area]``.
    """
    workloads: Tuple[str, ...]
    detail: str
    area: np.ndarray                                # (n,)
    latency: Dict[str, np.ndarray]                  # workload -> (n,)
    stall: Optional[Dict[str, np.ndarray]] = None   # workload -> (n, 4)
    op_time: Optional[Dict[str, np.ndarray]] = None
    op_class: Optional[Dict[str, np.ndarray]] = None
    op_names: Optional[Dict[str, tuple]] = None

    @property
    def n(self) -> int:
        return int(self.area.shape[0])

    @property
    def objectives(self) -> np.ndarray:
        """(n, len(workloads) + 1) objective matrix [*latencies, area]."""
        cols = [self.latency[w] for w in self.workloads] + [self.area]
        return np.stack(cols, axis=1)

    def stall_report(self, workload: Optional[str] = None, i: int = 0,
                     top: int = 5) -> StallReport:
        """Critical-path report for design row `i` on one workload."""
        if self.detail != "stalls":
            raise ValueError(
                f"stall_report needs detail='stalls', have {self.detail!r}")
        w = workload if workload is not None else self.workloads[0]
        return build_report(
            self.latency[w][i], self.area[i], self.stall[w][i],
            self.op_time[w][i], self.op_class[w][i], self.op_names[w],
            top=top)

    def stall_reports(self, i: int = 0, top: int = 5) -> Dict[str, StallReport]:
        return {w: self.stall_report(w, i, top) for w in self.workloads}

    def row(self, i: int) -> "PPAReport":
        """Single-design view of batch row `i` — the slicing half of the
        batched multi-design path (one fused dispatch, per-design reads)."""
        def sl(d):
            return {nm: v[i:i + 1] for nm, v in d.items()} if d else None
        return PPAReport(
            workloads=self.workloads, detail=self.detail,
            area=self.area[i:i + 1],
            latency={nm: self.latency[nm][i:i + 1] for nm in self.workloads},
            stall=sl(self.stall), op_time=sl(self.op_time),
            op_class=sl(self.op_class), op_names=self.op_names)


class Evaluator(Protocol):
    """The one PPA contract: everything downstream programs against this."""
    space: DesignSpace
    workloads: Tuple[str, ...]
    tier: str

    def evaluate(self, request: EvalRequest) -> PPAReport: ...

    def objectives(self, idx: np.ndarray) -> np.ndarray: ...


# ---------------------------------------------------------------------------
# shared per-design report-row cache
# ---------------------------------------------------------------------------

class RowCache:
    """Bounded LRU of single-design :class:`PPAReport` rows.

    THE report cache: :class:`~repro.distributed.service.EvalService` shares
    one instance across all its clients, and :class:`~repro.core.explore.
    ExplorationEngine` uses the service's instance when its evaluator IS a
    service (one cache, not two) or a private one otherwise.

    Entries are keyed by the design row's index bytes and hold the
    highest-detail report seen for that design.  A lookup hits only when the
    cached detail covers the requested level AND the cached report covers
    the requested workloads — a pair-only row never masquerades as a
    full-suite one.  Eviction is strictly LRU (hot rows are touched on every
    hit, so a campaign's base design survives any number of colder
    evictions).  Thread-safe.
    """

    def __init__(self, capacity: int = 65_536):
        self.capacity = int(capacity)
        self._lock = threading.RLock()
        self._d: "OrderedDict[bytes, Tuple[int, PPAReport]]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._d)

    @staticmethod
    def key(row: np.ndarray) -> bytes:
        return np.ascontiguousarray(row, dtype=np.int32).tobytes()

    def get(self, key: bytes, detail: str,
            names: Tuple[str, ...]) -> Optional[PPAReport]:
        """The cached row, or None if absent / too shallow / wrong suite."""
        level = _DETAIL_LEVEL[detail]
        with self._lock:
            ent = self._d.get(key)
            if (ent is None or ent[0] < level
                    or not set(names) <= set(ent[1].workloads)):
                self.misses += 1
                return None
            self._d.move_to_end(key)
            self.hits += 1
            return ent[1]

    def get_any(self, key: bytes,
                names: Tuple[str, ...]) -> Optional[Tuple[str, PPAReport]]:
        """The cached row at WHATEVER detail it has — ``(detail, row)`` —
        or None if absent / wrong suite.  The graceful-degradation path:
        when the evaluator is down, a shallower cached row beats an error.
        """
        with self._lock:
            ent = self._d.get(key)
            if ent is None or not set(names) <= set(ent[1].workloads):
                self.misses += 1
                return None
            self._d.move_to_end(key)
            self.hits += 1
            return DETAILS[ent[0]], ent[1]

    def put(self, key: bytes, detail: str, row: PPAReport) -> None:
        """Insert one single-design report row (never downgrades: an entry
        with higher detail AND at least the same workloads is kept)."""
        level = _DETAIL_LEVEL[detail]
        with self._lock:
            ent = self._d.get(key)
            if (ent is not None and ent[0] >= level
                    and set(row.workloads) <= set(ent[1].workloads)):
                self._d.move_to_end(key)
                return
            self._d[key] = (level, row)
            self._d.move_to_end(key)
            while len(self._d) > self.capacity:
                self._d.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._d.clear()


# ---------------------------------------------------------------------------
# tiers
# ---------------------------------------------------------------------------

# the model class that computes each analytical tier's op terms
TIER_MODEL = {"proxy": RooflineModel, "target": CompassModel}


def homogeneous_models(models: Mapping[str, RooflineModel]) -> bool:
    """True when every model shares one op-term implementation (class +
    compass knobs): the union pass computes every workload's op terms
    with one model's math, so :class:`ModelEvaluator` refuses any other
    set."""
    return len({(type(m), m.op_overhead_s, m.nonoverlap, m.mem_efficiency)
                for m in models.values()}) == 1


# ---------------------------------------------------------------------------
# the analytical-model evaluator (proxy / target tiers)
# ---------------------------------------------------------------------------

class ModelEvaluator:
    """Evaluator over a set of named workload models sharing one design space.

    The traced path is FUSED: one jitted executable decodes the index batch,
    derives the hardware spec once, and computes every workload's op terms —
    a single device dispatch per :meth:`evaluate` call regardless of the
    number of workloads or the detail level.  ``dispatches`` counts them
    (the DSE loop asserts one per step).  The same executable packs the
    report (:class:`~repro.perfmodel.roofline.PackedFn`): every leaf goes
    through one optimization barrier with the ``_``-keyed sinks, so the
    computation before it fuses as it would with the leaves as outputs and
    the report stays bit-identical, and the leaves leave the device as one
    uint32 buffer in ONE transfer.  The sinks stay unfetched outputs so
    that ``t_op`` is still materialized before the latency reduce.
    ``tracer`` (default the no-op tracer) spans each call as ``eval.call``
    (``rows``, ``bucket``) over ``eval.upload``, ``eval.launch`` and
    ``eval.fetch`` (``leaves``, ``copies``).
    """

    def __init__(self, models: Mapping[str, RooflineModel], *,
                 tier: str = "proxy",
                 scenarios: Optional[Tuple[Scenario, ...]] = None,
                 tracer=None):
        if not models:
            raise ValueError("need at least one workload model")
        self.models: Dict[str, RooflineModel] = dict(models)
        spaces = {id(m.space): m.space for m in self.models.values()}
        if len(spaces) > 1:
            keys = {_space_key(s) for s in spaces.values()}
            if len(keys) > 1:
                raise ValueError("all workload models must share one design space")
        self.space: DesignSpace = next(iter(self.models.values())).space
        # ONE op-term pass over the deduped union of all workloads' op
        # tables: near-flat cost in the workload count, and it needs every
        # model to share the op-term math (class + compass knobs)
        if not homogeneous_models(self.models):
            raise ValueError(
                "every workload model must share one class and compass-knob "
                "set (their op terms fuse into one pass)")
        self.tier = tier
        self.scenarios = scenarios
        self.dispatches = 0            # fused jitted dispatch count
        self.tracer = tracer if tracer is not None else NOOP
        self._fns: Dict[tuple, PackedFn] = {}
        self._stacks: Dict[Tuple[str, ...], WorkloadStack] = {}

    # -- identity ------------------------------------------------------
    @property
    def workloads(self) -> Tuple[str, ...]:
        return tuple(self.models)

    def _stack(self, names: Tuple[str, ...]) -> WorkloadStack:
        stack = self._stacks.get(names)
        if stack is None:
            stack = WorkloadStack.build({nm: self.models[nm].wl
                                         for nm in names})
            self._stacks[names] = stack
        return stack

    def _cache_key(self, detail: str, names: Tuple[str, ...]) -> tuple:
        return (detail, _space_key(self.space),
                tuple((nm, type(m).__qualname__, m._tp,
                       (m.op_overhead_s, m.nonoverlap, m.mem_efficiency),
                       _workload_fingerprint(m.wl))
                      for nm, m in self.models.items() if nm in names))

    # -- fused traced path ---------------------------------------------
    def _fused_fn(self, detail: str, names: Tuple[str, ...]) -> PackedFn:
        local = self._fns.get((detail, names))
        if local is not None:
            return local
        key = self._cache_key(detail, names)
        fn = _JIT_CACHE.get(key)
        if fn is None:
            fn = PackedFn(self._build_traced(detail, names))
            _JIT_CACHE[key] = fn
        self._fns[(detail, names)] = fn
        return fn

    def _build_traced(self, detail: str, names: Tuple[str, ...]) -> Callable:
        stack = self._stack(names)
        rep_model = self.models[names[0]]

        def fused(idx: jnp.ndarray) -> Dict:
            vals = self.space.decode(idx)            # once per batch
            hw = derive_hardware(vals)               # once per batch
            hwb = {kk: vv[:, None] for kk, vv in hw.items()}
            return {"area": hw["area_mm2"],
                    "per_workload": stacked_workload_batches(
                        rep_model, stack, hwb, detail)}

        return fused

    # -- public API -----------------------------------------------------
    def evaluate(self, request: EvalRequest) -> PPAReport:
        names = (self.workloads if request.workloads is None
                 else tuple(request.workloads))
        unknown = set(names) - set(self.models)
        if unknown:
            raise KeyError(f"unknown workloads {sorted(unknown)}; "
                           f"have {self.workloads}")
        rows = int(np.shape(np.atleast_2d(request.idx))[0])
        with self.tracer.span("eval.call", rows=rows,
                              bucket=_batch_bucket(rows)):
            fn = self._fused_fn(request.detail, names)
            out = _bucketed_call(fn, request.idx,        # ONE fused dispatch
                                 self.tracer)
            self.dispatches += 1
            per = out["per_workload"]
            detail = request.detail
            rep = PPAReport(
                workloads=names, detail=detail, area=out["area"],
                latency={nm: per[nm]["latency"] for nm in names})
            if detail in ("ppa", "stalls"):
                rep.op_time = {nm: per[nm]["op_time"] for nm in names}
                rep.op_names = {nm: tuple(self.models[nm].wl.op_names)
                                for nm in names}
            if detail == "stalls":
                rep.stall = {nm: per[nm]["stall"] for nm in names}
                rep.op_class = {nm: per[nm]["op_class"] for nm in names}
            return rep

    def objectives(self, idx: np.ndarray) -> np.ndarray:
        """(n, len(workloads)+1) objectives [*latencies, area], one dispatch."""
        return self.evaluate(EvalRequest(idx, detail="objectives")).objectives

    def ppa(self, idx: np.ndarray) -> PPAReport:
        return self.evaluate(EvalRequest(idx, detail="ppa"))

    def stalls(self, idx: np.ndarray) -> PPAReport:
        return self.evaluate(EvalRequest(idx, detail="stalls"))

    # baseline drivers (`run_method`) accept plain callables; the evaluator
    # IS one, so legacy `evaluator(X) -> (n, 3)` call sites keep working
    def __call__(self, idx: np.ndarray) -> np.ndarray:
        return self.objectives(idx)


# ---------------------------------------------------------------------------
# oracle tier: the exhaustive sweep front as ground truth
# ---------------------------------------------------------------------------

class OracleEvaluator:
    """Wraps a base evaluator with the exhaustive-sweep ground truth.

    Point evaluations delegate to the base (same fused dispatch); the oracle
    adds the exact full-space Pareto front from
    :class:`~repro.perfmodel.sweep.SweepEngine` — lazily swept once per
    process — so campaign metrics can be normalized against ground truth:
    ``normalized_phv`` reports PHV as a fraction of the exhaustive-front PHV
    (the ROADMAP's oracle-normalized Table 2/3 metric) and ``regret``
    measures distance from the true per-objective optima.

    ``oracle_store=`` opts into the persistent oracle store: ``True``
    uses ``~/.cache/repro-oracle/``, a string names a directory.  The
    sweep artifact is keyed by the engine's configuration fingerprint
    (space cards, workload fingerprints, model classes, stop +
    sweep knobs), so a repeat OracleEvaluator anywhere on the machine is
    an O(1) ``load_sweep_result`` instead of a re-sweep; a corrupt
    artifact is quarantined and re-swept, never trusted.
    """

    tier = "oracle"

    def __init__(self, base: ModelEvaluator, *, stop: Optional[int] = None,
                 sweep_kwargs: Optional[dict] = None,
                 oracle_store=None):
        self.base = base
        self.space = base.space
        self.stop = stop                      # None = the full space
        self._sweep_kwargs = dict(sweep_kwargs or {})
        self.oracle_store = oracle_store
        self._result = None
        self._phv_cache: Dict[bytes, float] = {}

    @property
    def workloads(self) -> Tuple[str, ...]:
        return self.base.workloads

    @property
    def dispatches(self) -> int:
        return self.base.dispatches

    def evaluate(self, request: EvalRequest) -> PPAReport:
        return self.base.evaluate(request)

    def objectives(self, idx: np.ndarray) -> np.ndarray:
        return self.base.objectives(idx)

    def __call__(self, idx: np.ndarray) -> np.ndarray:
        return self.base.objectives(idx)

    # -- ground truth ---------------------------------------------------
    def _store_path(self, eng) -> Optional[Tuple[str, str]]:
        """(artifact path, content key) under the oracle store, or None
        when the store is off."""
        if not self.oracle_store:
            return None
        import hashlib
        import os
        from repro.perfmodel.sweep import DEFAULT_ORACLE_STORE
        root = (DEFAULT_ORACLE_STORE if self.oracle_store is True
                else str(self.oracle_store))
        root = os.path.expanduser(root)
        knobs = "|".join(f"{k}={self._sweep_kwargs[k]}"
                         for k in sorted(self._sweep_kwargs))
        key = f"{eng.fingerprint()}|stop={self.stop}|{knobs}"
        digest = hashlib.sha256(key.encode()).hexdigest()[:32]
        return os.path.join(root, f"oracle-{digest}.npz"), key

    def sweep_result(self):
        """The (memoized) exhaustive sweep over [0, stop or size) — loaded
        from the oracle store when enabled and populated, swept (and
        stored) otherwise."""
        if self._result is None:
            from repro.perfmodel.sweep import (SweepEngine,
                                               load_sweep_result,
                                               save_sweep_result)
            eng = SweepEngine(self.base, **self._sweep_kwargs)
            loc = self._store_path(eng)
            if loc is not None:
                import os
                import warnings
                path, key = loc
                if os.path.exists(path):
                    try:
                        self._result = load_sweep_result(path, key=key)
                        return self._result
                    except ValueError as exc:
                        q = path + ".quarantined"
                        try:
                            os.replace(path, q)
                        except OSError:
                            q = "<could not rename>"
                        warnings.warn(
                            f"oracle store artifact {path} is invalid "
                            f"({exc}); quarantined to {q} — re-sweeping",
                            RuntimeWarning, stacklevel=2)
                self._result = eng.run(0, self.stop)
                save_sweep_result(path, self._result, key=key)
            else:
                self._result = eng.run(0, self.stop)
        return self._result

    def front(self) -> np.ndarray:
        """Exact Pareto-front objective rows (p, n_obj)."""
        return self.sweep_result().pareto_y

    def front_idx(self) -> np.ndarray:
        return self.sweep_result().pareto_idx(self.space)

    def oracle_phv(self, ref_point: np.ndarray) -> float:
        """Hypervolume of the exhaustive front w.r.t. `ref_point`."""
        from repro.core.pareto import hypervolume
        ref = np.asarray(ref_point, dtype=np.float64)
        key = ref.tobytes()
        if key not in self._phv_cache:
            self._phv_cache[key] = hypervolume(self.front(), ref)
        return self._phv_cache[key]

    def normalized_phv(self, phv: float, ref_point: np.ndarray) -> float:
        """Campaign PHV as a fraction of the exhaustive-front PHV."""
        oracle = self.oracle_phv(ref_point)
        return float(phv) / oracle if oracle > 0 else 0.0

    def regret(self, y: np.ndarray) -> np.ndarray:
        """Per-objective relative regret of a campaign's best points vs the
        true optima: (best_found - best_possible) / best_possible.

        ``y`` must live in the oracle front's objective space — for a
        zoo-suite oracle that is the ROBUST [r_prefill, r_decode, area]
        triple, not raw workload latencies.
        """
        y = np.atleast_2d(np.asarray(y, dtype=np.float64))
        best_true = self.sweep_result().topk_val[:, 0]
        if y.shape[1] != best_true.shape[0]:
            raise ValueError(
                f"regret expects {best_true.shape[0]}-objective rows "
                f"(the oracle front's space), got {y.shape[1]}")
        best_found = y.min(axis=0)
        return (best_found - best_true) / np.maximum(best_true, 1e-300)


# ---------------------------------------------------------------------------
# construction helpers
# ---------------------------------------------------------------------------

def make_evaluator(workloads: Mapping[str, "object"], *, tier: str = "proxy",
                   space: DesignSpace = SPACE,
                   scenarios: Optional[Tuple[Scenario, ...]] = None
                   ) -> ModelEvaluator:
    """Build a ModelEvaluator from {name: Workload} at a fidelity tier."""
    if tier not in TIER_MODEL:
        raise ValueError(f"tier must be one of {sorted(TIER_MODEL)} here; "
                         "use get_evaluator('oracle') for the oracle tier")
    cls = TIER_MODEL[tier]
    models = {nm: cls(wl, space) for nm, wl in workloads.items()}
    return ModelEvaluator(models, tier=tier, scenarios=scenarios)


_PAPER_EVALUATORS: Dict[tuple, "Evaluator"] = {}


def get_evaluator(tier: str = "proxy", *, oracle_base: str = "proxy",
                  oracle_stop: Optional[int] = None,
                  oracle_store=None,
                  workers: int = 1, mode: str = "auto",
                  suite: str = "paper") -> Evaluator:
    """The paper-workload (or zoo-portfolio) evaluator per tier (memoized).

    tier="proxy"  -> roofline models (cheap acquisition tier);
    tier="target" -> compass models (the budgeted high-fidelity tier);
    tier="oracle" -> OracleEvaluator over the ``oracle_base`` tier's
                     evaluator, exposing the exhaustive front.
    oracle_base: "proxy" (default) | "target" — the tier the oracle
             sweeps; ignored at the other tiers.
    oracle_store: opt-in persistent sweep-artifact store for the oracle
             tier (``True`` = ``~/.cache/repro-oracle/``, or a directory
             path) — repeat oracle construction loads the stored front
             in O(1) instead of re-sweeping.
    workers: > 1 wraps the evaluator in a :class:`~repro.distributed.
             sharded.ShardedEvaluator` that fans each EvalRequest's batch
             across N workers (`mode`: "thread" | "process" | "device" |
             "auto"); the report stays bit-identical to the local path.
    suite: "paper" — the GPT-3 (ttft, tpot) pair, one scenario;
           "zoo"   — every assigned architecture config as a scenario
           (``<arch>:prefill`` / ``<arch>:decode`` workload pairs built via
           :func:`~repro.perfmodel.workload.zoo_suite`).  All workloads
           evaluate in ONE stacked dispatch over the deduped op union, and
           ``.scenarios`` drives the portfolio sweep's per-scenario fronts.
    """
    if tier not in TIERS:
        raise ValueError(f"tier must be one of {TIERS}, got {tier!r}")
    if suite not in SUITES:
        raise ValueError(f"suite must be one of {SUITES}, got {suite!r}")
    if oracle_base not in TIER_MODEL:
        raise ValueError(f"oracle_base must be one of {tuple(TIER_MODEL)}, "
                         f"got {oracle_base!r}")
    from repro.distributed.sharded import MODES  # leaf dep (mode validation)
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    workers = max(1, int(workers))
    if workers == 1:
        mode = "auto"      # inert knobs: collapse onto the memoized base key
    if tier != "oracle":
        oracle_base = "proxy"  # inert knob: collapse onto one key
    key = (tier, oracle_base, oracle_stop, workers, mode, suite,
           None if not oracle_store else str(oracle_store))
    cached = _PAPER_EVALUATORS.get(key)
    if cached is not None:
        return cached
    from repro.perfmodel.workload import paper_suite, zoo_suite
    if tier == "oracle":
        base = get_evaluator(oracle_base, workers=workers, mode=mode,
                             suite=suite)
        ev: Evaluator = OracleEvaluator(base, stop=oracle_stop,
                                        oracle_store=oracle_store)
    else:
        cls = TIER_MODEL[tier]
        wls, scenarios = (paper_suite() if suite == "paper" else zoo_suite())
        models = {nm: cls(wl) for nm, wl in wls.items()}
        ev = ModelEvaluator(models, tier=tier, scenarios=scenarios)
        if workers > 1:
            from repro.distributed.sharded import ShardedEvaluator  # leaf dep
            ev = ShardedEvaluator(ev, workers=workers, mode=mode)
    _PAPER_EVALUATORS[key] = ev
    return ev


_MODEL_EVALUATORS: Dict[int, ModelEvaluator] = {}


def evaluator_for_model(model: RooflineModel, name: str = "lat") -> ModelEvaluator:
    """Memoized single-workload evaluator for one legacy model instance."""
    key = id(model)
    ev = _MODEL_EVALUATORS.get(key)
    if ev is None or ev.models.get(name) is not model:
        ev = ModelEvaluator({name: model})
        if len(_MODEL_EVALUATORS) >= 256:     # bound the id-keyed memo
            _MODEL_EVALUATORS.clear()
        _MODEL_EVALUATORS[key] = ev
    return ev


def pair_view(evaluator, names: Tuple[str, str]) -> Evaluator:
    """A two-workload view over ``names`` of a model-backed evaluator.

    Scenario campaigns point the DSE stack (QualE probing, QuanE
    sensitivity — both read objectives columns 0/1) at ONE (prefill,
    decode) pair of a multi-workload suite.  The view shares the base's
    model objects, so its compiled executables come out of the same
    workload-keyed jit cache.
    """
    names = tuple(names)
    if tuple(evaluator.workloads) == names:
        return evaluator
    models = evaluator.models
    unknown = set(names) - set(models)
    if unknown:
        raise KeyError(f"unknown workloads {sorted(unknown)}; "
                       f"have {tuple(models)}")
    return ModelEvaluator({nm: models[nm] for nm in names},
                          tier=evaluator.tier,
                          tracer=getattr(evaluator, "tracer", None))


def as_evaluator(obj) -> Evaluator:
    """Coerce onto the Evaluator contract.

    - an Evaluator passes through;
    - a single model becomes a (memoized) single-workload evaluator.

    The pre-PR-2 ``(ttft_model, tpot_model)`` pair signature was removed
    after its one-release deprecation window; build a two-workload
    evaluator with ``ModelEvaluator({"ttft": mt, "tpot": mp})`` or use
    :func:`get_evaluator`.
    """
    if hasattr(obj, "evaluate") and hasattr(obj, "workloads"):
        return obj
    if isinstance(obj, RooflineModel):
        return evaluator_for_model(obj)
    raise TypeError(f"cannot interpret {type(obj).__name__} as an Evaluator")
