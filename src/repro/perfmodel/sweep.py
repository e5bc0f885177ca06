"""Streaming full-space sweep engine: every design in [0, 4.7M) on device.

The paper's substrate claim is that vectorized PPA evaluation makes the
*entire* 4,741,632-point design space cheaper to evaluate than a handful of
LLMCompass samples.  :class:`SweepEngine` delivers that as a production
path: the flat id range is streamed through the jitted roofline model in
fixed-size chunks, with

* mixed-radix unranking **on device** — no host-side ``flat_to_idx``
  materialization of 4.7M index vectors;
* per-chunk on-device reduction: a running top-k per objective, the count of
  designs strictly dominating the reference point, and a bounded dominance
  filter (the on-device slice of the streaming Pareto archive) that kills
  ~all dominated points before anything leaves the device;
* an exact host-side :class:`~repro.core.pareto.ParetoArchive` absorbing the
  few filter survivors per chunk, so the final front equals the brute-force
  ``pareto_front`` of all evaluated points (while under archive capacity);
* donated carry buffers (no per-chunk reallocation), checkpoint/resume of
  partial sweeps, and optional sharding of the id range across devices;
* **multi-worker sharding of the id range** (``run(workers=N)``): the range
  splits into N contiguous chunk-aligned spans, each worker streams its own
  span (its own carry, archive and checkpoint file in the unchanged
  format), and the host merges top-k, per-stall-class seeds and the Pareto
  archive — reproducing the single-process result exactly;
* ``chunk_size="auto"``: a short timed probe over ``chunk_candidates``
  picks the fastest chunk size for this process (memoized);
* **portfolio mode**: an evaluator carrying multiple
  :class:`~repro.perfmodel.workload.Scenario`\\ s (e.g.
  ``get_evaluator(suite="zoo")``) streams the id range ONCE — one stacked
  op-term pass over the deduped workload union per chunk — while
  maintaining per-scenario running top-k, per-scenario exact Pareto
  archives, per-scenario stall-class seeds AND a robust front under
  ``robust="worst" | "geomean"`` scalarization of the reference-normalized
  scenario latencies.  The result's top-level front is the robust one;
  ``SweepResult.per_scenario`` holds every scenario's own result and
  ``stall_seeds(scenario=...)`` feeds bottleneck-seeded campaigns per
  scenario class.

Objectives follow the repo convention: ``[ttft, tpot, area]`` per scenario
(prefill latency, decode latency, area), all minimized.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pareto import ParetoArchive
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NOOP
from repro.runtime.fault import RetryPolicy, run_with_retries
from repro.perfmodel.designspace import DesignSpace, SPACE, A100_REFERENCE
from repro.perfmodel.hardware import derive_hardware
from repro.perfmodel.roofline import _dominant_class, _workload_fingerprint
from repro.perfmodel.workload import WorkloadStack

_FMT_VERSION = 3       # v3 adds portfolio (multi-scenario) checkpoints

ROBUST = ("worst", "geomean")

# stall classes in carry order (matches critical_path.STALL_CLASSES)
_N_STALL = 4

# chunk_size="auto" probe results, memoized per (platform, config)
_CHUNK_AUTO_CACHE: Dict[tuple, int] = {}


def _state_digest(payload: Dict) -> str:
    """sha256 over the checkpoint payload (sorted keys; dtype + shape +
    bytes per entry) — detects truncated or bit-flipped checkpoint files
    before their garbage reaches a resumed sweep."""
    h = hashlib.sha256()
    for k in sorted(payload):
        if k == "digest":
            continue
        arr = np.asarray(payload[k])
        h.update(k.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


# --------------------------------------------------------------------------
# on-device pieces (all traced inside the chunk step)
# --------------------------------------------------------------------------

def _unrank(flat: jnp.ndarray, cards: Tuple[int, ...]) -> jnp.ndarray:
    """Mixed-radix unrank on device: (c,) flat ids -> (c, n_params) int32.

    Matches ``DesignSpace.flat_to_idx`` (last parameter fastest-varying).
    """
    cols = []
    rem = flat
    for c in reversed(cards):
        cols.append(rem % c)
        rem = rem // c
    return jnp.stack(cols[::-1], axis=1).astype(jnp.int32)


def _dominated_on_device(filt: jnp.ndarray, ys: jnp.ndarray) -> jnp.ndarray:
    """(f, m) filter rows x (c, m) points -> (c,) dominated mask.

    Per-objective 2D comparisons (same shape XLA fuses well); +inf-padded
    filter rows can never dominate anything.
    """
    f = filt.shape[0]
    c, m = ys.shape
    all_le = jnp.ones((c, f), dtype=bool)
    any_lt = jnp.zeros((c, f), dtype=bool)
    for j in range(m):
        fj = filt[:, j][None, :]
        yj = ys[:, j][:, None]
        all_le &= fj <= yj
        any_lt |= fj < yj
    return (all_le & any_lt).any(axis=1)


@dataclasses.dataclass
class SweepResult:
    n_evaluated: int
    n_superior: int               # designs strictly dominating the reference
    pareto_y: np.ndarray          # (p, 3) exact front of evaluated points
    pareto_ids: np.ndarray        # (p,) flat design ids of the front
    topk_val: np.ndarray          # (3, k) best objective values seen
    topk_ids: np.ndarray          # (3, k) their flat design ids
    ref_point: np.ndarray
    seconds: float
    points_per_sec: float
    archive_truncated: bool       # capacity pruning fired (front then inexact)
    stall_topk_val: Optional[np.ndarray] = None   # (4, k) best TTFT latency
    stall_topk_ids: Optional[np.ndarray] = None   # (4, k) per dominant stall
    archive_capacity: Optional[int] = None        # final (auto-sized) bound
    # ---- portfolio sweeps: the top-level fields above describe the ROBUST
    # objectives [robust_prefill, robust_decode, area] (reference-normalized
    # latencies scalarized across scenarios); per-scenario results nest here
    scenario_names: Optional[Tuple[str, ...]] = None
    robust: Optional[str] = None                  # "worst" | "geomean"
    per_scenario: Optional[Dict[str, "SweepResult"]] = None

    def pareto_idx(self, space: DesignSpace = SPACE) -> np.ndarray:
        """Front design-index vectors (p, n_params)."""
        return space.flat_to_idx(self.pareto_ids)

    def scenario(self, name: str) -> "SweepResult":
        """One scenario's own sweep result (portfolio sweeps only)."""
        if not self.per_scenario:
            raise ValueError("not a portfolio sweep result")
        if name not in self.per_scenario:
            raise KeyError(f"unknown scenario {name!r}; "
                           f"have {self.scenario_names}")
        return self.per_scenario[name]

    def stall_seeds(self, space: DesignSpace = SPACE,
                    scenario: Optional[str] = None) -> Dict[str, np.ndarray]:
        """Per-stall-class seed designs for bottleneck-guided DSE.

        {stall class -> (k', n_params) index vectors}, the best designs
        (under the engine's ``stall_rank`` key) whose dominant stall is that
        class (requires ``stall_topk > 0``).  A class no swept design was
        dominated by comes back as an EMPTY (0, n_params) array — seeded
        campaign runners must skip it, not crash
        (:meth:`repro.core.campaign.CampaignRunner.seed_starts` does).

        On a portfolio result, ``scenario=<name>`` selects that scenario's
        seed classes; ``scenario=None`` flattens every scenario into
        ``"<scenario>:<stall class>"`` keys — ready-made campaign labels
        for per-scenario-class seeded DSE.
        """
        if self.per_scenario is not None:
            if scenario is not None:
                return self.scenario(scenario).stall_seeds(space)
            return {f"{nm}:{cls}": arr
                    for nm in self.scenario_names
                    for cls, arr in
                    self.per_scenario[nm].stall_seeds(space).items()}
        if scenario is not None:
            raise ValueError("scenario= is only valid on portfolio results")
        if self.stall_topk_ids is None:
            raise ValueError("sweep ran without stall_topk; no stall seeds")
        from repro.perfmodel.critical_path import STALL_CLASSES
        out = {}
        for c, name in enumerate(STALL_CLASSES):
            ids = self.stall_topk_ids[c]
            out[name] = space.flat_to_idx(ids[ids >= 0])
        return out


class SweepEngine:
    """Chunked streaming evaluation of the full (or a partial) design space.

    Parameters
    ----------
    evaluator:
        A :class:`~repro.perfmodel.evaluator.ModelEvaluator` of two or
        more workloads.  With more than one scenario it is swept as a
        portfolio; otherwise its first two workloads are the two latency
        objectives (area comes from the shared area model).
    stall_topk:
        When > 0, the chunk step also attributes stalls (TTFT workload) on
        device and keeps the `stall_topk` best designs per dominant stall
        class — sweep-derived seeds for bottleneck analysis
        (``SweepResult.stall_seeds``).
    stall_rank:
        Ranking key for the per-stall-class top-k: ``"ttft"`` (default)
        keeps the lowest-TTFT designs per class; ``"ref"`` ranks by the
        minimax objective ratio vs the reference point
        (``max_o y_o / ref_o`` — < 1 means the design dominates the
        reference), which is what seeded DSE campaigns want: the most
        *competitive* representative of each bottleneck regime instead of
        a latency-minimal max-area corner.
    chunk_size:
        Designs per device step, or ``"auto"`` to pick the fastest of
        ``chunk_candidates`` by a short timed probe (memoized per
        process).  Rounded up to a multiple of the device count when
        sharding.
    topk:
        Running best-k designs kept per objective.
    filter_size:
        Rows of the on-device dominance filter (synced from the host archive
        every chunk).  Larger kills more points on device but costs
        c x filter_size comparisons per chunk.
    local_filter:
        Per-objective (and log-sum) chunk-local killer rows added to the
        filter — this is what makes the cold-start chunk cheap.
    archive_capacity:
        Bound on the host Pareto archive; overflow prunes by crowding
        distance and marks the result ``archive_truncated``.
    shard:
        Shard the id range over all local devices (no-op on one device).
    registry / tracer:
        Optional :class:`~repro.obs.metrics.MetricsRegistry` and tracer;
        the engine registers a chunk counter and a per-chunk wall time
        histogram, and wraps ``run``, worker spans and each chunk's phases
        in trace spans (``sweep.run`` with ``op_rows``, the per-op term
        rows the chunk step evaluates for one design; ``sweep.chunk`` >
        ``sweep.filter``, ``sweep.wait``, ``sweep.fetch``, ``sweep.insert``
        with ``rows``).  Defaults: a
        private registry, and the no-op tracer, whose spans still reach a
        ``jax.profiler`` trace.  The jitted chunk step names its phases
        with ``jax.named_scope``: ``sweep.decode``, ``sweep.op_terms``,
        ``sweep.reduce``.
    """

    def __init__(self, evaluator, *,
                 chunk_size: Union[int, str, None] = None, topk: int = 16,
                 filter_size: int = 128, local_filter: int = 32,
                 archive_capacity: Union[int, str, None] = 16_384,
                 ref_point: Optional[np.ndarray] = None,
                 shard: bool = False,
                 stall_topk: int = 0, stall_rank: str = "ttft",
                 robust: str = "worst",
                 chunk_candidates: Tuple[int, ...] = (65_536, 131_072,
                                                      262_144),
                 registry: Optional[MetricsRegistry] = None,
                 tracer=None):
        if not hasattr(evaluator, "models"):
            raise TypeError("pass a ModelEvaluator")
        if len(evaluator.workloads) < 2:
            raise ValueError("sweep needs a two-workload evaluator "
                             "(ttft + tpot)")
        scenarios = getattr(evaluator, "scenarios", None)
        if scenarios is not None and len(scenarios) < 2:
            scenarios = None
        self.evaluator = evaluator
        self.ttft_model = evaluator.models[evaluator.workloads[0]]
        self.tpot_model = evaluator.models[evaluator.workloads[1]]
        self.space = space = evaluator.space
        self.size = space.size
        self.topk = int(topk)
        self.stall_topk = int(stall_topk)
        if stall_rank not in ("ttft", "ref"):
            raise ValueError(f"stall_rank must be 'ttft' or 'ref', "
                             f"got {stall_rank!r}")
        self.stall_rank = stall_rank
        if robust not in ROBUST:
            raise ValueError(f"robust must be one of {ROBUST}, got {robust!r}")
        self.robust = robust
        self.filter_size = int(filter_size)
        self.local_filter = int(local_filter)
        if isinstance(archive_capacity, str) and archive_capacity != "auto":
            raise ValueError("archive_capacity must be an int, None or "
                             f"'auto', got {archive_capacity!r}")
        self.archive_capacity = archive_capacity

        # ---- portfolio mode: S > 1 scenarios over one stacked op union ----
        self.scenarios = scenarios
        self._portfolio = scenarios is not None
        if self._portfolio:
            models = evaluator.models
            self._scen_names = tuple(s.name for s in scenarios)
            self._wl_order = tuple(nm for s in scenarios
                                   for nm in (s.prefill, s.decode))
            self._stack = WorkloadStack.build(
                {nm: models[nm].wl for nm in self._wl_order})
            self._rep_model = models[self._wl_order[0]]
            # count matrices for the chunk step's matmul reductions:
            # per-workload latency = t_unit @ C^T (ONE (c,U)x(U,W) dot
            # instead of W gather+sum branches), and per-scenario stall
            # sums contract the class-masked t_unit with the PREFILL rows
            stack = self._stack
            self._cmat_all = stack.count_matrix[
                [stack.names.index(nm) for nm in self._wl_order]]
            cmat_prefill = stack.count_matrix[
                [stack.names.index(s.prefill) for s in scenarios]]
            # stall attribution only touches unique ops some PREFILL
            # workload uses — restricting the class-masked traversals to
            # those columns cuts the chunk step's dominant memory traffic
            self._stall_cols = np.flatnonzero(cmat_prefill.sum(axis=0) > 0)
            self._cmat_prefill = cmat_prefill[:, self._stall_cols]
            # per-scenario dominance filters stay lean: the host archive is
            # exact regardless, and S+1 group filters traverse (c, S+1, f)
            self._pf_rows = max(8, min(self.filter_size // 4, 32))

        self._cards = tuple(int(c) for c in space.cardinalities)

        if self._portfolio:
            n_scen = len(scenarios)
            if ref_point is None:
                ref_points = self._scenario_refs()
            else:
                ref_points = np.asarray(ref_point, dtype=np.float64)
                if ref_points.shape != (n_scen, 3):
                    raise ValueError(
                        f"portfolio ref_point must be ({n_scen}, 3) — one "
                        f"[prefill, decode, area] row per scenario — got "
                        f"shape {ref_points.shape}")
            self.ref_points = ref_points
            # the robust reference: every normalized latency is 1 at the
            # reference design, area is the raw reference area
            self.ref_point = np.array([1.0, 1.0, float(ref_points[0, 2])])
        else:
            if ref_point is None:
                ref_idx = space.encode_nearest(A100_REFERENCE)[None, :]
                ref_point = self._host_objectives(ref_idx)[0]
            self.ref_point = np.asarray(ref_point, dtype=np.float64)

        if chunk_size is None:
            # portfolio chunks stream ~10x the op rows per id: keep the
            # working set cache-friendly by default
            chunk_size = 65_536 if self._portfolio else 131_072
        if isinstance(chunk_size, str):
            if chunk_size != "auto":
                raise ValueError(
                    f"chunk_size must be an int or 'auto', got {chunk_size!r}")
            chunk_size = self._autotune_chunk(chunk_candidates, shard)

        self._sharding = None
        ndev = len(jax.devices())
        # the chunk must divide by the device count when sharding; ids past
        # `stop` are masked invalid, so padding the chunk is always safe
        multiple = 1
        if shard and ndev > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            # Auto axes: the chunk step is written without sharding
            # annotations, so the compiler partitions it (jax's default
            # Explicit axes reject its gathers outside a mesh context)
            mesh = jax.make_mesh((ndev,), ("sweep",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
            self._sharding = NamedSharding(mesh, P("sweep"))
            multiple = ndev
        chunk_size = int(chunk_size)
        chunk_size += (-chunk_size) % multiple
        self.chunk_size = int(chunk_size)
        iota = jnp.arange(self.chunk_size, dtype=jnp.int32)
        self._iota = (jax.device_put(iota, self._sharding)
                      if self._sharding is not None else iota)

        self.tracer = tracer if tracer is not None else NOOP
        self.metrics = registry if registry is not None else MetricsRegistry()
        self._c_chunks = self.metrics.counter(
            "sweep_chunks", "device chunk steps executed")
        self._h_chunk = self.metrics.histogram(
            "sweep_chunk_s", "wall time per chunk step incl. host reduce (s)")

        self._step = jax.jit(
            self._step_portfolio_impl if self._portfolio else self._step_impl,
            donate_argnums=(0,))
        # per-op term rows the chunk step evaluates for one design: both
        # workloads' tables on the pair step; the union, plus the stall
        # columns' second pass, on the portfolio step
        if self._portfolio:
            self.op_rows = self._stack.n_unique + (
                len(self._stall_cols) if self.stall_topk else 0)
        else:
            self.op_rows = (len(self.ttft_model.wl.ops)
                            + len(self.tpot_model.wl.ops))

    def _scenario_refs(self) -> np.ndarray:
        """(S, 3) reference [prefill, decode, area] per scenario (A100)."""
        from repro.perfmodel.evaluator import EvalRequest
        ref_idx = self.space.encode_nearest(A100_REFERENCE)[None, :]
        rep = self.evaluator.evaluate(EvalRequest(ref_idx,
                                                  detail="objectives"))
        return np.array([[float(rep.latency[s.prefill][0]),
                          float(rep.latency[s.decode][0]),
                          float(rep.area[0])] for s in self.scenarios])

    def _autotune_chunk(self, candidates: Tuple[int, ...],
                        shard: bool) -> int:
        """Timed probe: one warmed chunk step per candidate size, keep the
        highest-throughput one (memoized per process).
        Probe engines inherit the parent's shard flag so a sharded sweep is
        tuned on the sharded execution path."""
        if not candidates:
            raise ValueError("chunk_size='auto' needs a non-empty "
                             "chunk_candidates tuple")
        key = (jax.default_backend(), self.fingerprint(),
               int(self.stall_topk), bool(shard),
               tuple(int(c) for c in candidates))
        cached = _CHUNK_AUTO_CACHE.get(key)
        if cached is not None:
            return cached
        best, best_rate = int(candidates[0]), -1.0
        for cand in candidates:
            eng = SweepEngine(
                self.evaluator, chunk_size=int(cand), topk=self.topk,
                filter_size=self.filter_size, local_filter=self.local_filter,
                archive_capacity=self.archive_capacity,
                ref_point=(self.ref_points if self._portfolio
                           else self.ref_point),
                shard=shard, robust=self.robust,
                stall_topk=self.stall_topk, stall_rank=self.stall_rank)
            span = min(eng.chunk_size, self.size)
            eng.run(0, span)                       # compile + warm
            t0 = time.perf_counter()
            eng.run(0, span)
            rate = span / max(time.perf_counter() - t0, 1e-9)
            if rate > best_rate:
                best, best_rate = int(eng.chunk_size), rate
        _CHUNK_AUTO_CACHE[key] = best
        return best

    # ------------------------------------------------------------------
    def _host_objectives(self, idx: np.ndarray) -> np.ndarray:
        """Reference evaluation through the evaluator's fused public path."""
        return self.evaluator.objectives(idx)

    def _chunk_eval(self, idx: jnp.ndarray):
        """(c, n_params) int32 -> ((c, 3) objectives, dominant-stall (c,)
        or None), traced.  Decode + hardware derivation run once per chunk;
        stall attribution is only computed when stall_topk is enabled."""
        with jax.named_scope("sweep.decode"):
            vals = self.space.decode(idx)
            hw = derive_hardware(vals)
            hwb = {kk: vv[:, None] for kk, vv in hw.items()}
        detail_t = "stalls" if self.stall_topk else "objectives"
        with jax.named_scope("sweep.op_terms"):
            out_t = self.ttft_model._workload_batch(hwb, detail_t)
            out_p = self.tpot_model._workload_batch(hwb, "objectives")
            ys = jnp.stack([out_t["latency"], out_p["latency"],
                            hw["area_mm2"]], axis=1)
            dom = (jnp.argmax(out_t["stall"], axis=1).astype(jnp.int32)
                   if self.stall_topk else None)
        return ys, dom

    def _step_impl(self, carry: Dict[str, jnp.ndarray], start: jnp.ndarray,
                   stop: jnp.ndarray, filt: jnp.ndarray):
        """One donated-carry chunk step: unrank -> evaluate -> reduce."""
        with jax.named_scope("sweep.decode"):
            ids = start + self._iota
            valid = ids < stop
            idx = _unrank(jnp.minimum(ids, self.size - 1), self._cards)
        ys, dom = self._chunk_eval(idx)                       # (c, 3), (c,)
        with jax.named_scope("sweep.reduce"):
            ysm = jnp.where(valid[:, None], ys, jnp.inf)

            # ---- reference-superiority count (exact, streaming) ----
            ref = jnp.asarray(self.ref_point, ys.dtype)
            sup = (ysm < ref[None, :]).all(axis=1)
            n_super = carry["n_super"] + sup.sum(dtype=jnp.int32)
            n_eval = carry["n_eval"] + valid.sum(dtype=jnp.int32)

            # ---- running top-k per objective ----
            new_vals, new_ids = [], []
            for o in range(3):                                # static unroll
                vals = jnp.concatenate([carry["topk_val"][o], ysm[:, o]])
                cand = jnp.concatenate([carry["topk_id"][o], ids])
                neg, sel = jax.lax.top_k(-vals, self.topk)
                new_vals.append(-neg)
                new_ids.append(cand[sel])
            topk_val = jnp.stack(new_vals)
            topk_id = jnp.stack(new_ids)

            # ---- running top-k per dominant stall class (optional) ----
            stall_val = stall_id = None
            if self.stall_topk:
                if self.stall_rank == "ref":
                    # minimax objective ratio vs the reference (< 1 dominates)
                    lat = (ysm / ref[None, :]).max(axis=1)
                else:
                    lat = ysm[:, 0]                           # rank by TTFT
                new_vals, new_ids = [], []
                for c in range(_N_STALL):                     # static unroll
                    lat_c = jnp.where(dom == c, lat, jnp.inf)
                    vals = jnp.concatenate([carry["stall_topk_val"][c], lat_c])
                    cand = jnp.concatenate([carry["stall_topk_id"][c], ids])
                    neg, sel = jax.lax.top_k(-vals, self.stall_topk)
                    new_vals.append(-neg)
                    new_ids.append(jnp.where(jnp.isfinite(-neg), cand[sel],
                                             -1))
                stall_val = jnp.stack(new_vals)
                stall_id = jnp.stack(new_ids)

            # ---- streaming Pareto reduction ----
            # archive filter (synced from host) + chunk-local killer rows:
            # per-objective minima and smallest log-products dominate most of
            # the chunk, so the cold-start chunk also reduces on device.
            L = self.local_filter
            locals_ = []
            for o in range(3):
                _, sel = jax.lax.top_k(-ysm[:, o], L)
                locals_.append(ysm[sel])
            _, sel = jax.lax.top_k(
                -jnp.log(jnp.maximum(ysm, 1e-300)).sum(axis=1), L)
            locals_.append(ysm[sel])
            full_filt = jnp.concatenate([filt.astype(ys.dtype)] + locals_,
                                        axis=0)
            dominated = _dominated_on_device(full_filt, ysm)
            survivor = valid & ~dominated
            ys_out = jnp.where(survivor[:, None], ys, jnp.inf)

        carry = {"n_super": n_super, "n_eval": n_eval,
                 "topk_val": topk_val, "topk_id": topk_id}
        if self.stall_topk:
            carry["stall_topk_val"] = stall_val
            carry["stall_topk_id"] = stall_id
        return carry, survivor, ys_out, ids

    # ---------------- portfolio (multi-scenario) chunk step ----------------
    def _chunk_eval_portfolio(self, idx: jnp.ndarray):
        """(c, n_params) -> ((c, S, 3) per-scenario objectives, (c, S)
        dominant prefill stall or None).

        ONE stacked op-term pass over the deduped union; every per-workload
        reduction is a count-matrix contraction (latencies:
        ``t_unit @ C_all^T``; per-scenario stall sums: the class-masked
        ``t_unit`` against the prefill rows) — no per-workload unrolling,
        so both compile time and runtime stay near-flat in W.
        """
        with jax.named_scope("sweep.decode"):
            vals = self.space.decode(idx)
            hw = derive_hardware(vals)
            hwb = {kk: vv[:, None] for kk, vv in hw.items()}
        stack = self._stack
        with jax.named_scope("sweep.op_terms"):
            uops = {kk: jnp.asarray(vv) for kk, vv in stack.unique.items()}
            uops["count"] = jnp.ones(stack.n_unique)
            t = self._rep_model._op_terms(hwb, ops=uops)
            # HIGHEST: a DEFAULT-precision f32 dot may run as one bf16 pass on
            # the TPU (~1e-3 relative error in every scenario latency)
            lat = jnp.matmul(t["t_unit"], jnp.asarray(self._cmat_all).T,
                             precision=jax.lax.Precision.HIGHEST)  # (c, 2S)
            area = hw["area_mm2"]
            S = len(self.scenarios)
            ys = jnp.stack([lat[:, 0::2], lat[:, 1::2],
                            jnp.broadcast_to(area[:, None],
                                             (idx.shape[0], S))], axis=2)
            dom = None
            if self.stall_topk:
                # a SECOND op-term pass statically restricted to prefill-used
                # rows: consuming t_compute/t_memory/t_comm out of the full
                # union pass would force XLA to re-materialize its big (c, U)
                # intermediates — recomputing the small (c, P) chain is 2x
                # cheaper than widening the first pass's fusion
                uop2 = {kk: jnp.asarray(vv[self._stall_cols])
                        for kk, vv in stack.unique.items()}
                uop2["count"] = jnp.ones(len(self._stall_cols))
                t2 = self._rep_model._op_terms(hwb, ops=uop2)
                dom_g = _dominant_class(t2)                     # (c, P)
                cp = jnp.asarray(self._cmat_prefill).T          # (P, S)
                stall = jnp.stack(
                    [jnp.matmul(jnp.where(dom_g == k, t2["t_unit"], 0.0), cp,
                                precision=jax.lax.Precision.HIGHEST)
                     for k in range(_N_STALL)], axis=2)         # (c, S, 4)
                dom = jnp.argmax(stall, axis=2).astype(jnp.int32)
        return ys, dom

    def _robust_objectives(self, ys_s: jnp.ndarray) -> jnp.ndarray:
        """(c, S, 3) -> (c, 3) scalarized [robust_p, robust_d, area]: the
        reference-normalized latency aggregated across scenarios (worst
        case or geometric mean), plus the shared raw area."""
        refs = jnp.asarray(self.ref_points, ys_s.dtype)
        ratio = ys_s[:, :, :2] / refs[None, :, :2]
        if self.robust == "worst":
            r = ratio.max(axis=1)
        else:
            r = jnp.exp(jnp.log(jnp.maximum(ratio, 1e-300)).mean(axis=1))
        return jnp.concatenate([r, ys_s[:, 0, 2:3]], axis=1)

    def _step_portfolio_impl(self, carry: Dict[str, jnp.ndarray],
                             start: jnp.ndarray, stop: jnp.ndarray,
                             filt: jnp.ndarray):
        """One donated-carry portfolio chunk step.

        Group axis: S scenarios then the robust scalarization (index S).
        Every reduction is batched across groups — ONE top_k call merges
        all (S+1) x 3 running top-k rows, one merges the S x 4 stall-class
        rows, one picks every group's local-filter killer rows.
        """
        S = len(self.scenarios)
        S1, k, c = S + 1, self.topk, self.chunk_size
        with jax.named_scope("sweep.decode"):
            ids = start + self._iota
            valid = ids < stop
            idx = _unrank(jnp.minimum(ids, self.size - 1), self._cards)
        ys_s, dom = self._chunk_eval_portfolio(idx)       # (c,S,3), (c,S)
        with jax.named_scope("sweep.reduce"):
            ys_r = self._robust_objectives(ys_s)              # (c,3)
            ys_all = jnp.concatenate([ys_s, ys_r[:, None, :]], axis=1)
            ysm = jnp.where(valid[:, None, None], ys_all, jnp.inf)

            # ---- per-group reference-superiority counts ----
            refs_all = jnp.concatenate(
                [jnp.asarray(self.ref_points, ys_all.dtype),
                 jnp.asarray(self.ref_point, ys_all.dtype)[None, :]], axis=0)
            sup = (ysm < refs_all[None, :, :]).all(axis=2)    # (c, S1)
            n_super = carry["n_super"] + sup.sum(axis=0, dtype=jnp.int32)
            n_eval = carry["n_eval"] + valid.sum(dtype=jnp.int32)

            # ---- running top-k, batched over (S1 x 3) rows ----
            ysm_rows = jnp.moveaxis(ysm, 0, 2)                # (S1, 3, c)
            vals = jnp.concatenate(
                [carry["topk_val"].reshape(S1 * 3, k),
                 ysm_rows.reshape(S1 * 3, c)], axis=1)
            cand = jnp.concatenate(
                [carry["topk_id"].reshape(S1 * 3, k),
                 jnp.broadcast_to(ids[None, :], (S1 * 3, c))], axis=1)
            neg, sel = jax.lax.top_k(-vals, k)
            topk_val = (-neg).reshape(S1, 3, k)
            topk_id = jnp.take_along_axis(cand, sel, axis=1).reshape(S1, 3, k)

            # ---- per-scenario stall-class top-k (optional), batched ----
            stall_val = stall_id = None
            if self.stall_topk:
                sk = self.stall_topk
                refs = refs_all[:S]
                if self.stall_rank == "ref":
                    rank = (ysm[:, :S, :] / refs[None, :, :]).max(axis=2)
                else:
                    rank = ysm[:, :S, 0]                  # scenario prefill
                hit = dom[:, :, None] == jnp.arange(_N_STALL)[None, None, :]
                masked = jnp.where(hit, rank[:, :, None], jnp.inf)  # (c, S, 4)
                rows = jnp.moveaxis(masked, 0, 2).reshape(S * _N_STALL, c)
                vals = jnp.concatenate(
                    [carry["stall_topk_val"].reshape(S * _N_STALL, sk), rows],
                    axis=1)
                cand = jnp.concatenate(
                    [carry["stall_topk_id"].reshape(S * _N_STALL, sk),
                     jnp.broadcast_to(ids[None, :], (S * _N_STALL, c))],
                    axis=1)
                neg, sel = jax.lax.top_k(-vals, sk)
                stall_val = (-neg).reshape(S, _N_STALL, sk)
                stall_id = jnp.where(jnp.isfinite(-neg),
                                     jnp.take_along_axis(cand, sel, axis=1),
                                     -1).reshape(S, _N_STALL, sk)

            # ---- streaming Pareto reduction, batched over all S1 groups ----
            # chunk-local killer rows: each group's per-objective minima plus
            # its best reference-normalized sum (4 rows/group, one argmin pass)
            normsum = (ysm / refs_all[None, :, :]).sum(axis=2)     # (c, S1)
            keys = jnp.concatenate([ysm, normsum[:, :, None]], axis=2)
            sel = jnp.argmin(keys, axis=0)                         # (S1, 4)
            ysm_t = jnp.moveaxis(ysm, 0, 1)                        # (S1, c, 3)
            locals_ = jnp.take_along_axis(ysm_t, sel[:, :, None], axis=1)
            full_filt = jnp.concatenate(
                [filt.astype(ys_all.dtype), locals_], axis=1)  # (S1, f+4, 3)
            all_le = jnp.ones((c, S1, full_filt.shape[1]), bool)
            any_lt = jnp.zeros_like(all_le)
            for j in range(3):
                fj = full_filt[None, :, :, j]
                yj = ysm[:, :, j][:, :, None]
                all_le &= fj <= yj
                any_lt |= fj < yj
            dominated = (all_le & any_lt).any(axis=2)              # (c, S1)
            survivor = valid[:, None] & ~dominated
            ys_out = jnp.where(survivor[:, :, None], ys_all, jnp.inf)

        carry = {"n_super": n_super, "n_eval": n_eval,
                 "topk_val": topk_val, "topk_id": topk_id}
        if self.stall_topk:
            carry["stall_topk_val"] = stall_val
            carry["stall_topk_id"] = stall_id
        return carry, survivor, ys_out, ids

    # ------------------------------------------------------------------
    @property
    def _n_groups(self) -> int:
        """Archive/filter groups: S scenarios + the robust front, or 1."""
        return len(self.scenarios) + 1 if self._portfolio else 1

    def _fresh_state(self, start: int) -> Dict:
        k = self.topk
        if self._portfolio:
            S, S1 = len(self.scenarios), self._n_groups
            carry = {
                "n_super": jnp.zeros((S1,), jnp.int32),
                "n_eval": jnp.zeros((), jnp.int32),
                "topk_val": jnp.full((S1, 3, k), jnp.inf, jnp.float32),
                "topk_id": jnp.full((S1, 3, k), -1, jnp.int32),
            }
            if self.stall_topk:
                carry["stall_topk_val"] = jnp.full(
                    (S, _N_STALL, self.stall_topk), jnp.inf, jnp.float32)
                carry["stall_topk_id"] = jnp.full(
                    (S, _N_STALL, self.stall_topk), -1, jnp.int32)
            return {"next": int(start), "carry": carry,
                    "archives": [ParetoArchive(3,
                                               capacity=self.archive_capacity)
                                 for _ in range(S1)]}
        carry = {
            "n_super": jnp.zeros((), jnp.int32),
            "n_eval": jnp.zeros((), jnp.int32),
            "topk_val": jnp.full((3, k), jnp.inf, jnp.float32),
            "topk_id": jnp.full((3, k), -1, jnp.int32),
        }
        if self.stall_topk:
            carry["stall_topk_val"] = jnp.full(
                (_N_STALL, self.stall_topk), jnp.inf, jnp.float32)
            carry["stall_topk_id"] = jnp.full(
                (_N_STALL, self.stall_topk), -1, jnp.int32)
        return {"next": int(start), "carry": carry,
                "archive": ParetoArchive(3, capacity=self.archive_capacity)}

    def _filter_from_archive(self, archive: ParetoArchive,
                             rows: Optional[int] = None) -> np.ndarray:
        """Up to `rows` (default filter_size) spread-out front rows,
        +inf padded."""
        rows = self.filter_size if rows is None else int(rows)
        filt = np.full((rows, 3), np.inf, dtype=np.float32)
        n = len(archive)
        if n:
            order = np.argsort(archive.y.sum(axis=1), kind="stable")
            take = order[np.linspace(0, n - 1, min(n, rows))
                         .astype(np.int64)]
            filt[: take.size] = archive.y[take]
        return filt

    def fingerprint(self) -> str:
        """Identity of (space, workloads, knobs) for checkpoint validation."""
        if self._portfolio:
            parts = [str(self._cards), "roofline",
                     f"robust={self.robust}",
                     type(self._rep_model).__qualname__]
            for s in self.scenarios:
                parts.append(f"{s.name}="
                             + _workload_fingerprint(
                                 self.evaluator.models[s.prefill].wl)
                             + ":"
                             + _workload_fingerprint(
                                 self.evaluator.models[s.decode].wl))
            if self.stall_rank != "ttft":
                parts.append(f"stall_rank={self.stall_rank}")
            return "|".join(parts)
        parts = [
            str(self._cards), "roofline",
            _workload_fingerprint(self.ttft_model.wl),
            _workload_fingerprint(self.tpot_model.wl),
            type(self.ttft_model).__qualname__,
            type(self.tpot_model).__qualname__,
        ]
        if self.stall_rank != "ttft":   # default omitted: old ckpts stay valid
            parts.append(f"stall_rank={self.stall_rank}")
        return "|".join(parts)

    # ------------------------------------------------------------------
    def run(self, start: int = 0, stop: Optional[int] = None, *,
            workers: int = 1,
            checkpoint_path: Optional[str] = None,
            checkpoint_every: Optional[int] = None,
            resume_from: Optional[str] = None,
            progress: bool = False,
            fault_plan=None,
            span_retry: Optional[RetryPolicy] = None) -> SweepResult:
        """Sweep flat ids [start, stop) and reduce to a SweepResult.

        ``workers=N`` shards the id range into N contiguous chunk-aligned
        spans streamed concurrently (each worker has its own carry and
        archive); the host merge reproduces the single-process result
        exactly.  ``checkpoint_path``/``checkpoint_every`` persist partial
        state every N chunks — atomically (tmp + ``os.replace``) with a
        content digest, so a kill mid-write can never leave a checkpoint
        that poisons a resume; ``resume_from`` restores it (and overrides
        ``start``).  A corrupt or truncated checkpoint is QUARANTINED
        (renamed ``*.quarantined`` + warning) and the span restarts fresh
        instead of crashing — only genuine config mismatches
        (space/workload fingerprint, reference point) still refuse to
        resume.  Multi-worker runs keep one checkpoint file per worker
        (``{path}.w{i}of{N}``, unchanged single-worker format with the
        worker's span stamped into the fingerprint), so a resume must use
        the same range and worker count.

        ``fault_plan`` injects a seeded :class:`~repro.distributed.faults.
        FaultPlan` into the span loop (worker = span index, dispatch =
        chunk ordinal): ``crash`` events abort the span, which is then
        REPLAYED under ``span_retry`` (default: 2 retries) from its own
        last checkpoint if one exists, from scratch otherwise — either
        way the streamed reduction is deterministic, so the merged result
        stays bit-identical to a fault-free run.
        """
        stop = self.size if stop is None else min(int(stop), self.size)
        workers = max(1, int(workers))
        tr = self.tracer
        t0 = time.perf_counter()
        with tr.span("sweep.run", start=int(start), stop=int(stop),
                     workers=workers, op_rows=self.op_rows):
            parent = tr.current_ctx()
            if workers == 1:
                states = [self._run_span(
                    0, start, stop, checkpoint_path=checkpoint_path,
                    checkpoint_every=checkpoint_every,
                    resume_from=resume_from,
                    progress=progress, label="", fp_extra="",
                    fault_plan=fault_plan, span_retry=span_retry,
                    trace_parent=parent)]
            else:
                spans = self._worker_spans(start, stop, workers)
                n = len(spans)
                with ThreadPoolExecutor(max_workers=n,
                                        thread_name_prefix="sweep") as ex:
                    futs = []
                    for w, (s0, s1) in enumerate(spans):
                        suffix = f".w{w}of{n}"
                        futs.append(ex.submit(
                            self._run_span, w, s0, s1,
                            checkpoint_path=(f"{checkpoint_path}{suffix}"
                                             if checkpoint_path else None),
                            checkpoint_every=checkpoint_every,
                            resume_from=(f"{resume_from}{suffix}"
                                         if resume_from else None),
                            progress=progress, label=f"w{w}: ",
                            fp_extra=f"|span={s0}:{s1}",
                            fault_plan=fault_plan, span_retry=span_retry,
                            trace_parent=parent))
                    states = [f.result() for f in futs]
        return self._reduce_states(states, time.perf_counter() - t0)

    def _run_span(self, worker: int, start: int, stop: int, *,
                  checkpoint_path: Optional[str],
                  checkpoint_every: Optional[int],
                  resume_from: Optional[str], progress: bool,
                  label: str, fp_extra: str,
                  fault_plan=None,
                  span_retry: Optional[RetryPolicy] = None,
                  trace_parent=None) -> Dict:
        """One worker span, replayed on crash: a failed attempt resumes
        from the span's own atomic checkpoint when one exists, from
        scratch otherwise — deterministic either way.

        ``trace_parent`` is the sweep.run span ctx: worker spans run on
        pool threads, so parenting is explicit, not thread-inherited."""
        tr = self.tracer
        sp = (tr.start("sweep.span", parent=trace_parent, detached=True,
                       worker=worker, start=int(start), stop=int(stop))
              if tr.enabled else None)

        def attempt(resume: Optional[str]) -> Dict:
            return self._run_range(
                start, stop, checkpoint_path=checkpoint_path,
                checkpoint_every=checkpoint_every, resume_from=resume,
                progress=progress, label=label, fp_extra=fp_extra,
                fault_plan=fault_plan, worker_slot=worker)

        try:
            if fault_plan is None and span_retry is None:
                return attempt(resume_from)
            policy = (span_retry if span_retry is not None
                      else RetryPolicy(max_retries=2,
                                       retryable=(RuntimeError,)))
            resume = {"from": resume_from}

            def restore(attempt_no: int) -> None:
                if sp is not None:
                    sp.attrs["replays"] = attempt_no
                resume["from"] = None
                if checkpoint_path:
                    f = (checkpoint_path if checkpoint_path.endswith(".npz")
                         else f"{checkpoint_path}.npz")
                    if os.path.exists(f):
                        resume["from"] = checkpoint_path

            return run_with_retries(lambda: attempt(resume["from"]), restore,
                                    policy)
        except Exception as exc:
            if sp is not None:
                sp.attrs["error"] = str(exc)
                tr.finish(sp, status="error")
            raise
        finally:
            if sp is not None:
                tr.finish(sp)      # idempotent: no-op on the error path

    def _worker_spans(self, start: int, stop: int,
                      workers: int) -> List[Tuple[int, int]]:
        """Contiguous chunk-aligned spans covering [start, stop) — every
        worker streams the same chunk sequence a single process would."""
        n_chunks = -(-max(0, stop - start) // self.chunk_size)
        if n_chunks == 0:
            return [(start, stop)]
        per = -(-n_chunks // min(workers, n_chunks))
        spans, s = [], start
        while s < stop:
            e = min(stop, s + per * self.chunk_size)
            spans.append((s, e))
            s = e
        return spans

    def _run_range(self, start: int, stop: int, *,
                   checkpoint_path: Optional[str] = None,
                   checkpoint_every: Optional[int] = None,
                   resume_from: Optional[str] = None,
                   progress: bool = False, label: str = "",
                   fp_extra: str = "", fault_plan=None,
                   worker_slot: int = 0) -> Dict:
        """Stream one contiguous id span; returns its final state dict
        (plus the resumed-eval count under ``"resumed"``)."""
        state = self._load(resume_from, fp_extra) if resume_from else None
        if state is None:          # no checkpoint, or quarantined as corrupt
            state = self._fresh_state(start)
        archives: List[ParetoArchive] = (state["archives"] if self._portfolio
                                         else [state["archive"]])
        carry = state["carry"]
        n_eval_resumed = int(carry["n_eval"])
        tr = self.tracer
        t0 = time.perf_counter()
        chunk_i = 0
        while state["next"] < stop:
            if fault_plan is not None:
                ev = fault_plan.fire(worker_slot, chunk_i)
                if ev is not None and ev.kind == "crash":
                    from repro.distributed.faults import WorkerFault
                    raise WorkerFault(f"injected sweep crash: worker "
                                      f"{worker_slot} chunk {chunk_i}")
                if ev is not None and ev.kind == "slow":
                    time.sleep(ev.delay_s)
            t_chunk = time.perf_counter()
            with tr.span("sweep.chunk"):
                s = state["next"]
                rows = self._pf_rows if self._portfolio else None
                with tr.span("sweep.filter"):
                    filt = np.stack([self._filter_from_archive(a, rows)
                                     for a in archives])
                    filt = jnp.asarray(filt if self._portfolio else filt[0])
                # ids >= stop are masked invalid on device, so a partial
                # final chunk (or a truncated-range sweep) stays exact.
                carry, survivor, ys_out, ids = self._step(
                    carry, jnp.int32(s), jnp.int32(stop), filt)
                with tr.span("sweep.wait"):
                    mask = np.asarray(survivor)       # (c,) or (c, S+1)
                n_rows = int(np.count_nonzero(mask))  # summed over groups
                if n_rows:
                    with tr.span("sweep.fetch"):
                        ys_np, ids_np = np.asarray(ys_out), np.asarray(ids)
                    with tr.span("sweep.insert", rows=n_rows):
                        if self._portfolio:
                            for g, a in enumerate(archives):
                                mg = mask[:, g]
                                if mg.any():
                                    a.insert(ys_np[mg, g, :], ids=ids_np[mg])
                        else:
                            archives[0].insert(ys_np[mask], ids=ids_np[mask])
                # clamp to `stop`: ids beyond it were masked invalid, and a
                # later resume with a larger stop must re-visit them
                state["next"] = min(s + self.chunk_size, stop)
                state["carry"] = carry
                chunk_i += 1
                self._c_chunks.inc()
                self._h_chunk.observe(time.perf_counter() - t_chunk)
            if progress:
                done = min(state["next"], stop)
                # rate counts only ids swept in THIS process (resumed ids
                # were paid for in a previous one)
                here = int(carry["n_eval"]) - n_eval_resumed
                print(f"{label}sweep: {done:,}/{stop:,} ids  "
                      f"front={len(archives[-1])}  "
                      f"{here / max(time.perf_counter() - t0, 1e-9):,.0f} ids/s",
                      flush=True)
            if (checkpoint_path and checkpoint_every
                    and chunk_i % checkpoint_every == 0):
                self._save(checkpoint_path, state, fp_extra)
        if checkpoint_path:
            self._save(checkpoint_path, state, fp_extra)
        state["resumed"] = n_eval_resumed
        return state

    @staticmethod
    def _merge_topk_rows(states: List[Dict], key_val: str, key_id: str,
                         rows: int, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Stable span-order merge of per-worker running top-k row blocks
        (each worker contributes a (..., rows, k) carry, flattened)."""
        vals = np.concatenate(
            [np.asarray(st["carry"][key_val]).reshape(rows, k)
             for st in states], axis=1)
        cand = np.concatenate(
            [np.asarray(st["carry"][key_id]).reshape(rows, k)
             for st in states], axis=1)
        out_v = np.empty((rows, k), vals.dtype)
        out_i = np.empty((rows, k), cand.dtype)
        for r in range(rows):
            order = np.argsort(vals[r], kind="stable")[:k]
            out_v[r] = vals[r][order]
            out_i[r] = cand[r][order]
        return out_v, out_i

    def _merge_archives(self, archive_lists: List[List[ParetoArchive]],
                        g: int) -> Tuple[ParetoArchive, bool]:
        """Merge group g's archive across workers (exact host reduction)."""
        if len(archive_lists) == 1:
            a = archive_lists[0][g]
            return a, a.truncated
        archive = ParetoArchive(3, capacity=self.archive_capacity)
        truncated = False
        n_seen = 0
        for al in archive_lists:
            a = al[g]
            truncated |= a.truncated
            n_seen += a.n_seen
            if len(a):
                archive.insert(a.y, ids=a.ids)
        truncated |= archive.truncated
        archive.n_seen = n_seen
        archive.truncated = truncated
        return archive, truncated

    def _reduce_states(self, states: List[Dict],
                       seconds: float) -> SweepResult:
        """Merge worker states into one SweepResult.  The top-k merges are
        stable in span order, so ties resolve exactly as the single-process
        streaming reduction would."""
        if self._portfolio:
            return self._reduce_states_portfolio(states, seconds)
        resumed = sum(st.get("resumed", 0) for st in states)
        n_eval = sum(int(st["carry"]["n_eval"]) for st in states)
        n_super = sum(int(st["carry"]["n_super"]) for st in states)

        k = self.topk
        vals = np.concatenate(
            [np.asarray(st["carry"]["topk_val"]) for st in states], axis=1)
        cand = np.concatenate(
            [np.asarray(st["carry"]["topk_id"]) for st in states], axis=1)
        topk_val = np.empty((3, k), vals.dtype)
        topk_id = np.empty((3, k), cand.dtype)
        for o in range(3):
            order = np.argsort(vals[o], kind="stable")[:k]
            topk_val[o] = vals[o][order]
            topk_id[o] = cand[o][order]

        stall_val = stall_id = None
        if self.stall_topk:
            sk = self.stall_topk
            svals = np.concatenate(
                [np.asarray(st["carry"]["stall_topk_val"]) for st in states],
                axis=1)
            scand = np.concatenate(
                [np.asarray(st["carry"]["stall_topk_id"]) for st in states],
                axis=1)
            stall_val = np.empty((_N_STALL, sk), svals.dtype)
            stall_id = np.empty((_N_STALL, sk), scand.dtype)
            for c in range(_N_STALL):
                order = np.argsort(svals[c], kind="stable")[:sk]
                stall_val[c] = svals[c][order]
                stall_id[c] = np.where(np.isfinite(stall_val[c]),
                                       scand[c][order], -1)

        if len(states) == 1:
            archive: ParetoArchive = states[0]["archive"]
            truncated = archive.truncated
        else:
            archive = ParetoArchive(3, capacity=self.archive_capacity)
            truncated = False
            n_seen = 0
            for st in states:
                a: ParetoArchive = st["archive"]
                truncated |= a.truncated
                n_seen += a.n_seen
                if len(a):
                    archive.insert(a.y, ids=a.ids)
            truncated |= archive.truncated
            archive.n_seen = n_seen
            archive.truncated = truncated

        order = np.argsort(archive.ids, kind="stable")
        return SweepResult(
            n_evaluated=n_eval,
            n_superior=n_super,
            pareto_y=archive.y[order],
            pareto_ids=archive.ids[order],
            topk_val=topk_val,
            topk_ids=topk_id,
            ref_point=self.ref_point.copy(),
            seconds=seconds,
            # resumed runs only time the ids swept in *this* process
            points_per_sec=(n_eval - resumed) / max(seconds, 1e-9),
            archive_truncated=truncated,
            stall_topk_val=stall_val,
            stall_topk_ids=stall_id,
            archive_capacity=archive.capacity,
        )

    def _reduce_states_portfolio(self, states: List[Dict],
                                 seconds: float) -> SweepResult:
        """Portfolio merge: per-scenario results nested under the robust
        top-level result (the same stable span-order reduction per group)."""
        S, S1, k = len(self.scenarios), self._n_groups, self.topk
        resumed = sum(st.get("resumed", 0) for st in states)
        n_eval = sum(int(st["carry"]["n_eval"]) for st in states)
        n_super = np.sum([np.asarray(st["carry"]["n_super"])
                          for st in states], axis=0)
        topk_val, topk_id = self._merge_topk_rows(
            states, "topk_val", "topk_id", S1 * 3, k)
        topk_val = topk_val.reshape(S1, 3, k)
        topk_id = topk_id.reshape(S1, 3, k)
        stall_val = stall_id = None
        if self.stall_topk:
            sk = self.stall_topk
            stall_val, stall_id = self._merge_topk_rows(
                states, "stall_topk_val", "stall_topk_id", S * _N_STALL, sk)
            stall_id = np.where(np.isfinite(stall_val), stall_id, -1)
            stall_val = stall_val.reshape(S, _N_STALL, sk)
            stall_id = stall_id.reshape(S, _N_STALL, sk)
        archive_lists = [st["archives"] for st in states]
        pps = (n_eval - resumed) / max(seconds, 1e-9)

        def group_result(g: int, ref: np.ndarray, **extra) -> SweepResult:
            archive, truncated = self._merge_archives(archive_lists, g)
            order = np.argsort(archive.ids, kind="stable")
            return SweepResult(
                n_evaluated=n_eval, n_superior=int(n_super[g]),
                pareto_y=archive.y[order], pareto_ids=archive.ids[order],
                topk_val=topk_val[g], topk_ids=topk_id[g],
                ref_point=np.asarray(ref, dtype=np.float64).copy(),
                seconds=0.0, points_per_sec=0.0,
                archive_truncated=truncated,
                archive_capacity=archive.capacity, **extra)

        per = {s.name: group_result(
                   i, self.ref_points[i],
                   stall_topk_val=(stall_val[i] if self.stall_topk else None),
                   stall_topk_ids=(stall_id[i] if self.stall_topk else None))
               for i, s in enumerate(self.scenarios)}
        res = group_result(S, self.ref_point)
        res.seconds = seconds
        res.points_per_sec = pps
        res.scenario_names = tuple(s.name for s in self.scenarios)
        res.robust = self.robust
        res.per_scenario = per
        return res

    # ------------------------------------------------------------------
    def telemetry(self) -> dict:
        """Registry view of the engine's streaming counters."""
        return {
            "chunks": int(self._c_chunks.value()),
            "chunk_s": self._h_chunk.stats(),
        }

    # ------------------------------------------------------------------
    def _archives_of(self, state: Dict) -> List[ParetoArchive]:
        return state["archives"] if self._portfolio else [state["archive"]]

    def _save(self, path: str, state: Dict, fp_extra: str = "") -> None:
        """Atomic checkpoint write: the payload (plus a sha256 content
        digest) lands in a ``.tmp`` sibling and is published with
        ``os.replace`` — a kill mid-write leaves the previous checkpoint
        intact, never a truncated one."""
        archives = self._archives_of(state)
        extra = {}
        if self.stall_topk:
            extra["stall_topk_val"] = np.asarray(state["carry"]["stall_topk_val"])
            extra["stall_topk_id"] = np.asarray(state["carry"]["stall_topk_id"])
        for g, a in enumerate(archives[1:], start=1):
            # portfolio: scenario archives 1..S1-1 ride alongside the first
            extra[f"archive{g}_y"] = a.y
            extra[f"archive{g}_ids"] = a.ids
            extra[f"archive{g}_seen"] = a.n_seen
            extra[f"archive{g}_truncated"] = a.truncated
        if self._portfolio:
            # the robust ref [1, 1, area] alone cannot detect changed
            # latency refs (its latency entries are 1 by construction)
            extra["ref_points"] = self.ref_points
        payload = dict(
            version=_FMT_VERSION,
            fingerprint=self.fingerprint() + fp_extra,
            next=state["next"],
            n_super=np.asarray(state["carry"]["n_super"]),
            n_eval=np.asarray(state["carry"]["n_eval"]),
            topk_val=np.asarray(state["carry"]["topk_val"]),
            topk_id=np.asarray(state["carry"]["topk_id"]),
            archive_y=archives[0].y,
            archive_ids=archives[0].ids,
            archive_seen=archives[0].n_seen,
            archive_truncated=archives[0].truncated,
            ref_point=self.ref_point,
            **extra,
        )
        payload["digest"] = _state_digest(payload)
        fname = path if str(path).endswith(".npz") else f"{path}.npz"
        tmp = fname + ".tmp"
        # write through an open handle: np.savez would append another
        # ``.npz`` to a bare tmp path, breaking the replace pairing
        with open(tmp, "wb") as f:
            np.savez(f, **payload)
        os.replace(tmp, fname)

    @staticmethod
    def _quarantine(fname: str, reason: str) -> None:
        q = f"{fname}.quarantined"
        try:
            os.replace(fname, q)
        except OSError:
            q = "<could not rename>"
        warnings.warn(f"sweep checkpoint {fname} is corrupt ({reason}); "
                      f"quarantined to {q} — restarting the span fresh",
                      RuntimeWarning, stacklevel=3)

    def _load(self, path: str, fp_extra: str = "") -> Optional[Dict]:
        """Restore a checkpoint, or None after quarantining a corrupt /
        truncated file (config mismatches still raise: the file is VALID,
        resuming it would just be wrong)."""
        fname = path if str(path).endswith(".npz") else f"{path}.npz"
        try:
            with np.load(fname, allow_pickle=False) as zf:
                z = {k: np.asarray(zf[k]) for k in zf.files}
        except FileNotFoundError:
            raise
        except Exception as exc:
            self._quarantine(fname, f"unreadable: {exc}")
            return None
        if "digest" in z:          # pre-digest checkpoints stay loadable
            stored = str(z["digest"])
            body = {k: v for k, v in z.items() if k != "digest"}
            if _state_digest(body) != stored:
                self._quarantine(fname, "content digest mismatch")
                return None
        if int(z["version"]) > _FMT_VERSION:
            raise ValueError(
                f"checkpoint format v{int(z['version'])} is newer than this "
                f"build's v{_FMT_VERSION}; refusing to resume")
        if str(z["fingerprint"]) != self.fingerprint() + fp_extra:
            raise ValueError(
                "checkpoint was produced by a different space/workload "
                "configuration (or a different worker span); "
                "refusing to resume")
        if not np.allclose(np.asarray(z["ref_point"]), self.ref_point,
                           rtol=1e-6):
            raise ValueError(
                "checkpoint was produced with a different reference point; "
                "its superiority counts cannot be continued — refusing to "
                "resume")
        if self._portfolio:
            if "ref_points" not in z or not np.allclose(
                    np.asarray(z["ref_points"]), self.ref_points, rtol=1e-6):
                raise ValueError(
                    "checkpoint was produced with different per-scenario "
                    "reference points; its robust scalarization cannot be "
                    "continued — refusing to resume")

        def load_archive(prefix: str) -> ParetoArchive:
            a = ParetoArchive(3, capacity=self.archive_capacity)
            a.y = np.asarray(z[f"{prefix}_y"], dtype=np.float64)
            a.ids = np.asarray(z[f"{prefix}_ids"], dtype=np.int64)
            a.n_seen = int(z[f"{prefix}_seen"])
            a.truncated = bool(z[f"{prefix}_truncated"])
            if a.auto:
                a._peak = len(a)
                a.capacity = max(a.auto_floor,
                                 int(a.auto_headroom * a._peak))
            return a

        carry = {
            "n_super": jnp.asarray(z["n_super"]),
            "n_eval": jnp.asarray(z["n_eval"]),
            "topk_val": jnp.asarray(z["topk_val"]),
            "topk_id": jnp.asarray(z["topk_id"]),
        }
        if self._portfolio and carry["topk_val"].ndim != 3:
            raise ValueError("checkpoint is single-scenario but this engine "
                             "sweeps a portfolio; refusing to resume")
        if self.stall_topk:
            if "stall_topk_val" not in z:
                raise ValueError(
                    "checkpoint carries no per-stall-class top-k state but "
                    "this engine was built with stall_topk > 0; refusing to "
                    "resume")
            if z["stall_topk_val"].shape[-1] != self.stall_topk:
                raise ValueError(
                    "checkpoint stall_topk width differs from this engine's; "
                    "refusing to resume")
            carry["stall_topk_val"] = jnp.asarray(z["stall_topk_val"])
            carry["stall_topk_id"] = jnp.asarray(z["stall_topk_id"])
        if self._portfolio:
            archives = [load_archive("archive")]
            archives += [load_archive(f"archive{g}")
                         for g in range(1, self._n_groups)]
            return {"next": int(z["next"]), "carry": carry,
                    "archives": archives}
        return {"next": int(z["next"]), "carry": carry,
                "archive": load_archive("archive")}


# --------------------------------------------------------------------------
# persistent oracle store: SweepResult artifacts on disk
# --------------------------------------------------------------------------
# A full-space sweep costs seconds-to-minutes; its SweepResult (front,
# top-k tables, stall seeds, per-scenario nests) is a few MB.  The oracle
# store memoizes exactly that: save/load one SweepResult npz, digested
# and atomically written like the checkpoints above, so a repeat
# OracleEvaluator over the same (fingerprint, stop, knobs) key is an
# O(1) load instead of a re-sweep (see OracleEvaluator's oracle_store=).

ORACLE_STORE_VERSION = 1
DEFAULT_ORACLE_STORE = os.path.join("~", ".cache", "repro-oracle")

_RESULT_REQ = ("n_evaluated", "n_superior", "pareto_y", "pareto_ids",
               "topk_val", "topk_ids", "ref_point", "seconds",
               "points_per_sec", "archive_truncated")
_RESULT_OPT = ("stall_topk_val", "stall_topk_ids", "archive_capacity",
               "robust")


def _result_payload(res: SweepResult, prefix: str = "") -> Dict:
    out = {}
    for f in _RESULT_REQ:
        out[prefix + f] = np.asarray(getattr(res, f))
    for f in _RESULT_OPT:
        v = getattr(res, f)
        if v is not None:
            out[prefix + f] = np.asarray(v)
    if res.scenario_names is not None:
        out[prefix + "scenario_names"] = np.asarray(res.scenario_names)
    if res.per_scenario:
        # flatten scenario nests with positional prefixes (s0., s1., ...)
        for i, nm in enumerate(res.scenario_names):
            out.update(_result_payload(res.per_scenario[nm],
                                       prefix=f"{prefix}s{i}."))
    return out


def _result_from_payload(z: Dict, prefix: str = "") -> SweepResult:
    def opt(name, cast):
        key = prefix + name
        return cast(z[key]) if key in z else None

    names = None
    per = None
    if prefix + "scenario_names" in z:
        names = tuple(str(s) for s in np.asarray(z[prefix
                                                   + "scenario_names"]))
        if any(k.startswith(f"{prefix}s0.") for k in z):
            per = {nm: _result_from_payload(z, prefix=f"{prefix}s{i}.")
                   for i, nm in enumerate(names)}
    return SweepResult(
        n_evaluated=int(z[prefix + "n_evaluated"]),
        n_superior=int(z[prefix + "n_superior"]),
        pareto_y=np.asarray(z[prefix + "pareto_y"], dtype=np.float64),
        pareto_ids=np.asarray(z[prefix + "pareto_ids"], dtype=np.int64),
        topk_val=np.asarray(z[prefix + "topk_val"]),
        topk_ids=np.asarray(z[prefix + "topk_ids"]),
        ref_point=np.asarray(z[prefix + "ref_point"]),
        seconds=float(z[prefix + "seconds"]),
        points_per_sec=float(z[prefix + "points_per_sec"]),
        archive_truncated=bool(z[prefix + "archive_truncated"]),
        stall_topk_val=opt("stall_topk_val", np.asarray),
        stall_topk_ids=opt("stall_topk_ids", np.asarray),
        archive_capacity=opt("archive_capacity", int),
        robust=opt("robust", str),
        scenario_names=names,
        per_scenario=per,
    )


def save_sweep_result(path: str, result: SweepResult, *,
                      key: str = "") -> str:
    """Persist one SweepResult (atomic tmp + ``os.replace``, sha256
    content digest).  ``key`` ties the artifact to its producing
    configuration — loads with a different key refuse.  Returns the
    final filename."""
    payload = _result_payload(result)
    payload["store_version"] = np.asarray(ORACLE_STORE_VERSION)
    payload["oracle_key"] = np.asarray(key)
    payload["digest"] = _state_digest(payload)
    fname = path if str(path).endswith(".npz") else f"{path}.npz"
    os.makedirs(os.path.dirname(os.path.abspath(fname)), exist_ok=True)
    tmp = fname + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **payload)
    os.replace(tmp, fname)
    return fname


def load_sweep_result(path: str, *, key: str = "") -> SweepResult:
    """Load a stored SweepResult; raises ``ValueError`` on a corrupt,
    truncated, newer-format or key-mismatched file (callers quarantine
    and re-sweep)."""
    fname = path if str(path).endswith(".npz") else f"{path}.npz"
    try:
        with np.load(fname, allow_pickle=False) as zf:
            z = {k: np.asarray(zf[k]) for k in zf.files}
    except FileNotFoundError:
        raise
    except Exception as exc:
        raise ValueError(f"unreadable oracle artifact: {exc}") from exc
    stored = str(z.pop("digest", ""))
    if _state_digest(z) != stored:
        raise ValueError("oracle artifact content digest mismatch")
    if int(z["store_version"]) > ORACLE_STORE_VERSION:
        raise ValueError(
            f"oracle artifact format v{int(z['store_version'])} is newer "
            f"than this build's v{ORACLE_STORE_VERSION}")
    if key and str(z["oracle_key"]) != key:
        raise ValueError("oracle artifact belongs to a different "
                         "configuration key")
    return _result_from_payload(z)
