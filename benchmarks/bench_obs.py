"""Observability overhead benchmark + traced-fleet smoke.

Two claims, both ASSERTED (not just reported):

* **always-on-cheap** — the full dispatch tick path costs < 3% extra
  with a real :class:`~repro.obs.Tracer` attached vs the default
  :data:`~repro.obs.NOOP` tracer (``obs,traced_overhead_pct``), and the
  no-op span itself is sub-microsecond while no profiler records
  (``obs,noop_span_ns``);
* **one causal tree across machines** — a ``Gateway.evaluate`` against
  two SPAWNED worker processes, with a chaos crash injected on the
  first dispatch and one worker SIGKILLed between requests, still
  exports a schema-valid, structurally complete Perfetto trace (one
  root per trace, no dangling parents, every failed attempt closed
  ``error``/``lost``).  The trace JSON is written to
  ``obs_trace.json`` (override with ``REPRO_OBS_TRACE``) so CI can
  upload it as an artifact.
"""
from __future__ import annotations

import os
import time
from typing import List

import numpy as np

from repro.distributed import EvalService, ShardedEvaluator
from repro.distributed.faults import FaultEvent, FaultPlan
from repro.obs import (NOOP, Tracer, completeness_errors, trace_events,
                       validate_trace_events, write_trace)
from repro.perfmodel import EvalRequest, ModelEvaluator, get_evaluator
from repro.perfmodel.designspace import SPACE
from repro.serve import Gateway, start_worker_process


def _fresh(tier: str = "proxy") -> ModelEvaluator:
    return ModelEvaluator(get_evaluator(tier).models, tier=tier)


def _timed(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(smoke: bool = False, full: bool = False) -> List[str]:
    lines: List[str] = []
    rng = np.random.default_rng(0)

    # ---- no-op span microbench ---------------------------------------
    n = 50_000 if smoke else 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with NOOP.span("x"):
            pass
    noop_ns = (time.perf_counter() - t0) / n * 1e9
    lines.append(f"obs,noop_span_ns,{noop_ns:.0f}")
    assert noop_ns < 5_000, f"no-op span costs {noop_ns:.0f}ns"

    # ---- tick-path overhead: traced vs NOOP --------------------------
    rows = 256 if smoke else 512
    repeats = 5 if smoke else 9
    req = EvalRequest(SPACE.sample(rng, rows), detail="stalls")

    base_svc = EvalService(_fresh())           # default tracer: NOOP
    base_svc.evaluate(req)                     # warm caches + compiles
    t_base = _timed(lambda: base_svc.evaluate(
        EvalRequest(SPACE.sample(rng, rows), detail="stalls")), repeats)
    base_svc.close()

    tr = Tracer(proc="bench")
    traced_svc = EvalService(_fresh(), tracer=tr)
    traced_svc.evaluate(req)
    t_traced = _timed(lambda: (
        traced_svc.evaluate(
            EvalRequest(SPACE.sample(rng, rows), detail="stalls")),
        tr.drain()), repeats)
    traced_svc.close()

    overhead = 100.0 * (t_traced - t_base) / max(t_base, 1e-9)
    lines.append(f"obs,tick_noop_ms,{t_base * 1e3:.2f}")
    lines.append(f"obs,tick_traced_ms,{t_traced * 1e3:.2f}")
    lines.append(f"obs,traced_overhead_pct,{overhead:.2f}")
    assert overhead < 3.0, f"tracing costs {overhead:.1f}% on the tick path"

    # ---- traced fleet smoke: chaos crash + SIGKILL, one tree ---------
    w1 = start_worker_process()
    w2 = start_worker_process()
    tr = Tracer(proc="client")
    try:
        plan = FaultPlan([FaultEvent(0, 0, "crash")])
        sock = ShardedEvaluator(_fresh(), mode="socket",
                                addresses=[w1.address, w2.address],
                                fault_plan=plan, elastic=True,
                                speculate=False, shard_timeout_s=10.0,
                                tracer=tr)
        gw = Gateway(EvalService(sock, tracer=tr), tracer=tr)
        batch = SPACE.sample(rng, 64 if smoke else 256)
        gw.evaluate(EvalRequest(batch, detail="stalls"), tenant="bench")
        w2.kill()                              # SIGKILL, no goodbye
        gw.evaluate(EvalRequest(batch, detail="stalls"), tenant="bench")

        spans = tr.spans()
        struct = completeness_errors(spans)
        assert struct == [], struct
        obj = trace_events(spans)
        schema = validate_trace_events(obj)
        assert schema == [], schema
        roots = [s for s in spans if s.parent_id is None]
        workers = {s.proc for s in spans if s.name == "worker.eval"}
        failed = [s for s in spans if s.status in ("error", "lost")]
        lines.append(f"obs,smoke_spans,{len(spans)}")
        lines.append(f"obs,smoke_traces,{len(roots)}")
        lines.append(f"obs,smoke_worker_procs,{len(workers)}")
        lines.append(f"obs,smoke_failed_attempts,{len(failed)}")
        assert len(roots) == 2                 # one tree per evaluate
        assert all(r.name == "gateway.evaluate" for r in roots)
        assert workers, "no worker spans crossed the wire"
        assert failed, "chaos + SIGKILL left no error/lost spans"

        out = os.environ.get("REPRO_OBS_TRACE", "obs_trace.json")
        write_trace(out, spans)
        lines.append(f"obs,trace_artifact,{out}")
        lines.append("obs,smoke_tree_complete,1")
        gw.close()
    finally:
        for w in (w1, w2):
            if w.alive():
                w.kill()
    return lines


if __name__ == "__main__":
    for line in run(smoke=True):
        print(line)
