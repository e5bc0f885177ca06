"""Observability end to end: one causal trace across client and workers.

Spawns two ``repro.serve`` worker daemons, attaches one
:class:`repro.obs.Tracer` to the whole dispatch path (Gateway ->
EvalService -> ShardedEvaluator -> SocketPool -> wire -> worker), runs a
request with a chaos crash injected and another after SIGKILLing a
worker, then prints the causal span tree, validates it structurally,
and writes a Perfetto/Chrome-traceable JSON plus a metrics snapshot.

    PYTHONPATH=src python examples/traced_service.py

Open ``traced_service.json`` at https://ui.perfetto.dev to see the
client spans and the adopted worker spans on separate process lanes,
re-parented into one tree per request.
"""
import json

import numpy as np

from repro.distributed import (EvalService, FaultEvent, FaultPlan,
                               ShardedEvaluator)
from repro.obs import (Tracer, completeness_errors, render_tree,
                       trace_events, validate_trace_events, write_trace)
from repro.perfmodel import EvalRequest, ModelEvaluator, get_evaluator
from repro.perfmodel.designspace import SPACE
from repro.runtime.chip import enable_compile_cache
from repro.serve import Gateway, start_worker_process


def main() -> None:
    enable_compile_cache()
    rng = np.random.default_rng(0)
    w1 = start_worker_process()
    w2 = start_worker_process()
    print(f"fleet: workers at {w1.address} and {w2.address}")

    # one tracer threads through every layer; workers get the trace
    # context on the wire and ship their spans back in the result frame
    tracer = Tracer(proc="client")
    sharded = ShardedEvaluator(
        ModelEvaluator(get_evaluator("proxy").models),
        mode="socket", addresses=[w1.address, w2.address],
        fault_plan=FaultPlan([FaultEvent(0, 0, "crash")]),
        elastic=True, speculate=False, tracer=tracer)
    gw = Gateway(EvalService(sharded, tracer=tracer), tracer=tracer)

    batch = SPACE.sample(rng, 256)
    gw.evaluate(EvalRequest(batch, detail="stalls"), tenant="demo")
    print("request 1 done (chaos crash on the first dispatch, retried)")
    w2.kill()
    # a FRESH batch (the coalescing cache would swallow a repeat)
    gw.evaluate(EvalRequest(SPACE.sample(rng, 256), detail="stalls"),
                tenant="demo")
    print("request 2 done (one worker SIGKILLed, fleet degraded to 1)")

    spans = tracer.spans()
    assert completeness_errors(spans) == [], "causal tree incomplete"
    assert validate_trace_events(trace_events(spans)) == []
    print(f"\ncausal tree ({len(spans)} spans; '!'=error, '?'=lost):")
    print(render_tree(spans))

    write_trace("traced_service.json", spans)
    print("Perfetto trace -> traced_service.json")

    # the same registry feeds the fleet dashboard and flat exports
    tel = gw.telemetry()
    print("\nfleet telemetry:", json.dumps(tel.get("fleet", {}), indent=2,
                                           default=str))
    gw.save_snapshot("traced_service_metrics.json")
    print("metrics snapshot -> traced_service_metrics.json "
          "(render: python -m repro.obs.report traced_service_metrics.json)")

    gw.close()
    for w in (w1, w2):
        if w.alive():
            w.kill()


if __name__ == "__main__":
    main()
