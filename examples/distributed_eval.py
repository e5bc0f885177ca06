"""Distributed evaluation: a 2-worker sharded sweep plus two concurrent
DSE campaign sets coalescing through ONE EvalService.

The sharded evaluator fans each EvalRequest's design batch across N
workers (bit-identical report); the sweep engine shards its id range the
same way; and the EvalService merges every client's concurrent requests
into one fused dispatch per tick with a shared cross-client report cache.

    PYTHONPATH=src python examples/distributed_eval.py \
        [--workers 2] [--budget 12] [--sweep-stop 400000] [--mode thread]
"""
import argparse

import numpy as np

from repro.core.campaign import CampaignRunner
from repro.distributed import EvalService, ShardedEvaluator
from repro.perfmodel import EvalRequest, ModelEvaluator, get_evaluator
from repro.perfmodel.designspace import SPACE
from repro.perfmodel.sweep import SweepEngine
from repro.runtime.chip import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--mode", default="thread",
                    choices=["thread", "process", "device"])
    ap.add_argument("--budget", type=int, default=12)
    ap.add_argument("--sweep-stop", type=int, default=400_000,
                    help="sweep only ids [0, stop) (keeps the demo quick)")
    args = ap.parse_args()

    # ---- 1. sharded evaluator: one request, N workers, same report ----
    local = ModelEvaluator(get_evaluator("proxy").models)
    sharded = ShardedEvaluator(ModelEvaluator(get_evaluator("proxy").models),
                               workers=args.workers, mode=args.mode)
    batch = SPACE.sample(np.random.default_rng(0), 4_096)
    a = local.evaluate(EvalRequest(batch, detail="stalls"))
    b = sharded.evaluate(EvalRequest(batch, detail="stalls"))
    same = all(np.array_equal(a.latency[w], b.latency[w])
               for w in local.workloads) and np.array_equal(a.area, b.area)
    print(f"sharded x{args.workers} ({sharded.mode}): "
          f"{batch.shape[0]} designs, bit-identical={same}, "
          f"worker dispatches={sharded.worker_dispatches}")

    # ---- 2. the sweep shards its id range across the same worker count ----
    eng = SweepEngine(get_evaluator("proxy"), stall_topk=8, stall_rank="ref")
    sweep = eng.run(0, args.sweep_stop, workers=args.workers)
    print(f"sweep x{args.workers}: {sweep.n_evaluated:,} ids, "
          f"front={len(sweep.pareto_ids)}, "
          f"{sweep.points_per_sec:,.0f} ids/s, "
          f"superior-to-A100={sweep.n_superior:,}")

    # ---- 3. two campaign sets through ONE coalescing service ----
    service = EvalService(ModelEvaluator(get_evaluator("proxy").models))
    proxy = ModelEvaluator(get_evaluator("proxy").models)
    for policy in ("uniform", "adaptive"):
        runner = CampaignRunner(service, proxy=proxy, seed=0, policy=policy)
        res = runner.run(budget=args.budget, sweep=sweep)
        weights = ("" if res.budget_weights is None else
                   ", weights=" + "/".join(
                       f"{lb}:{w:.2f}"
                       for lb, w in sorted(res.budget_weights.items())))
        print(f"campaigns[{policy}]: {len(res.per_campaign)} campaigns, "
              f"{len(res.samples)} evals in {res.rounds} rounds / "
              f"{res.dispatches} fused dispatches{weights}")
    print(f"service: {service.submits} requests -> "
          f"{service.fused_dispatches} fused dispatches, "
          f"{service.cache_hits} cross-client cache hits")


if __name__ == "__main__":
    main()
