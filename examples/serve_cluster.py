"""DSE-as-a-service: an authenticated 2-worker cluster behind the Gateway.

Spawns two ``repro.serve`` worker daemons on localhost sharing an HMAC
keyring, has them announce themselves to a membership registrar (no
static address list), points a socket-mode ShardedEvaluator at the live
membership view (bit-identical to in-process over the signed binary
codec), injects chaos (a crashed and a hung dispatch) to show the retry
path, then runs a bottleneck-seeded campaign THROUGH the
admission-controlled gateway — QoS-tiered coalescing, per-tenant
budgets, fleet telemetry down to the lease table — and finally SIGKILLs
a worker mid-service to show elastic survival (its lease ages out; the
pool disables the slot).

    PYTHONPATH=src python examples/serve_cluster.py [--budget 10]

In production the workers run on other machines
(``python -m repro.serve.worker --host 0.0.0.0 --port 9707
--key fleet=... --registrar gateway:9700``) and nothing below changes:
discovery is the registrar, trust is the keyring.
"""
import argparse
import json

import numpy as np

from repro.core.campaign import CampaignRunner
from repro.distributed import (EvalService, FaultEvent, FaultPlan,
                               ShardedEvaluator)
from repro.perfmodel import EvalRequest, ModelEvaluator, get_evaluator
from repro.perfmodel.designspace import SPACE
from repro.runtime.chip import enable_compile_cache
from repro.serve import (Gateway, Keyring, MembershipView, Registrar,
                         WorkerOptions, start_worker_process)

KEYS = {"fleet": b"demo-cluster-secret"}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=10)
    args = ap.parse_args()
    rng = np.random.default_rng(0)
    ring = Keyring(KEYS)

    # ---- 1. the fleet: registrar + two authenticated workers ---------
    view = MembershipView(ttl_s=2.0)
    registrar = Registrar(view, keyring=ring).start()
    opts = WorkerOptions(keys=KEYS, registrar=registrar.address,
                         announce_interval_s=0.2,
                         max_rows_per_dispatch=4_096)
    w1 = start_worker_process(options=opts)
    w2 = start_worker_process(options=opts)
    view.wait_for(2)
    print(f"fleet: {len(view)} workers under lease -> {view.live()}")

    # ---- 2. socket fabric: signed codec, bit-identical ---------------
    local = ModelEvaluator(get_evaluator("proxy").models)
    batch = SPACE.sample(rng, 512)
    remote = ShardedEvaluator(ModelEvaluator(get_evaluator("proxy").models),
                              mode="socket", membership=view,
                              keyring=ring, elastic=True)
    a = local.evaluate(EvalRequest(batch, detail="stalls"))
    b = remote.evaluate(EvalRequest(batch, detail="stalls"))
    same = all(np.array_equal(a.latency[w], b.latency[w])
               for w in a.workloads) and np.array_equal(a.area, b.area)
    print(f"socket x2 (HMAC codec): {batch.shape[0]} designs, "
          f"bit-identical={same}, "
          f"worker dispatches={remote.worker_dispatches}")

    # ---- 3. chaos over the wire: crash + hang, same report -----------
    plan = FaultPlan([FaultEvent(0, 0, "crash"), FaultEvent(1, 1, "hang")])
    chaos = ShardedEvaluator(ModelEvaluator(get_evaluator("proxy").models),
                             mode="socket", membership=view, keyring=ring,
                             fault_plan=plan, shard_timeout_s=1.0,
                             speculate=False)
    c = chaos.evaluate(EvalRequest(batch, detail="stalls"))
    same = all(np.array_equal(a.latency[w], c.latency[w])
               for w in a.workloads)
    print(f"chaos: crash+hang injected, retried={chaos.retried}, "
          f"bit-identical={same}, plan drained={len(plan) == 0}")
    chaos.close()

    # ---- 4. a campaign through the admission-controlled gateway ------
    service = EvalService(remote)
    gateway = Gateway(service, rows_per_window=5_000, max_queued_rows=512)
    proxy = ModelEvaluator(get_evaluator("proxy").models)
    runner = CampaignRunner(service, proxy=proxy, seed=0, policy="adaptive")
    seeds = {"memory_bw": SPACE.sample(rng, 2),
             "compute": SPACE.sample(rng, 2)}
    res = runner.run(budget=args.budget, seeds=seeds)
    print(f"campaigns via gateway fleet: {len(res.per_campaign)} campaigns, "
          f"{len(res.samples)} evals in {res.rounds} rounds, "
          f"weights={res.budget_weights}")
    leases = gateway.telemetry()["fleet"]["leases"]
    print(f"leases: {json.dumps(leases, indent=1, default=str)}")

    # ---- 5. SIGKILL a worker; its lease lapses, service survives -----
    w2.kill()
    view.wait_for(1)                      # (already true; TTL ages w2 out)
    fut = gateway.submit(EvalRequest(SPACE.sample(rng, 64)), tenant="demo")
    while not fut.done():
        gateway.tick()
    fut.result()
    tel = gateway.telemetry()
    print(f"post-kill: leases={sorted(tel['fleet']['leases'])}, "
          f"fleet live={tel['fleet']['live']}, "
          f"admitted={tel['admission']['admitted']}")
    print("telemetry:", json.dumps(
        {"tiers": tel["service"]["tiers"], "tenants": tel["tenants"]},
        indent=1, default=str))

    gateway.close()
    remote.close()
    if w1.alive():
        w1.kill()
    if w2.alive():
        w2.kill()
    registrar.close()


if __name__ == "__main__":
    main()
