"""Sweep-seeded multi-campaign DSE: K parallel Lumina campaigns started
from the full-space sweep's per-stall-class best designs, sharing one
budget and one fused batched dispatch per round, with per-step regret
telemetry against the exhaustive oracle front.

    PYTHONPATH=src python examples/seeded_campaigns.py --budget 20 \
        [--sweep-stop 200000] [--telemetry campaigns.json]
"""
import argparse

import numpy as np

from repro.core.campaign import CampaignRunner
from repro.perfmodel import ModelEvaluator, OracleEvaluator, get_evaluator
from repro.perfmodel.designspace import SPACE
from repro.runtime.chip import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=20)
    ap.add_argument("--seeds-per-campaign", type=int, default=1)
    ap.add_argument("--sweep-stop", type=int, default=None,
                    help="sweep only ids [0, stop) (default: full 4.7M space)")
    ap.add_argument("--telemetry", default=None,
                    help="write the per-step regret/PHV JSON series here")
    args = ap.parse_args()

    ev = get_evaluator("proxy")
    oracle = OracleEvaluator(ev, stop=args.sweep_stop,
                             sweep_kwargs=dict(stall_topk=16,
                                               stall_rank="ref"))
    sweep = oracle.sweep_result()        # one sweep: seeds AND ground truth
    seeds = sweep.stall_seeds()
    print("sweep:", sweep.n_evaluated, "designs,",
          {k: len(v) for k, v in seeds.items()}, "seeds/class")

    # acquisition runs on its own proxy instance so the dispatch report
    # below counts only the budgeted fused dispatches
    runner = CampaignRunner(ev, proxy=ModelEvaluator(ev.models),
                            oracle=oracle, seed=0,
                            seeds_per_campaign=args.seeds_per_campaign)
    res = runner.run(budget=args.budget, sweep=sweep)

    print(f"\n{len(res.per_campaign)} campaigns, {len(res.samples)} evals in "
          f"{res.rounds} rounds / {res.dispatches} fused dispatches")
    print(f"merged: {res.superior_count} A100-superior designs, "
          f"PHV fraction of oracle {res.phv_frac_curve()[-1]:.3f}, "
          f"final regret {np.round(res.regret_curve()[-1], 3)}")
    for label, r in sorted(res.per_campaign.items()):
        print(f"  {label:16s} evals={len(r.samples):3d} "
              f"superior={r.superior_count:3d} phv={r.phv:.3g}")
    best = res.pareto[0]
    print("\nbest merged design:", dict(
        (k, int(v)) for k, v in SPACE.decode_np(best.idx).items()))
    if args.telemetry:
        res.save_telemetry(args.telemetry)
        print("telemetry ->", args.telemetry)


if __name__ == "__main__":
    main()
