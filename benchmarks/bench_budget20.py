"""Paper §5.3 LLMCompass-budget experiment: 20 evaluations on the
high-fidelity (target) tier.  Paper: Lumina is the ONLY method that finds
designs beating the A100 — six of them; every black-box baseline finds zero.

All methods run through the unified Evaluator API (one fused jitted dispatch
per DSE step; the emitted ``LUMINA_dispatches_per_eval`` counter verifies
it).  PHV is reported oracle-normalized against the exhaustive compass-tier
sweep front.
"""
from __future__ import annotations

from typing import List

import numpy as np

from repro.core.baselines import METHODS, run_method
from repro.core.loop import LuminaDSE
from repro.perfmodel import get_evaluator
from repro.perfmodel.designspace import SPACE, A100_REFERENCE


def run(budget: int = 20, trials: int = 3) -> List[str]:
    target = get_evaluator("target")
    proxy = get_evaluator("proxy")
    oracle = get_evaluator("oracle", oracle_base="target")   # ground truth

    ref = target.objectives(SPACE.encode_nearest(A100_REFERENCE)[None, :])[0]
    lines = []
    for name, cls in METHODS.items():
        sups = [run_method(cls, target, budget, ref, seed=t).superior_count
                for t in range(trials)]
        lines.append(f"budget20,{name}_superior_mean,{np.mean(sups):.1f}")
    sups, phvs, disp = [], [], []
    for t in range(trials):
        d0 = target.dispatches
        res = LuminaDSE(target, proxy=proxy, seed=t).run(budget=budget)
        disp.append((target.dispatches - d0) / budget)
        sups.append(res.superior_count)
        phvs.append(res.phv)
    lines.append(f"budget20,LUMINA_superior_mean,{np.mean(sups):.1f}")
    lines.append(f"budget20,LUMINA_superior_min,{min(sups)}")
    lines.append(f"budget20,LUMINA_phv_frac_of_oracle,"
                 f"{oracle.normalized_phv(np.mean(phvs), ref):.4f}")
    lines.append(f"budget20,LUMINA_dispatches_per_eval,{np.mean(disp):.2f}")
    lines.append("budget20,paper_claim,LUMINA>=6_baselines=0")
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
