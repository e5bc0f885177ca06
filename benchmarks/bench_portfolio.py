"""Portfolio benchmark: the whole workload zoo in ~one-pass wall clock.

Three measurements behind the PR's acceptance bar:

* **W-scaling curve** — fused-dispatch latency of the evaluator as the
  stacked workload count W grows (2 -> 20): near-flat in W because the
  op-term model runs once over the deduped union (``zoo_ops_*`` lines
  report the union-vs-concat op counts).
* **Portfolio sweep vs paper sweep** — the same id range swept with the
  2-workload paper evaluator and with the full zoo suite (10 scenarios,
  20 workloads, per-scenario fronts + stall seeds + robust front);
  ``zoo_vs_paper_ratio`` is the acceptance metric (must be <= 2x).
* **Robust vs per-scenario fronts** — how much the ``robust="worst"`` /
  ``"geomean"`` fronts overlap each scenario's own front, and how many
  designs beat the A100 on EVERY scenario at once (the robust superiority
  count) — the portfolio answer a per-workload sweep cannot give.

``smoke=True`` (CI) truncates the sweeps to a 200k-id range and thins the
W axis.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from repro.perfmodel import (ModelEvaluator, get_evaluator, zoo_suite)
from repro.perfmodel.designspace import SPACE
from repro.perfmodel.sweep import SweepEngine
from repro.perfmodel.workload import WorkloadStack


def _time_dispatch(ev, idx, repeats: int = 3) -> float:
    ev.objectives(idx)                       # compile + warm
    t0 = time.perf_counter()
    for _ in range(repeats):
        ev.objectives(idx)
    return (time.perf_counter() - t0) / repeats


def run(full: bool = False, smoke: bool = False) -> List[str]:
    lines = []
    wls, scenarios = zoo_suite()
    stack = WorkloadStack.build(wls)
    lines.append(f"portfolio,zoo_workloads,{len(wls)}")
    lines.append(f"portfolio,zoo_ops_concat,{stack.total_ops}")
    lines.append(f"portfolio,zoo_ops_unique,{stack.n_unique}")

    # ---- W-scaling: fused-dispatch latency against the workload count ----
    names = list(wls)
    idx = SPACE.sample(np.random.default_rng(0), 4096)
    w_axis = (2, 8, 20) if smoke else (2, 4, 8, 12, 16, 20)
    base_ms = None
    for w in w_axis:
        sub = {nm: wls[nm] for nm in names[:w]}
        from repro.perfmodel.roofline import RooflineModel
        models = {nm: RooflineModel(wl) for nm, wl in sub.items()}
        ms_stacked = _time_dispatch(ModelEvaluator(models), idx) * 1e3
        if base_ms is None:
            base_ms = ms_stacked
        lines.append(f"portfolio,stacked_w{w}_ms,{ms_stacked:.2f}")
        lines.append(f"portfolio,stacked_w{w}_vs_w2,"
                     f"{ms_stacked / max(base_ms, 1e-9):.2f}")

    # ---- the acceptance sweep: zoo portfolio vs 2-workload paper ----
    stop = 200_000 if smoke else (None if full else 600_000)
    paper = SweepEngine(get_evaluator("proxy"), stall_topk=8)
    t0 = time.perf_counter()
    paper_res = paper.run(0, stop)
    paper_s = time.perf_counter() - t0
    lines.append(f"portfolio,paper_sweep_seconds,{paper_s:.2f}")
    lines.append(f"portfolio,paper_points_per_sec,"
                 f"{paper_res.points_per_sec:.0f}")

    zoo_ev = get_evaluator("proxy", suite="zoo")
    eng = SweepEngine(zoo_ev, stall_topk=4, archive_capacity="auto")
    t0 = time.perf_counter()
    res = eng.run(0, stop)
    zoo_s = time.perf_counter() - t0
    lines.append(f"portfolio,zoo_scenarios,{len(res.scenario_names)}")
    lines.append(f"portfolio,zoo_sweep_seconds,{zoo_s:.2f}")
    lines.append(f"portfolio,zoo_points_per_sec,{res.points_per_sec:.0f}")
    ratio = zoo_s / max(paper_s, 1e-9)
    lines.append(f"portfolio,zoo_vs_paper_ratio,{ratio:.2f}")
    lines.append(f"portfolio,zoo_vs_paper_ratio_ok,{int(ratio <= 2.0)}")
    lines.append(f"portfolio,robust_front_size,{len(res.pareto_ids)}")
    lines.append(f"portfolio,robust_superior_to_a100,{res.n_superior}")
    lines.append(f"portfolio,auto_archive_capacity,{res.archive_capacity}")

    # ---- the one-pass claim: vs S sequential per-scenario pair sweeps
    # (what scoring the zoo costs WITHOUT the portfolio engine; smoke
    # samples 3 scenarios and extrapolates to keep CI short) ----
    from repro.perfmodel import pair_view
    seq_scen = res.scenario_names[:3] if smoke else res.scenario_names
    seq_s = 0.0
    for s in zoo_ev.scenarios:
        if s.name not in seq_scen:
            continue
        pev = pair_view(zoo_ev, (s.prefill, s.decode))
        t0 = time.perf_counter()
        SweepEngine(pev, stall_topk=4).run(0, stop)
        seq_s += time.perf_counter() - t0
    seq_s *= len(res.scenario_names) / len(seq_scen)
    lines.append(f"portfolio,sequential_pair_sweeps_seconds,{seq_s:.2f}")
    lines.append(f"portfolio,zoo_vs_sequential_ratio,"
                 f"{zoo_s / max(seq_s, 1e-9):.2f}")

    # ---- robust vs per-scenario fronts ----
    robust_ids = set(int(i) for i in res.pareto_ids)
    for nm in res.scenario_names:
        r = res.scenario(nm)
        overlap = len(robust_ids & set(int(i) for i in r.pareto_ids))
        lines.append(f"portfolio,front_{nm},{len(r.pareto_ids)}")
        lines.append(f"portfolio,front_{nm}_robust_overlap,{overlap}")
        lines.append(f"portfolio,superior_{nm},{r.n_superior}")
        seeds = res.stall_seeds(scenario=nm)
        nonempty = sum(1 for v in seeds.values() if len(v))
        lines.append(f"portfolio,stall_classes_{nm},{nonempty}")

    # worst-case vs geometric-mean scalarization of the same space slice
    geo = SweepEngine(zoo_ev, robust="geomean",
                      archive_capacity="auto").run(0, stop)
    shared = len(robust_ids & set(int(i) for i in geo.pareto_ids))
    lines.append(f"portfolio,geomean_front_size,{len(geo.pareto_ids)}")
    lines.append(f"portfolio,geomean_worst_overlap,{shared}")
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
