"""Portfolio sweep: score the design space against the whole workload zoo.

One streaming pass over a slice of the 4.7M-point space evaluates every
assigned architecture config (10 scenarios, 20 stacked workloads) at once:
per-scenario Pareto fronts + stall-class seeds, plus the robust front under
worst-case scalarization — then a bottleneck-seeded DSE campaign targets
ONE scenario's stall classes.

    PYTHONPATH=src python examples/portfolio_sweep.py
"""
from repro.core.campaign import CampaignRunner
from repro.perfmodel import get_evaluator
from repro.perfmodel.sweep import SweepEngine
from repro.runtime.chip import enable_compile_cache

STOP = 150_000          # slice of the 4,741,632-design space (demo scale)


def main() -> None:
    enable_compile_cache()
    zoo = get_evaluator("proxy", suite="zoo")
    print(f"zoo suite: {len(zoo.scenarios)} scenarios, "
          f"{len(zoo.workloads)} stacked workloads")

    eng = SweepEngine(zoo, stall_topk=4, archive_capacity="auto")
    res = eng.run(0, STOP, progress=True)
    print(f"\nswept {res.n_evaluated:,} designs in {res.seconds:.1f}s "
          f"({res.points_per_sec:,.0f} ids/s, robust={res.robust!r})")
    print(f"robust front: {len(res.pareto_ids)} designs "
          f"({res.n_superior} beat the A100 on EVERY scenario)")
    for name in res.scenario_names:
        r = res.scenario(name)
        seeds = res.stall_seeds(scenario=name)
        classes = [c for c, v in seeds.items() if len(v)]
        print(f"  {name:24s} front={len(r.pareto_ids):4d} "
              f"superior={r.n_superior:4d} stall classes={classes}")

    # bottleneck-seeded campaigns for one scenario class
    scen = res.scenario_names[0]
    runner = CampaignRunner(zoo, proxy=zoo, scenario=scen, seed=0)
    out = runner.run(budget=12, seeds=res.stall_seeds(scenario=scen))
    print(f"\nscenario {scen!r} campaigns: {sorted(out.per_campaign)}")
    print(f"  {len(out.samples)} evaluations in {out.rounds} fused rounds "
          f"({out.dispatches} dispatches), PHV={out.phv:.3e}")


if __name__ == "__main__":
    main()
