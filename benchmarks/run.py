"""Benchmark driver — one module per paper table/figure.

Prints ``name,metric,value[,derived]`` CSV lines.  Default scale is tuned
for CI (~10 min on this CPU container); pass --full for the paper-scale
suite (308-question benchmark, 1000-sample campaigns).

    PYTHONPATH=src python -m benchmarks.run [--full|--smoke] [--only table3,...]
"""
from __future__ import annotations

import argparse
import time
import traceback

from repro.runtime.chip import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: truncate the sweep bench's throughput "
                         "pass to a 600k-id range, run fig4/5 at quick "
                         "scale, and drop budget20/ablation to one trial "
                         "(oracle-PHV normalization still sweeps the full "
                         "space once — a few seconds)")
    ap.add_argument("--only", default=None,
                    help="comma list: table3,fig45,fig6,budget20,table4,"
                         "sweep,campaigns,portfolio,distributed,faults,"
                         "service,secure,obs,kernels,archs,ablation")
    args = ap.parse_args()
    if args.full and args.smoke:
        raise SystemExit("--full and --smoke are mutually exclusive")
    only = set(args.only.split(",")) if args.only else None

    benches = []
    if only is None or "table3" in only:
        from benchmarks import bench_dse_benchmark
        benches.append(("table3",
                        lambda: bench_dse_benchmark.run(quick=not args.full)))
    if only is None or "fig45" in only:
        from benchmarks import bench_dse_methods
        benches.append(("fig4/5", lambda: bench_dse_methods.run(
            budget=1000 if args.full else 300,
            trials=5 if args.full else 3,
            quick=args.smoke)))
    if only is None or "fig6" in only:
        from benchmarks import bench_search_pattern
        benches.append(("fig6", bench_search_pattern.run))
    if only is None or "budget20" in only:
        from benchmarks import bench_budget20
        benches.append(("budget20", lambda: bench_budget20.run(
            trials=1 if args.smoke else 3)))
    if only is None or "table4" in only:
        from benchmarks import bench_top_designs
        benches.append(("table4", bench_top_designs.run))
    if only is None or "sweep" in only:
        from benchmarks import bench_sweep
        benches.append(("sweep", lambda: bench_sweep.run(full=args.full,
                                                         smoke=args.smoke)))
    if only is None or "campaigns" in only:
        from benchmarks import bench_campaigns
        benches.append(("campaigns",
                        lambda: bench_campaigns.run(smoke=args.smoke)))
    if only is None or "portfolio" in only:
        from benchmarks import bench_portfolio
        benches.append(("portfolio",
                        lambda: bench_portfolio.run(full=args.full,
                                                    smoke=args.smoke)))
    if only is None or "distributed" in only:
        from benchmarks import bench_distributed
        benches.append(("distributed",
                        lambda: bench_distributed.run(smoke=args.smoke)))
    if only is None or "faults" in only:
        from benchmarks import bench_faults
        benches.append(("faults",
                        lambda: bench_faults.run(smoke=args.smoke)))
    if only is None or "service" in only:
        from benchmarks import bench_service
        benches.append(("service",
                        lambda: bench_service.run(smoke=args.smoke,
                                                  full=args.full)))
    if only is None or "secure" in only:
        from benchmarks import bench_secure
        benches.append(("secure",
                        lambda: bench_secure.run(smoke=args.smoke,
                                                 full=args.full)))
    if only is None or "obs" in only:
        from benchmarks import bench_obs
        benches.append(("obs", lambda: bench_obs.run(smoke=args.smoke,
                                                     full=args.full)))
    if only is None or "kernels" in only:
        from benchmarks import bench_kernels
        benches.append(("kernels", bench_kernels.run))
    if only is None or "archs" in only:
        from benchmarks import bench_arch_workloads
        benches.append(("archs", bench_arch_workloads.run))
    if only is None or "ablation" in only:
        from benchmarks import bench_ablations
        benches.append(("ablation", lambda: bench_ablations.run(
            trials=3 if args.full else 1 if args.smoke else 2)))

    if only and not benches:
        raise SystemExit(f"no benchmark matches --only {args.only!r} "
                         "(see --help for valid names)")
    failures = 0
    for name, fn in benches:
        t0 = time.time()
        try:
            for line in fn():
                print(line, flush=True)
            print(f"meta,{name}_seconds,{time.time() - t0:.1f}", flush=True)
        except Exception:
            failures += 1
            print(f"meta,{name}_FAILED,1")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
