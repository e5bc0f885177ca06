"""Quickstart: run a 20-sample Lumina DSE campaign against the A100
reference and print the Pareto-optimal designs it finds.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.perfmodel import get_evaluator
from repro.perfmodel.designspace import SPACE
from repro.core.loop import LuminaDSE
from repro.runtime.chip import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    # the paper's evaluation workload: one GPT-3 175B layer, TP=8,
    # batch 8, seq 2048 (TTFT) / 1024th output token (TPOT), FP16.
    # The high-fidelity target tier pays the budget; the roofline proxy
    # tier serves QualE/QuanE acquisition for free.
    dse = LuminaDSE(get_evaluator("target"), proxy=get_evaluator("proxy"),
                    seed=0)

    result = dse.run(budget=20)

    print(f"evaluations: {len(result.samples)}  "
          f"designs dominating the A100: {result.superior_count}  "
          f"PHV: {result.phv:.4g}")
    print("\nPareto front (vs A100 = 1.0):")
    ref = dse.ref_point
    for s in result.pareto:
        vals = SPACE.decode_np(s.idx)
        cfgstr = " ".join(f"{k}={int(v)}" for k, v in vals.items())
        print(f"  TTFT {s.ttft / ref[0]:.3f}  TPOT {s.tpot / ref[1]:.3f}  "
              f"Area {s.area / ref[2]:.3f}   [{cfgstr}]")
    if result.trajectory_notes:
        print("\nreflection notes (refinement loop):")
        for n in result.trajectory_notes[:5]:
            print("  " + n)


if __name__ == "__main__":
    main()
