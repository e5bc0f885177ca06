"""Compare every DSE method (paper Fig. 4) on a workload derived from one of
the ASSIGNED architectures — each arch config doubles as a Lumina workload.

    PYTHONPATH=src python examples/explore_design_space.py \
        --arch rwkv6-7b --budget 150
"""
import argparse

from repro.configs import get_arch
from repro.core.baselines import METHODS, run_method
from repro.core.loop import LuminaDSE
from repro.perfmodel import make_evaluator
from repro.perfmodel.designspace import SPACE, A100_REFERENCE
from repro.perfmodel.workload import from_arch
from repro.runtime.chip import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-7b")
    ap.add_argument("--budget", type=int, default=150)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--backend", default=None,
                    help="evaluator backend: roofline|pallas|auto")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    evaluator = make_evaluator({
        "ttft": from_arch(cfg, args.batch, args.seq, decode=False),
        "tpot": from_arch(cfg, args.batch, args.seq, decode=True),
    }, backend=args.backend)

    ref = evaluator.objectives(SPACE.encode_nearest(A100_REFERENCE)[None, :])[0]
    print(f"workload: {args.arch}  A100 point: "
          f"TTFT {ref[0] * 1e3:.2f}ms TPOT {ref[1] * 1e6:.0f}us "
          f"area {ref[2]:.0f}mm2\n")

    print(f"{'method':8s} {'PHV':>10s} {'sample-eff':>10s} {'superior':>9s}")
    for name, cls in METHODS.items():
        r = run_method(cls, evaluator, args.budget, ref, seed=0, batch=8)
        print(f"{name:8s} {r.phv:10.4g} {r.sample_efficiency:10.3f} "
              f"{r.superior_count:9d}")
    res = LuminaDSE(evaluator, seed=0).run(budget=args.budget)
    print(f"{'LUMINA':8s} {res.phv:10.4g} {res.sample_efficiency:10.3f} "
          f"{res.superior_count:9d}")
    best = res.pareto[0]
    print("\nbest Lumina design:", dict(
        (k, int(v)) for k, v in SPACE.decode_np(best.idx).items()))


if __name__ == "__main__":
    main()
