"""Sweep the ENTIRE 4.7M-point design space and report the exact number of
designs that dominate the NVIDIA A100 reference — the paper's ground-truth
oracle that black-box DSE methods can only sample.

    PYTHONPATH=src python examples/full_space_sweep.py
    PYTHONPATH=src python examples/full_space_sweep.py --stop 500000 \
        --checkpoint /tmp/sweep_ck --checkpoint-every 8
"""
import argparse

from repro.perfmodel import get_evaluator
from repro.perfmodel.designspace import SPACE
from repro.perfmodel.sweep import SweepEngine
from repro.runtime.chip import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--stop", type=int, default=None,
                    help="sweep only flat ids [0, STOP) instead of the full space")
    ap.add_argument("--chunk", type=int, default=131_072)
    ap.add_argument("--backend", default="roofline",
                    choices=["roofline", "pallas"])
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    help="chunks between checkpoint writes")
    ap.add_argument("--resume", default=None,
                    help="checkpoint file to resume a partial sweep from")
    ap.add_argument("--stall-topk", type=int, default=8,
                    help="per-stall-class seed designs to track (0 = off)")
    args = ap.parse_args()

    eng = SweepEngine(get_evaluator("proxy"), chunk_size=args.chunk,
                      backend=args.backend, stall_topk=args.stall_topk)
    ref = eng.ref_point
    print(f"design space: {SPACE.size:,} points "
          f"({' x '.join(str(len(c)) for c in SPACE.choices)})")
    print(f"A100 reference: TTFT {ref[0] * 1e3:.2f}ms  "
          f"TPOT {ref[1] * 1e6:.0f}us  area {ref[2]:.0f}mm2\n")

    res = eng.run(stop=args.stop, checkpoint_path=args.checkpoint,
                  checkpoint_every=args.checkpoint_every,
                  resume_from=args.resume, progress=True)

    print(f"\nswept {res.n_evaluated:,} designs in {res.seconds:.1f}s "
          f"({res.points_per_sec:,.0f} designs/sec)")
    print(f"designs strictly dominating the A100 in ALL objectives: "
          f"{res.n_superior:,} "
          f"({100.0 * res.n_superior / max(res.n_evaluated, 1):.3f}%)")
    print(f"exact Pareto front: {len(res.pareto_ids)} designs"
          + (" (archive truncated)" if res.archive_truncated else ""))

    if res.n_evaluated == 0:
        print("\n(empty range: nothing swept)")
        return
    names = ("ttft", "tpot", "area")
    units = (1e3, 1e6, 1.0)
    print("\nbest design per objective:")
    for o, (nm, u) in enumerate(zip(names, units)):
        idx = SPACE.flat_to_idx(int(res.topk_ids[o][0]))
        vals = {k: int(v) for k, v in SPACE.decode_np(idx).items()}
        print(f"  {nm:5s} {res.topk_val[o][0] * u:10.4g} "
              f"{'ms' if o == 0 else 'us' if o == 1 else 'mm2':3s}  {vals}")

    if args.stall_topk:
        print("\nbottleneck-analysis seeds (best TTFT per dominant stall):")
        for stall, seeds in res.stall_seeds().items():
            if not len(seeds):
                print(f"  {stall:16s} (none found)")
                continue
            vals = {k: int(v) for k, v in SPACE.decode_np(seeds[0]).items()}
            print(f"  {stall:16s} {len(seeds):2d} seeds, best: {vals}")


if __name__ == "__main__":
    main()
