"""The control of a cell: the plain reference computed in bfloat16, one
precision below the float32 the configurations state, put in the program's
place and compared with the float64 reference as a run compares the
program.  It must read as not correct; its numbers are the upper readings
the limits in ``traffic/*.json`` were set below.  Benchmark runs never run
it.

    python3 perfbench/control.py --workload <cell> --seed <n> [--seconds s]

Sweep cells sweep the whole space in bfloat16 on the default device.  The
campaign cell first runs the program for a short window at the cell's own
load (``--seconds``), then evaluates every design the window asked for in
bfloat16.  Prints one JSON line: the numbers, their limits, and whether the
control broke a limit.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from harness import device, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    from contextlib import nullcontext
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    from repro.runtime.chip import enable_compile_cache
    enable_compile_cache()
    dev = device.require(cell["chips"])
    unit = spec.unit(mix["kind"])
    if mix["kind"] == "campaign":
        state = unit.setup(cfg, mix, args.seed)
        unit.window(state, args.seconds, lambda name: nullcontext())
        numbers = unit.control(state, cfg, mix)
    else:
        numbers = unit.control(cfg, mix)
    broken = sorted(k for k, v in numbers.items() if v > mix["limits"][k])
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "device": dev, "numbers": numbers,
                      "limits": mix["limits"], "control_fails": broken}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
