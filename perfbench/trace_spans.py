"""Runs one cell traced, and prints the result line with the raw span
reduction and its cross-checks.

    python3 perfbench/trace_spans.py --workload <cell> --seed <n> \\
        --seconds <s>

It is ``run.py --trace 1`` (the same ``run.measure``: compile cache off,
the per-layer metrics of ``BENCHMARK.json``, the spans and scopes of
``harness.spans``).  Besides ``run.py``'s result, the line holds
``spans``, ``scopes`` and ``crosscheck``:

- ``eval_call_over_dispatch``: ``eval_call_ms.campaign`` over the
  harness's ``dispatch_ms.campaign``;
- ``chunk_spans_over_wall``: ``sweep.chunk``'s self time plus its phases,
  per ``sweep.chunk`` span, over ``chunk_wall_ms.sweep``;
- ``idle_by_program_span``: the share of idle device time whose innermost
  annotation is a program span (any but the harness's ``pb.*``);
- ``scopes_over_busy``: the ``sweep.*`` scopes' device time over the
  busiest device's busy time.
"""
import json
import sys

import run  # puts the harness on the path
from harness import spans, trace  # noqa: E402

PHASES = ("sweep.filter", "sweep.wait", "sweep.fetch", "sweep.insert")


def crosscheck(red, metrics):
    """The in-program spans against the outside timers (see above)."""
    val = {k: v["value"] for k, v in metrics.items()}
    sp, out = red["spans"], {}
    if "eval_call_ms.campaign" in val and val.get("dispatch_ms.campaign"):
        out["eval_call_over_dispatch"] = (val["eval_call_ms.campaign"]
                                          / val["dispatch_ms.campaign"])
    if "sweep.chunk" in sp and val.get("chunk_wall_ms.sweep"):
        parts = (spans.seconds(sp, "sweep.chunk", "self_s")
                 + sum(spans.seconds(sp, p) for p in PHASES))
        out["chunk_spans_over_wall"] = (
            parts / sp["sweep.chunk"]["count"] * 1e3
            / val["chunk_wall_ms.sweep"])
    busy = sum(red["busy_s"].values()) / len(red["busy_s"])
    idle = red["window_s"] - busy
    if idle > 0:
        out["idle_by_program_span"] = sum(
            s for label, s in red["idle_gaps"]
            if label != trace.NO_ANNOTATION
            and not label.startswith("pb.")) / idle
    sc = red["scopes"]
    if any(k.startswith("sweep.") for k in sc):
        out["scopes_over_busy"] = (sum(v for k, v in sc.items()
                                       if k.startswith("sweep."))
                                   / max(red["busy_s"].values()))
    return out


def measure(workload, seed, seconds, op_lines=trace.tpu_op_lines):
    """``run.measure`` of a traced run; returns its result with the
    additions above."""
    out = run.measure(workload, seed, seconds, True, op_lines=op_lines)
    red = out.pop("trace")
    out["spans"], out["scopes"] = red["spans"], red["scopes"]
    out["crosscheck"] = crosscheck(red, out["metrics"])
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        out = measure(args.workload, args.seed, args.seconds)
    except run.device.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    out.pop("timing", None)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
