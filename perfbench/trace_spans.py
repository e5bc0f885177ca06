"""Runs one cell traced, with the program's spans and device scopes in the
reduction, and prints the result line with the span metrics.

    python3 perfbench/trace_spans.py --workload <cell> --seed <n> \\
        --seconds <s>

``run.py --trace 1`` reduces a traced window by the harness's own
annotations (``pb.*``), and reports the per-layer metrics that
``BENCHMARK.json`` lists.  This runs the same ``run.measure`` with the
reduction ``harness.spans.add`` extends: idle gaps labelled by the
innermost of ``harness.spans.PREFIXES`` (the program's ``sweep.*``,
``eval.*`` and ``dse.*`` spans too), the spans themselves, and the device
time of each ``sweep.*`` scope; and it adds the metrics of
``span_metrics.json`` to the cell's.  It compiles with the persistent
compilation cache off, so set-up takes the compile: the cache's key leaves
out op-name metadata, and a program cached by a build without the scopes
would run without them.  Besides ``run.py``'s result, the line holds
``spans``, ``scopes`` and ``crosscheck``:

- ``eval_call_over_dispatch``: ``eval_call_ms.campaign`` over the
  harness's ``dispatch_ms.campaign``;
- ``chunk_spans_over_wall``: ``sweep.chunk``'s self time plus its phases,
  per ``sweep.chunk`` span, over ``chunk_wall_ms.sweep``;
- ``idle_by_program_span``: the share of idle device time whose innermost
  annotation is a program span;
- ``scopes_over_busy``: the ``sweep.*`` scopes' device time over the
  busiest device's busy time.
"""
import json
import os
import sys

import run  # puts the harness on the path
import jax  # noqa: E402
from harness import spec, spans, trace  # noqa: E402

PROGRAM = tuple(p for p in spans.PREFIXES if p != "pb.")
PHASES = ("sweep.filter", "sweep.wait", "sweep.fetch", "sweep.insert")


def pending_metrics():
    with open(os.path.join(run.HERE, "span_metrics.json")) as f:
        return json.load(f)["per_layer"]


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
            if label.startswith(PROGRAM)) / idle
    sc = red["scopes"]
    if any(k.startswith("sweep.") for k in sc):
        out["scopes_over_busy"] = (sum(v for k, v in sc.items()
                                       if k.startswith("sweep."))
                                   / max(red["busy_s"].values()))
    return out


def measure(workload, seed, seconds, op_lines=trace.tpu_op_lines):
    """``run.measure`` of a traced run with the span reduction and the
    span metrics; returns its result with the additions above."""
    kept = {}
    real_load, real_reduce = trace.load, trace.reduce_profile

    def load(path):
        kept["dir"] = path
        return real_load(path)

    def reduce_profile(pd, window, annotations, **kw):
        red = real_reduce(pd, window, spans.PREFIXES, op_lines=op_lines)
        kept["red"] = spans.add(red, pd, window, kept["dir"],
                                spans.PREFIXES, op_lines)
        return red

    bench = spec.load_benchmark()
    bench["per_layer"] = bench["per_layer"] + pending_metrics()
    # the persistent cache's key leaves out op-name metadata: a program
    # cached by a build without scopes would run here without them
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    trace.load, trace.reduce_profile = load, reduce_profile
    try:
        out = run.measure(workload, seed, seconds, True, bench=bench)
    finally:
        trace.load, trace.reduce_profile = real_load, real_reduce
        jax.config.update("jax_enable_compilation_cache", cache)
    red = kept["red"]
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
