"""Runs one benchmark cell and prints its result as the last stdout line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell's configuration, traffic mix and metrics are found by the names
in ``BENCHMARK.json``.  A run sets up (imports, device, evaluator, compile
or compile-cache load, one warm unit), measures for ``--seconds`` with
nothing compiling, then checks what the window produced against the plain
reference.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` compiles with the persistent cache off, runs the window under
the profiler and reports its per-layer metrics, read from the trace's
device time and from the harness's and the program's spans and device
scopes (``harness.spans``).  No TPU, or fewer
chips than the cell asks for, exits 2 with no result.  The numbers compared
with their limits end standard error and the result line (under
``checks``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from harness import device, spec  # noqa: E402

WINDOW = "pb.window"


def _annotate(name):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class CompileCounter:
    """Counts programs lowered while ``active`` (a jit cache miss lowers,
    whether the compile then comes from the persistent cache or not)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.active and name == self.EVENT:
            self.count += 1


def measure(workload: str, seed: int, seconds: float, traced: bool,
            bench=None, op_lines=None) -> dict:
    """Set up, measure and check one run of a cell; returns the result.

    A traced run's result also holds the trace's reduction under
    ``trace`` (``main`` leaves it out of the line); ``op_lines`` picks
    the device's op lines in the trace (``harness.trace.tpu_op_lines``
    by default)."""
    import jax
    bench = bench or spec.load_benchmark()
    cell = spec.cell(bench, workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    from repro.runtime.chip import enable_compile_cache
    enable_compile_cache()
    # cache every program, however fast it compiles, so set-up is steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cache = jax.config.jax_enable_compilation_cache
    if traced:
        # a program loaded from the persistent cache brings no op names
        # into the profiler's trace (seen on a v5e), and the device scopes
        # are read from them: a traced run compiles its own programs
        jax.config.update("jax_enable_compilation_cache", False)
    trace_dir = None
    try:
        dev = device.require(cell["chips"])
        unit = spec.unit(mix["kind"])
        state = unit.setup(cfg, mix, seed)
        counter = CompileCounter()
        setup_s = time.perf_counter() - T_START
        if traced:
            trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        counter.active = True
        with (jax.profiler.trace(trace_dir) if traced
              else contextlib.nullcontext()):
            with _annotate(WINDOW):
                window = unit.window(state, seconds, _annotate)
        counter.active = False
        dev["memory_peak_bytes"] = device.memory_peak(cell["chips"])
        rec = {"setup_s": setup_s, "window": window}
        breakdown = None
        if traced:
            from harness import spans, trace
            pd = trace.load(trace_dir)
            win = trace.window_from_annotation(pd, WINDOW)
            lines = op_lines or trace.tpu_op_lines
            red = spans.add(trace.reduce_profile(pd, win, op_lines=lines),
                            pd, win, trace_dir, lines)
            rec["trace"] = red
            used = sorted(red["busy_s"])[:cell["chips"]]
            dev["busy_s"] = sum(red["busy_s"][d] for d in used) / len(used)
            dev["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    t_check = time.perf_counter()
    numbers, failed = unit.check(state, cfg, mix, seed)
    rec["timing"] = {"setup_s": setup_s, "window_s": window["elapsed_s"],
                     "check_s": time.perf_counter() - t_check,
                     "unit_s": window.get("unit_s", [])}
    metrics = {}
    for m in spec.cell_metrics(bench, workload, traced):
        v = spec.metric_reader(m["name"]).read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    checks = {k: {"value": numbers[k], "limit": mix["limits"][k]}
              for k in numbers}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": bool(correct and failed == 0),
           "attempted": int(window["units"]), "failed": int(failed),
           "metrics": metrics, "device": dev,
           "compiles_in_window": counter.count, "timing": rec["timing"]}
    if breakdown is not None:
        out["breakdown"] = breakdown
        out["trace"] = rec["trace"]
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = measure(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    except device.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    d = out["device"]
    print(f"platform {d['platform']}  device_kind {d['kind']}  "
          f"device_count {d['count']}")
    print(f"compiles_in_window {out['compiles_in_window']}")
    t = out.pop("timing")
    out.pop("trace", None)
    u = sorted(t["unit_s"])
    print(f"timing setup_s {t['setup_s']:.3f} window_s {t['window_s']:.3f} "
          f"check_s {t['check_s']:.3f} units {len(u)} unit_s first "
          f"{[round(x, 4) for x in t['unit_s'][:3]]} min "
          f"{u[0] if u else 0:.4f} median {u[len(u) // 2] if u else 0:.4f} "
          f"max {u[-1] if u else 0:.4f}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
