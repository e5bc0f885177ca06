"""The program's own spans and device scopes, read from a JAX profiler
trace.

The program opens a ``jax.profiler.TraceAnnotation`` for every span of its
tracer (``sweep.*``, ``eval.*``, ``dse.*``) and names the phases of its
jitted sweep step with ``jax.named_scope`` (``sweep.decode``,
``sweep.op_terms``, ``sweep.reduce``), which XLA keeps as each
instruction's op-name metadata; the trace file holds it in the HLO of each
program it saw run (``harness.xplane``).  Spans and scopes are found by
the one rule of ``trace.is_name`` (a lowercase ``family.name``), so a span
or scope that a later program opens is reduced with no edit here.  This
module reduces both:

- ``reduce_spans``: for each host annotation inside the window that the
  rule names, its count, seconds, self seconds (time not covered by such an
  annotation nested in it on the same thread line) and the sum of each
  integer argument;
- ``reduce_scopes``: device busy seconds per scope on the busiest chip; an
  operation counts under every scope of its op-name path (a scope's time
  holds the scopes nested in it), under ``unscoped`` where the path holds
  none, and a fusion under its root's path;
- ``add``: both, as the keys ``spans`` and ``scopes`` of a
  ``trace.reduce_profile`` result (what the span metrics under
  ``metrics/`` read).

A program without spans gives no such annotations, and the metrics that
read them report nothing.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Callable, Dict, List, Mapping, Optional, Tuple

from harness import trace, xplane

UNSCOPED = "unscoped"
#: a TPU operation's event name starts with its HLO instruction
INSTR = re.compile(r"%?([\w.\-]+)")
#: the program id a TPU ``XLA Modules`` event appends to the module name
MODULE_ID = re.compile(r"\(\d+\)$")


def _stats(ev) -> List[Tuple[str, object]]:
    return [(k, v) for k, v in ev.stats]


def reduce_spans(pd, window: trace.Interval) -> Dict[str, Dict]:
    """``{name: {"count", "s", "self_s", "stats": {arg: sum}}}`` over the
    host annotations that ``trace.is_name`` names and that lie inside
    ``window``."""
    w0, w1 = window
    out: Dict[str, Dict] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = []
            for ev in ln.events:
                if not trace.is_name(ev.name):
                    continue
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if s >= w0 and e <= w1:
                    evs.append((s, -e, ev))
            evs.sort(key=lambda x: x[:2])
            stack: List[List] = []          # [end, name, covered ns]
            for s, neg_e, ev in evs:
                e = -neg_e
                while stack and stack[-1][0] <= s:
                    _close(out, stack.pop())
                if stack:
                    stack[-1][2] += min(e, stack[-1][0]) - s
                rec = out.setdefault(ev.name, {"count": 0, "s": 0.0,
                                               "self_s": 0.0, "stats": {}})
                rec["count"] += 1
                rec["s"] += (e - s) / 1e9
                rec["self_s"] += (e - s) / 1e9
                for k, v in _stats(ev):
                    if isinstance(v, int) and not isinstance(v, bool):
                        rec["stats"][k] = rec["stats"].get(k, 0) + v
                stack.append([e, ev.name, 0])
            while stack:
                _close(out, stack.pop())
    return out


def _close(out: Dict[str, Dict], frame: List) -> None:
    """Take the time of the annotations nested in a closed one off its
    self seconds."""
    out[frame[1]]["self_s"] -= frame[2] / 1e9


def _scopes(op_name: str) -> List[str]:
    """The scopes of an op-name path, outermost first, or ``unscoped``."""
    return ([c for c in op_name.split("/") if trace.is_name(c)]
            or [UNSCOPED])


def _module_spans(ln_modules) -> Tuple[List[int], List[Tuple[int, str]]]:
    """Start times and (end, name) of a TPU plane's ``XLA Modules`` events,
    the program each operation in them belongs to."""
    evs = sorted((int(e.start_ns), int(e.start_ns) + int(e.duration_ns),
                  MODULE_ID.sub("", e.name)) for e in ln_modules.events)
    return [e[0] for e in evs], [(e[1], e[2]) for e in evs]


def reduce_scopes(pd, window: trace.Interval,
                  op_names: Mapping[str, Mapping[str, str]],
                  op_lines: Callable = trace.tpu_op_lines
                  ) -> Dict[str, float]:
    """Device busy seconds per scope inside ``window`` on the busiest
    device (the one with the largest union of operation intervals).

    ``op_names`` is ``xplane.hlo_op_names`` of the same trace.  An
    operation's program and HLO instruction come from its ``hlo_module``
    and ``hlo_op`` stats (CPU), else from the ``XLA Modules`` event around
    it and the instruction name that starts its event name (TPU)."""
    w0, w1 = window
    per_dev: Dict[str, Dict[str, List]] = {}
    for plane in pd.planes:
        lines = op_lines(plane)
        if not lines:
            continue
        mods = [ln for ln in plane.lines if ln.name == "XLA Modules"]
        starts, ends = _module_spans(mods[0]) if mods else ([], [])
        scopes = per_dev.setdefault(plane.name, defaultdict(list))
        for ln in lines:
            for ev in ln.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if e <= w0 or s >= w1 or ev.name.startswith(
                        "ThreadpoolListener"):
                    continue
                st = dict(_stats(ev))
                module, instr = st.get("hlo_module"), st.get("hlo_op")
                if instr is None:
                    m = INSTR.match(ev.name)
                    instr = m.group(1) if m else ev.name
                    i = bisect.bisect_right(starts, s) - 1
                    if i >= 0 and ends[i][0] >= e:
                        module = ends[i][1]
                op = op_names.get(module, {}).get(instr, "")
                for sc in _scopes(op):
                    scopes[sc].append((max(s, w0), min(e, w1)))
    if not per_dev:
        return {}
    busiest = max(per_dev, key=lambda d: trace.union_length(
        [iv for ivs in per_dev[d].values() for iv in ivs])[0])
    return {k: trace.union_length(iv)[0] / 1e9
            for k, iv in sorted(per_dev[busiest].items())}


def add(red: Dict, pd, window: trace.Interval, trace_dir: str,
        op_lines: Callable = trace.tpu_op_lines) -> Dict:
    """Adds ``spans`` and ``scopes`` to ``red``, a ``trace.reduce_profile``
    result of the trace under ``trace_dir`` over the same window, and
    returns it."""
    red["spans"] = reduce_spans(pd, window)
    red["scopes"] = reduce_scopes(pd, window, xplane.load_op_names(trace_dir),
                                  op_lines)
    return red


# --------------------------------------------------------------- readers
def program_spans(rec: Dict, kind: str, root: str) -> Optional[Dict]:
    """The traced run's span reduction, or None where the run is not of
    ``kind``, was not traced with spans, or holds no ``root`` span (the
    program opened none)."""
    t = rec.get("trace") or {}
    sp = t.get("spans")
    if rec["window"]["kind"] != kind or not sp or root not in sp:
        return None
    return sp


def seconds(sp: Dict, name: str, field: str = "s") -> float:
    """A span's seconds (or ``self_s``); 0 where it never opened."""
    return sp[name][field] if name in sp else 0.0


def stat(sp: Dict, name: str, arg: str) -> int:
    """The sum of a span's integer argument; 0 where it never opened."""
    return sp[name]["stats"].get(arg, 0) if name in sp else 0


def scope_ms_per_chunk(rec: Dict, scope: str) -> Optional[float]:
    """Device ms per chunk step in one ``sweep.*`` scope, or None where the
    trace names no scope (no op-name metadata, or a program without
    scopes)."""
    t = rec.get("trace") or {}
    sc = t.get("scopes") or {}
    w = rec["window"]
    if (w["kind"] != "sweep" or not w["chunks"]
            or not any(k.startswith("sweep.") for k in sc)):
        return None
    return sc.get(scope, 0.0) / w["chunks"] * 1e3
