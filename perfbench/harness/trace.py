"""Reduction of a JAX profiler trace to device busy time, idle share and a
breakdown.

Busy time is the union of the intervals in which an operation ran on a
device; the idle share is one minus busy over the traced window.  Idle gaps
are labelled with the innermost host annotation (``jax.profiler.
TraceAnnotation``) open at the gap's midpoint, which puts the harness's and
the program's spans and the device on one clock.

The annotations reduced are those named by one rule (``is_name``): a
lowercase ``family.name``, such as the harness's ``pb.window`` or the
program's ``sweep.chunk`` and ``eval.fetch``.  A span that a later program
opens is reduced with no edit here; JAX's own host events (``$file.py:N
function`` from the Python tracer, ``PjitFunction(...)``, ``fusion.3``) do
not follow it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Tuple

import numpy as np

Interval = Tuple[int, int]       # (start_ns, end_ns)

#: the names of the harness's and the program's annotations and device
#: scopes: lowercase dotted words, each starting with a letter
NAME = re.compile(r"[a-z][a-z0-9_]*(?:\.[a-z][a-z0-9_]*)+")
#: the label of an idle gap in which no such annotation is open
NO_ANNOTATION = "no annotation open"


def is_name(s: str) -> bool:
    """Whether ``s`` names a span or scope of the harness or the program."""
    return NAME.fullmatch(s) is not None


def tpu_op_lines(plane) -> list:
    """The lines of a TPU device plane that hold its operations."""
    if not plane.name.startswith("/device:TPU:"):
        return []
    return [ln for ln in plane.lines if ln.name == "XLA Ops"]


def cpu_op_lines(plane) -> list:
    """The lines of the host plane on which the CPU client runs its
    compiled programs (the CPU backend has no device plane)."""
    if plane.name != "/host:CPU":
        return []
    return [ln for ln in plane.lines
            if ln.name.startswith("tf_XLAPjRtCpuClient")]


def union_length(iv: List[Interval]) -> Tuple[int, List[Interval]]:
    """Total length of the union of intervals, and the merged intervals."""
    if not iv:
        return 0, []
    iv = sorted(iv)
    merged = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [tuple(m) for m in merged]


def load(path: str):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def reduce_profile(pd, window: Interval,
                   op_lines: Callable = tpu_op_lines) -> Dict:
    """Busy time per device inside ``window`` (ns, on the trace's clock),
    the device operations that took the most time, and idle gaps by the
    innermost host annotation (``is_name``) open in each gap."""
    w0, w1 = window
    devices: Dict[str, List[Interval]] = {}
    op_time: Dict[str, float] = defaultdict(float)
    host: List[Tuple[int, int, str]] = []
    for plane in pd.planes:
        lines = op_lines(plane)
        if lines:
            iv = devices.setdefault(plane.name, [])
            for ln in lines:
                for ev in ln.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if e <= w0 or s >= w1 or ev.name.startswith(
                            "ThreadpoolListener"):
                        continue
                    s, e = max(s, w0), min(e, w1)
                    iv.append((s, e))
                    op_time[ev.name] += (e - s) / 1e9
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if is_name(ev.name):
                        s = int(ev.start_ns)
                        host.append((s, s + int(ev.duration_ns), ev.name))
    busy = {}
    gaps: Dict[str, float] = defaultdict(float)
    host.sort()
    hs = np.array([h[0] for h in host], dtype=np.int64)
    for dev, iv in devices.items():
        total, merged = union_length(iv)
        busy[dev] = total / 1e9
        edges = [w0] + [x for m in merged for x in m] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_label(host, hs, (a + b) // 2)] += (b - a) / 1e9 / len(
                    devices)
    window_s = (w1 - w0) / 1e9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": window_s, "busy_s": busy,
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": [[n, s] for n, s in top_gaps]}


def _label(host, hs, t: int) -> str:
    """Innermost (latest-starting) host annotation containing time t."""
    i = int(np.searchsorted(hs, t, side="right"))
    for s, e, name in reversed(host[max(0, i - 512):i]):
        if e >= t:
            return name
    return NO_ANNOTATION


def window_from_annotation(pd, name: str) -> Interval:
    """The span of the host annotation ``name`` (the traced window)."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name == name:
                    s = int(ev.start_ns)
                    return s, s + int(ev.duration_ns)
    raise ValueError(f"annotation {name!r} not in the trace")
