"""Traffic kind ``sweep``: whole-space sweeps, back to back.

One ``SweepEngine`` per process, built from the configuration's evaluator
at the mix's tier; every sweep starts from fresh state, as a user's repeat
sweep does.  The window ends when the first sweep that crosses the window
length completes.  Every sweep of the window is compared with the float64
reference over the same ids: superiority counts, fronts, top-k values and
stall seeds of every group (each scenario, and the robust front of a
portfolio).
"""
from __future__ import annotations

import hashlib
import sys
import time
from typing import Dict

import numpy as np

import jax

from harness import compare, program
from harness import reference as R

# ids a sweep covers, from 0; None is the whole space.  Only tests shrink it.
STOP = None


def setup(cfg: Dict, mix: Dict, seed: int) -> Dict:
    """Build the engine and run one whole sweep: that compiles the chunk
    step (or loads it from the persistent cache) and warms every host path
    a sweep takes.  The sweep's work is the same for every seed."""
    from repro.perfmodel.sweep import SweepEngine
    eng = SweepEngine(program.evaluator(cfg, mix["tier"]),
                      stall_topk=mix["stall_topk"], shard=mix["shard"],
                      chunk_size=mix.get("chunk"))
    eng.run(0, STOP)
    return {"engine": eng}


def window(state: Dict, seconds: float, annotate) -> Dict:
    eng = state["engine"]
    h0 = eng.telemetry()
    results, unit_s = [], []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        t = time.perf_counter()
        with annotate("pb.sweep"):
            results.append(eng.run(0, STOP))
        now = time.perf_counter()
        unit_s.append(now - t)
        if now >= deadline:
            break
    elapsed = now - t0
    h1 = eng.telemetry()
    state["results"] = results
    return {"kind": "sweep", "elapsed_s": elapsed, "units": len(results),
            "work": int(sum(r.n_evaluated for r in results)),
            "chunks": h1["chunks"] - h0["chunks"],
            "chunk_s_count": h1["chunk_s"]["count"] - h0["chunk_s"]["count"],
            "chunk_s_sum": h1["chunk_s"]["sum"] - h0["chunk_s"]["sum"],
            "unit_s": unit_s}


def _digest(groups, seeds) -> str:
    h = hashlib.sha256()
    for g in groups:
        h.update(repr(g["n_superior"]).encode())
        for k in ("front_ids", "front_y", "topk_val", "topk_ids"):
            h.update(np.ascontiguousarray(g[k]).tobytes())
    for s in seeds:
        if s is not None:
            h.update(s["val"].tobytes() + s["ids"].tobytes())
    return h.hexdigest()


def check(state: Dict, cfg: Dict, mix: Dict, seed: int):
    """(numbers, failed units): the worst of every sweep of the window
    against the reference, and how many sweeps broke a limit."""
    results = state.pop("results")
    state.pop("engine", None)                     # free the program's state
    n_scen = len(R.scenarios(cfg))
    size = R.SIZE if STOP is None else int(STOP)
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with jax.enable_x64(True):
        model = R.Model(cfg, mix["tier"], "float64")
        ys, dom, ids = model.sweep(np.arange(size, dtype=np.int64),
                                   device=cpu)
        ref = compare.SweepReference(model, ids, ys, dom,
                                     robust=n_scen > 1)
        print(f"reference sweep_s {time.perf_counter() - t0:.3f}",
              file=sys.stderr)
        seen: Dict[str, Dict] = {}
        per_unit = []
        for res in results:
            groups, seeds = compare.sweep_result_groups(res, n_scen)
            key = _digest(groups, seeds)
            if key not in seen:
                seen[key] = compare.compare_sweep(
                    ref, groups, seeds, int(res.n_evaluated), size)
            per_unit.append(seen[key])
    worst = {k: max(u[k] for u in per_unit) for k in per_unit[0]}
    failed = sum(any(u[k] > mix["limits"][k] for k in u) for u in per_unit)
    return worst, failed


def control(cfg: Dict, mix: Dict, device=None) -> Dict:
    """The reference in bfloat16 put in the program's place: its own
    whole-space sweep, reduced to a program-shaped result and compared
    with the float64 reference as a run compares the program."""
    n_scen = len(R.scenarios(cfg))
    size = R.SIZE if STOP is None else int(STOP)
    ids = np.arange(size, dtype=np.int64)
    low = R.Model(cfg, mix["tier"], "bfloat16")
    ys_c, dom_c, _ = low.sweep(ids, device=device)
    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True):
        model = R.Model(cfg, mix["tier"], "float64")
        ys, dom, _ = model.sweep(ids, device=cpu)
        ref = compare.SweepReference(model, ids, ys, dom, robust=n_scen > 1)
        ctl = compare.SweepReference(model, ids, ys_c, dom_c,
                                     robust=n_scen > 1)
        k, sk = 16, mix["stall_topk"]
        groups = []
        for g in range(len(ctl.groups)):
            front = ctl.front(g)
            top = np.stack([np.argsort(ctl.groups[g][:, o],
                                       kind="stable")[:k]
                            for o in range(3)])
            lo, _ = ctl.superior_band(g, tol=0.0)
            groups.append(dict(
                n_superior=lo, front_ids=front,
                front_y=ctl.values(g, front),
                topk_val=np.stack([ctl.groups[g][top[o], o]
                                   for o in range(3)]),
                topk_ids=ids[top], truncated=False))
        seeds = []
        for s in range(n_scen):
            vals, sids = zip(*[ctl.stall_topk(s, c, sk)
                               for c in range(R.N_STALL)])
            pad = lambda a, f: np.concatenate(            # noqa: E731
                [a, np.full(sk - len(a), f)])
            seeds.append(dict(
                val=np.stack([pad(v, np.inf) for v in vals]),
                ids=np.stack([pad(i, -1) for i in sids]).astype(np.int64)))
        return compare.compare_sweep(ref, groups, seeds, size, size)
