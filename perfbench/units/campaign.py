"""Traffic kind ``campaign``: budgeted LUMINA campaigns, back to back.

Each campaign is ``LuminaDSE(target, proxy=proxy, seed=s_i).run(budget)``
from the A100 start, one caller waiting on every step (a closed loop).
Campaign i's seed is drawn from ``--seed`` and i.  Both evaluators are
wrapped so that every call is timed, annotated and recorded; the
comparison then checks every report they returned in the window against
the float64 reference.

A step is propose + evaluate + observe and ends when ``observe`` returns;
a campaign's start-up (its reference point, influence probing and first
sensitivity pass) is window time but belongs to no step.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import jax

from harness import compare, program
from harness import reference as R


def campaign_seed(seed: int, i: int) -> int:
    return int(np.random.SeedSequence([seed % 2 ** 64, i])
               .generate_state(1)[0])


class Recorder:
    """An evaluator that times, annotates and records every dispatching
    call of the one it wraps, and delegates everything else."""

    def __init__(self, inner, annotate):
        self._inner = inner
        self._annotate = annotate
        self.recording = False
        self.calls: List[Dict] = []
        self.n_calls = 0
        self.seconds = 0.0
        self.step = 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _timed(self, fn, arg):
        t0 = time.perf_counter()
        with self._annotate("pb.evaluate"):
            out = fn(arg)
        self.seconds += time.perf_counter() - t0
        self.n_calls += 1
        return out

    def evaluate(self, request):
        rep = self._timed(self._inner.evaluate, request)
        if self.recording:
            call = dict(step=self.step, idx=np.atleast_2d(request.idx),
                        names=rep.workloads, area=rep.area,
                        latency=rep.latency)
            if rep.detail == "stalls":
                call.update(op_time=rep.op_time, stall=rep.stall,
                            op_class=rep.op_class)
            self.calls.append(call)
        return rep

    def objectives(self, idx):
        y = self._timed(self._inner.objectives, idx)
        if self.recording:
            names = tuple(self._inner.workloads)
            self.calls.append(dict(
                step=self.step, idx=np.atleast_2d(idx), names=names,
                area=y[:, -1],
                latency={nm: y[:, i] for i, nm in enumerate(names)}))
        return y


def _warm(ev, warm: Dict) -> None:
    from repro.perfmodel.evaluator import EvalRequest
    for detail, buckets in warm.items():
        for b in buckets:
            ev.evaluate(EvalRequest(np.zeros((b, len(R.CARDS)), np.int32),
                                    detail=detail))


def _campaign(state: Dict, seed: int, steps: List, annotate) -> int:
    """One campaign; appends (step seconds, evaluator seconds in it)."""
    from repro.core.loop import LuminaDSE
    tw, pw = state["target"], state["proxy"]
    budget = state["mix"]["budget"]
    mark = {}

    def open_step():
        mark["t"] = time.perf_counter()
        mark["eval"] = tw.seconds + pw.seconds
        mark["ann"] = annotate("pb.step")
        mark["ann"].__enter__()
        tw.step += 1
        pw.step = tw.step

    class _DSE(LuminaDSE):
        def start(self, *a, **k):
            camp = super().start(*a, **k)
            open_step()
            return camp

    def done(camp, sample):
        now = time.perf_counter()
        mark["ann"].__exit__(None, None, None)
        steps.append((now - mark["t"],
                      tw.seconds + pw.seconds - mark["eval"]))
        mark["n"] = mark.get("n", 0) + 1
        if mark["n"] < budget:
            open_step()

    _DSE(tw, proxy=pw, seed=seed).run(budget=budget, step_callback=done)
    return mark.get("n", 0)


def setup(cfg: Dict, mix: Dict, seed: int) -> Dict:
    """Build both tiers, compile every batch bucket and detail the steps
    use, and run one campaign so that every host path is warm."""
    from jax.profiler import TraceAnnotation
    state = {"mix": mix, "seed": seed}
    for role in ("target", "proxy"):
        ev = program.evaluator(cfg, mix[f"{role}_tier"])
        _warm(ev, mix["warm"][role])
        state[role] = Recorder(ev, TraceAnnotation)
    _campaign(state, campaign_seed(seed, 2 ** 32 - 1), [], TraceAnnotation)
    return state


def window(state: Dict, seconds: float, annotate) -> Dict:
    tw, pw = state["target"], state["proxy"]
    for r in (tw, pw):
        r._annotate = annotate
        r.recording, r.calls, r.n_calls, r.seconds, r.step = (
            True, [], 0, 0.0, 0)
    steps: List = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    i = 0
    while True:
        with annotate("pb.campaign"):
            _campaign(state, campaign_seed(state["seed"], i), steps,
                      annotate)
        i += 1
        if time.perf_counter() >= deadline:
            break
    elapsed = time.perf_counter() - t0
    for r in (tw, pw):
        r.recording = False
    state["steps"] = steps
    return {"kind": "campaign", "elapsed_s": elapsed, "units": len(steps),
            "campaigns": i,
            "step_s": [s for s, _ in steps], "unit_s": [s for s, _ in steps],
            "step_eval_s": [e for _, e in steps],
            "dispatch_calls": tw.n_calls + pw.n_calls,
            "dispatch_s": tw.seconds + pw.seconds}


def check(state: Dict, cfg: Dict, mix: Dict, seed: int):
    """(numbers, failed steps): every recorded report of both tiers
    against the float64 reference."""
    worst: Dict[str, float] = {}
    bad_steps = set()
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        for role in ("target", "proxy"):
            rec = state[role]
            model = R.Model(cfg, mix[f"{role}_tier"], "float64")
            nums, per_call = compare.compare_reports(model, rec.calls)
            for k, v in nums.items():
                worst[k] = max(worst.get(k, 0), v)
            for c, u in zip(rec.calls, per_call):
                if any(u[k] > mix["limits"][k] for k in u):
                    bad_steps.add(c["step"])
    return worst, len(bad_steps)


def control(state: Dict, cfg: Dict, mix: Dict, device=None) -> Dict:
    """The bfloat16 reference in the evaluators' place: the reports it
    gives for every design the window's calls asked for, compared with the
    float64 reference as a run compares the program's."""
    worst: Dict[str, float] = {}
    for role in ("target", "proxy"):
        tier = mix[f"{role}_tier"]
        low = R.Model(cfg, tier, "bfloat16")
        fake = []
        for c in state[role].calls:
            with jax.default_device(device or jax.devices()[0]):
                rep = low.reports(c["idx"], c["names"])
            per = rep["per"]
            call = dict(step=c["step"], idx=c["idx"], names=c["names"],
                        area=rep["area"],
                        latency={nm: per[nm]["latency"] for nm in c["names"]})
            if "op_time" in c:
                call.update({k: {nm: per[nm][k] for nm in c["names"]}
                             for k in ("op_time", "stall", "op_class")})
            fake.append(call)
        with jax.enable_x64(True), \
                jax.default_device(jax.devices("cpu")[0]):
            nums, _ = compare.compare_reports(
                R.Model(cfg, tier, "float64"), fake)
        for k, v in nums.items():
            worst[k] = max(worst.get(k, 0), v)
    return worst
