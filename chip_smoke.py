#!/usr/bin/env python3
"""Bring-up smoke of the design-space explorer on a TPU.

Drives the main path once through the entry points a user calls and checks
every result against the same computation on the host's CPU device, inside
this one process (a chip belongs to one process; nothing here spawns a
child).  One chip, no arguments:

  device    platform, device kind and count; anything but a TPU exits 2
  paper     SweepEngine(get_evaluator("proxy"), stall_topk=8) over all
            4,741,632 designs vs the CPU device: n_superior exact, fronts
            equal up to ties within TOL, sampled objectives within TOL
  zoo       the zoo portfolio sweep over the full space, and its first
            1,048,576 ids vs the CPU device: per-scenario n_superior, the
            robust front, per-scenario objectives
  dispatch  evaluate(detail="stalls") at batch 32 and 4096 on the proxy and
            target tiers vs the CPU device; one fused dispatch per call
  campaign  a sweep-seeded CampaignRunner and the paper's LuminaDSE, both
            at budget 20
  kernel    the ppa_eval Pallas kernel compiled for the chip
            (tpu_custom_call in the compiled HLO) vs the traced roofline
            path, in the evaluator and in a sweep over 1,048,576 ids

``--chips 4`` runs only the multi-chip paths and what they are compared
with: the sharded full-space sweep vs the one-chip sweep (identical), and
the ``device`` worker pool vs the local evaluator (bit-identical).

    python3 chip_smoke.py
    python3 chip_smoke.py --chips 4

Facts print as ``phase,key,value`` lines.  Times are first observations on
the chip, not benchmark results.  Every phase runs even after another one
failed; any failure exits 1.  Only a run that passed prints the last line
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Relative tolerance of chip f32 against CPU f32.  A DEFAULT-precision dot
# done as one bf16 pass shows up near 1e-3, two orders above it.
TOL = 1e-5
N_SAMPLE = 4096            # sampled designs per objective comparison
SUB_RANGE = 1 << 20        # zoo reference and kernel sweep: ids [0, 2**20)
BUDGET = 20


class Smoke:
    """Prints facts and records failed checks."""

    def __init__(self):
        self.failures = []

    def fact(self, phase: str, key: str, value) -> None:
        print(f"{phase},{key},{value}", flush=True)

    def check(self, phase: str, what: str, ok: bool, detail="") -> None:
        self.fact(phase, f"check_{what}", "ok" if ok else f"FAIL {detail}")
        if not ok:
            self.failures.append(f"{phase}:{what}")

    def phase(self, name: str, fn, *args):
        t0 = time.perf_counter()
        try:
            return fn(self, *args)
        except Exception:
            traceback.print_exc()
            self.failures.append(f"{name}:raised")
            self.fact(name, "check_raised", "FAIL see stderr")
            return None
        finally:
            self.fact(name, "phase_s", f"{time.perf_counter() - t0:.2f}")


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def mem_stat(dev, key: str = "peak_bytes_in_use") -> int:
    return int((dev.memory_stats() or {}).get(key, -1))


def max_rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if not a.size:
        return 0.0
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


def cpu_twin(ev):
    """``ev`` rebuilt from its workloads; call under
    ``jax.default_device(cpu)`` so every array it makes lives there."""
    from repro.perfmodel import ModelEvaluator
    models = {nm: type(m)(m.wl, m.space) for nm, m in ev.models.items()}
    return ModelEvaluator(models, tier=ev.tier, backend=ev.backend,
                          scenarios=ev.scenarios)


def reports_equal(a, b) -> bool:
    """Bit equality of two PPAReports' arrays."""
    return (np.array_equal(a.area, b.area)
            and all(np.array_equal(getattr(a, f)[w], getattr(b, f)[w])
                    for f in ("latency", "stall", "op_time", "op_class")
                    for w in a.workloads))


def front_ties_ok(ids_a, ids_b, obj_a, obj_b, tol=TOL):
    """Fronts ``ids_a`` and ``ids_b`` agree up to ties: an id on only one
    front may be dominated on the other side only by points that lie
    within ``tol`` of it on some objective, where one ulp can flip the
    comparison.  ``obj_x(ids)`` gives objectives as device x computes them.
    Returns (ids only in a, ids only in b, ok)."""
    only_a = np.setdiff1d(ids_a, ids_b)
    only_b = np.setdiff1d(ids_b, ids_a)

    def tied(only, front_ids, objf):
        if not len(only):
            return True
        y, f = objf(only), objf(front_ids)
        for row in y:
            dom = (f <= row).all(axis=1) & (f < row).any(axis=1)
            near = ((row - f[dom]) <= tol * np.abs(row)).any(axis=1)
            if not near.all():
                return False
        return True

    ok = tied(only_a, ids_b, obj_b) and tied(only_b, ids_a, obj_a)
    return len(only_a), len(only_b), ok


def sample_ids(rng, size, extra=()):
    ids = rng.choice(size, N_SAMPLE, replace=False)
    return np.unique(np.concatenate([ids] + [np.ravel(e) for e in extra])
                     .astype(np.int64))


def timed_sweep(eng, stop=None):
    """(first-chunk seconds incl. compile, SweepResult of [0, stop))."""
    t0 = time.perf_counter()
    eng.run(0, eng.chunk_size)
    first = time.perf_counter() - t0
    return first, eng.run(0, stop)


def compiled_hlo(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


def step_args(eng):
    """Concrete arguments of ``eng``'s chunk step for lowering."""
    st = eng._fresh_state(0)
    archives = st["archives"] if eng._portfolio else [st["archive"]]
    rows = eng._pf_rows if eng._portfolio else None
    filt = np.stack([eng._filter_from_archive(a, rows) for a in archives])
    return (st["carry"], jnp.int32(0), jnp.int32(eng.size),
            jnp.asarray(filt if eng._portfolio else filt[0]))


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_device(sm, want_count):
    devs = jax.devices()
    d = devs[0]
    sm.fact("device", "platform", d.platform)
    sm.fact("device", "device_kind", d.device_kind)
    sm.fact("device", "count", len(devs))
    if d.platform != "tpu":
        print(f"no TPU: JAX found {d.platform!r} devices; this smoke has no "
              "CPU fallback", file=sys.stderr)
        sys.exit(2)
    if want_count > 1 and len(devs) != want_count:
        print(f"--chips {want_count} needs {want_count} devices, JAX found "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_paper(sm, cpu):
    from repro.perfmodel import get_evaluator
    from repro.perfmodel.sweep import SweepEngine
    ev = get_evaluator("proxy")
    eng = SweepEngine(ev, stall_topk=8)
    first, res = timed_sweep(eng)
    sm.fact("paper", "chunk", eng.chunk_size)
    sm.fact("paper", "n_evaluated", res.n_evaluated)
    sm.fact("paper", "n_superior", res.n_superior)
    sm.fact("paper", "front", len(res.pareto_ids))
    sm.fact("paper", "first_chunk_s_incl_compile", f"{first:.2f}")
    sm.fact("paper", "warm_s", f"{res.seconds:.3f}")
    sm.fact("paper", "warm_designs_per_s", f"{res.points_per_sec:.0f}")
    sm.fact("paper", "peak_bytes_in_use", mem_stat(jax.devices()[0]))
    sm.check("paper", "full_space", res.n_evaluated == eng.size,
             res.n_evaluated)

    t0 = time.perf_counter()
    with jax.default_device(cpu):
        ev_c = cpu_twin(ev)
        res_c = SweepEngine(ev_c, stall_topk=8).run()
    sm.fact("paper", "cpu_reference_s", f"{time.perf_counter() - t0:.2f}")
    sm.fact("paper", "cpu_n_superior", res_c.n_superior)
    sm.fact("paper", "cpu_front", len(res_c.pareto_ids))
    sm.check("paper", "n_superior_exact", res.n_superior == res_c.n_superior,
             f"chip {res.n_superior} cpu {res_c.n_superior}")

    from repro.perfmodel.designspace import SPACE

    def obj_chip(ids):
        return ev.objectives(SPACE.flat_to_idx(ids))

    def obj_cpu(ids):
        with jax.default_device(cpu):
            return ev_c.objectives(SPACE.flat_to_idx(ids))

    n_a, n_b, ok = front_ties_ok(res.pareto_ids, res_c.pareto_ids,
                                 obj_chip, obj_cpu)
    sm.fact("paper", "front_only_chip", n_a)
    sm.fact("paper", "front_only_cpu", n_b)
    sm.check("paper", "fronts_agree_up_to_ties", ok)
    ids = sample_ids(np.random.default_rng(0), eng.size,
                     (res.pareto_ids, res.topk_ids))
    err = max_rel(obj_chip(ids), obj_cpu(ids))
    sm.fact("paper", "tolerance", TOL)
    sm.fact("paper", "objectives_max_rel", f"{err:.3e}")
    sm.fact("paper", "objectives_compared", len(ids))
    sm.check("paper", "objectives_within_tol", err <= TOL, f"{err:.3e}")
    return res


def phase_zoo(sm, cpu):
    from repro.perfmodel import EvalRequest, get_evaluator
    from repro.perfmodel.designspace import SPACE
    from repro.perfmodel.sweep import SweepEngine
    zoo = get_evaluator("proxy", suite="zoo")
    eng = SweepEngine(zoo, stall_topk=4)
    first, res = timed_sweep(eng)
    names = res.scenario_names
    sm.fact("zoo", "scenarios", len(names))
    sm.fact("zoo", "chunk", eng.chunk_size)
    sm.fact("zoo", "n_evaluated", res.n_evaluated)
    sm.fact("zoo", "robust_n_superior", res.n_superior)
    sm.fact("zoo", "robust_front", len(res.pareto_ids))
    sm.fact("zoo", "first_chunk_s_incl_compile", f"{first:.2f}")
    sm.fact("zoo", "warm_s", f"{res.seconds:.3f}")
    sm.fact("zoo", "warm_designs_per_s", f"{res.points_per_sec:.0f}")
    sm.fact("zoo", "peak_bytes_in_use", mem_stat(jax.devices()[0]))
    sm.check("zoo", "full_space", res.n_evaluated == eng.size,
             res.n_evaluated)

    sub = eng.run(0, SUB_RANGE)
    t0 = time.perf_counter()
    with jax.default_device(cpu):
        zoo_c = cpu_twin(zoo)
        eng_c = SweepEngine(zoo_c, stall_topk=4)
        sub_c = eng_c.run(0, SUB_RANGE)
    sm.fact("zoo", "cpu_reference_ids", SUB_RANGE)
    sm.fact("zoo", "cpu_reference_s", f"{time.perf_counter() - t0:.2f}")
    chip_sup = [sub.scenario(n).n_superior for n in names] + [sub.n_superior]
    cpu_sup = [sub_c.scenario(n).n_superior for n in names] + [sub_c.n_superior]
    sm.fact("zoo", "sub_n_superior_per_scenario_and_robust",
            " ".join(map(str, chip_sup)))
    sm.check("zoo", "n_superior_exact", chip_sup == cpu_sup,
             f"cpu {' '.join(map(str, cpu_sup))}")

    def robust(ev, e, ids):
        rep = ev.evaluate(EvalRequest(SPACE.flat_to_idx(ids)))
        p = np.stack([rep.latency[s.prefill] for s in e.scenarios], 1)
        d = np.stack([rep.latency[s.decode] for s in e.scenarios], 1)
        return np.stack([(p / e.ref_points[:, 0]).max(1),
                         (d / e.ref_points[:, 1]).max(1), rep.area], 1)

    def robust_cpu(ids):
        with jax.default_device(cpu):
            return robust(zoo_c, eng_c, ids)

    n_a, n_b, ok = front_ties_ok(sub.pareto_ids, sub_c.pareto_ids,
                                 lambda ids: robust(zoo, eng, ids),
                                 robust_cpu)
    sm.fact("zoo", "sub_robust_front", len(sub.pareto_ids))
    sm.fact("zoo", "sub_front_only_chip", n_a)
    sm.fact("zoo", "sub_front_only_cpu", n_b)
    sm.check("zoo", "robust_fronts_agree_up_to_ties", ok)
    ids = sample_ids(np.random.default_rng(1), SUB_RANGE, (sub.pareto_ids,))
    y = zoo.objectives(SPACE.flat_to_idx(ids))
    with jax.default_device(cpu):
        y_c = zoo_c.objectives(SPACE.flat_to_idx(ids))
    err = max_rel(y, y_c)
    sm.fact("zoo", "tolerance", TOL)
    sm.fact("zoo", "objectives_max_rel", f"{err:.3e}")
    sm.fact("zoo", "objective_columns", y.shape[1])
    sm.check("zoo", "objectives_within_tol", err <= TOL, f"{err:.3e}")


def _class_flips_are_ties(model_c, cpu, idx, rows, cols, tol=TOL):
    """Each (row, op) whose stall class differs between devices is a near
    tie on the CPU: its two largest time terms lie within ``tol``."""
    from repro.perfmodel.hardware import derive_hardware
    with jax.default_device(cpu):
        vals = model_c.space.decode(jnp.asarray(idx[rows]))
        hw = derive_hardware(vals)
        t = model_c._op_terms({k: v[:, None] for k, v in hw.items()})
        terms = np.stack([np.asarray(t[k]) for k in
                          ("t_compute", "t_memory", "t_comm")], axis=2)
    top2 = np.sort(terms[np.arange(len(rows)), cols], axis=1)[:, -2:]
    return bool(np.all(top2[:, 1] - top2[:, 0] <= tol * top2[:, 1]))


def phase_dispatch(sm, cpu):
    from repro.perfmodel import EvalRequest, get_evaluator
    from repro.perfmodel.designspace import SPACE
    rng = np.random.default_rng(2)
    for tier in ("proxy", "target"):
        ev = get_evaluator(tier)
        with jax.default_device(cpu):
            ev_c = cpu_twin(ev)
        for b in (32, 4096):
            tag = f"{tier}_b{b}"
            idx = SPACE.sample(rng, b)
            req = EvalRequest(idx, detail="stalls")
            d0 = ev.dispatches
            t0 = time.perf_counter()
            ev.evaluate(req)
            cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            rep = ev.evaluate(req)
            warm = time.perf_counter() - t0
            calls = ev.dispatches - d0
            with jax.default_device(cpu):
                rep_c = ev_c.evaluate(req)
            sm.fact("dispatch", f"{tag}_first_call_s_incl_compile",
                    f"{cold:.3f}")
            sm.fact("dispatch", f"{tag}_second_call_s", f"{warm:.4f}")
            sm.check("dispatch", f"{tag}_one_dispatch_per_call", calls == 2,
                     f"{calls} dispatches for 2 calls")
            err = max(max_rel(rep.area, rep_c.area),
                      *(max_rel(rep.latency[w], rep_c.latency[w])
                        for w in rep.workloads),
                      *(max_rel(rep.op_time[w], rep_c.op_time[w])
                        for w in rep.workloads))
            flips, stall_err, ties = 0, 0.0, True
            for w in rep.workloads:
                diff = rep.op_class[w] != rep_c.op_class[w]
                rows, cols = np.nonzero(diff)
                flips += len(rows)
                if len(rows):
                    ties &= _class_flips_are_ties(ev_c.models[w], cpu, idx,
                                                  rows, cols)
                keep = ~diff.any(axis=1)
                lat = rep_c.latency[w][keep, None]
                if keep.any():
                    stall_err = max(stall_err, float(np.max(
                        np.abs(rep.stall[w][keep] - rep_c.stall[w][keep])
                        / lat)))
            sm.fact("dispatch", f"{tag}_max_rel", f"{err:.3e}")
            sm.fact("dispatch", f"{tag}_stall_max_rel_to_latency",
                    f"{stall_err:.3e}")
            sm.fact("dispatch", f"{tag}_op_class_flips", flips)
            sm.check("dispatch", f"{tag}_within_tol",
                     err <= TOL and stall_err <= TOL,
                     f"{err:.3e} {stall_err:.3e}")
            sm.check("dispatch", f"{tag}_class_flips_are_ties", ties)


def phase_campaign(sm, paper):
    from repro.core.campaign import CampaignRunner
    from repro.core.loop import LuminaDSE
    from repro.perfmodel import ModelEvaluator, OracleEvaluator, get_evaluator
    from repro.perfmodel.designspace import SPACE, A100_REFERENCE

    # -- sweep-seeded campaigns over the proxy tier; the oracle's sweep is
    #    the paper phase's program, so it compiles from the cache
    proxy = get_evaluator("proxy")
    oracle = OracleEvaluator(proxy, sweep_kwargs=dict(stall_topk=8))
    t0 = time.perf_counter()
    sweep = oracle.sweep_result()
    sm.fact("campaign", "oracle_sweep_s", f"{time.perf_counter() - t0:.2f}")
    if paper is not None:
        sm.check("campaign", "oracle_matches_paper_sweep",
                 sweep.n_superior == paper.n_superior
                 and np.array_equal(sweep.pareto_ids, paper.pareto_ids))
    runner = CampaignRunner(proxy, proxy=ModelEvaluator(proxy.models),
                            oracle=oracle, seed=0)
    marks = {}

    def mark(record, sample):
        marks.setdefault(record.round_i, []).append(proxy.dispatches)

    t0 = time.perf_counter()
    res = runner.run(budget=BUDGET, sweep=sweep, step_callback=mark)
    wall = time.perf_counter() - t0
    per_round = [marks[r][0] - marks[r - 1][-1]
                 for r in sorted(marks) if r - 1 in marks]
    sm.fact("campaign", "runner_campaigns", len(res.per_campaign))
    sm.fact("campaign", "runner_evaluations", len(res.samples))
    sm.fact("campaign", "runner_rounds", res.rounds)
    sm.fact("campaign", "runner_dispatches", res.dispatches)
    sm.fact("campaign", "runner_dispatches_per_round_max",
            max(per_round, default=0))
    sm.fact("campaign", "runner_superior", res.superior_count)
    sm.fact("campaign", "runner_phv_frac_of_oracle",
            f"{res.phv_frac_curve()[-1]:.4f}")
    sm.fact("campaign", "runner_wall_s", f"{wall:.2f}")
    sm.check("campaign", "runner_evaluations", len(res.samples) == BUDGET)
    sm.check("campaign", "runner_one_dispatch_per_round",
             max(per_round, default=0) <= 1, per_round)

    # -- the paper's budget-20 loop on the target tier
    target = get_evaluator("target")
    dse_proxy = get_evaluator("proxy")
    compass_oracle = get_evaluator("oracle", "compass")
    ref = target.objectives(SPACE.encode_nearest(A100_REFERENCE)[None, :])[0]
    steps = []
    d0 = target.dispatches
    t0 = time.perf_counter()
    out = LuminaDSE(target, proxy=dse_proxy, seed=0).run(
        budget=BUDGET,
        step_callback=lambda camp, s: steps.append(target.dispatches))
    wall = time.perf_counter() - t0
    per_step = np.diff([d0] + steps)
    t1 = time.perf_counter()
    frac = compass_oracle.normalized_phv(out.phv, ref)
    sm.fact("campaign", "lumina_evaluations", len(out.samples))
    sm.fact("campaign", "lumina_dispatches", int(target.dispatches - d0))
    sm.fact("campaign", "lumina_dispatches_per_step_max",
            int(per_step.max()))
    sm.fact("campaign", "lumina_superior", out.superior_count)
    sm.fact("campaign", "lumina_phv_frac_of_oracle", f"{frac:.4f}")
    sm.fact("campaign", "lumina_wall_s", f"{wall:.2f}")
    sm.fact("campaign", "compass_oracle_sweep_s",
            f"{time.perf_counter() - t1:.2f}")
    sm.check("campaign", "lumina_evaluations", len(out.samples) == BUDGET)
    sm.check("campaign", "lumina_one_dispatch_per_step",
             int(per_step.max()) <= 1, per_step.tolist())


def phase_kernel(sm):
    from repro.perfmodel import get_evaluator
    from repro.perfmodel.designspace import SPACE
    from repro.perfmodel.sweep import SweepEngine
    traced = get_evaluator("proxy")
    pal = get_evaluator("proxy", "pallas")
    sm.fact("kernel", "evaluator_backend", pal.backend)
    rng = np.random.default_rng(3)
    for b in (32, 4096):
        fn = pal._fused_fn("objectives", pal.workloads).jitted
        hlo = compiled_hlo(fn, jnp.zeros((b, SPACE.n_params), jnp.int32))
        sm.check("kernel", f"evaluator_b{b}_tpu_custom_call",
                 "tpu_custom_call" in hlo)
        idx = SPACE.sample(rng, b)
        err = max_rel(pal.objectives(idx), traced.objectives(idx))
        sm.fact("kernel", f"evaluator_b{b}_max_rel", f"{err:.3e}")
        sm.check("kernel", f"evaluator_b{b}_within_tol", err <= TOL,
                 f"{err:.3e}")

    eng = SweepEngine(pal, stall_topk=8)
    sm.fact("kernel", "sweep_backend", eng.backend)
    first, res = timed_sweep(eng, SUB_RANGE)
    hlo = compiled_hlo(eng._step, *step_args(eng))
    sm.check("kernel", "sweep_step_tpu_custom_call",
             "tpu_custom_call" in hlo)
    sm.fact("kernel", "sweep_ids", res.n_evaluated)
    sm.fact("kernel", "sweep_first_chunk_s_incl_compile", f"{first:.2f}")
    sm.fact("kernel", "sweep_warm_designs_per_s", f"{res.points_per_sec:.0f}")
    ref = SweepEngine(traced, stall_topk=8).run(0, SUB_RANGE)
    sm.fact("kernel", "sweep_n_superior", res.n_superior)
    sm.check("kernel", "sweep_n_superior_matches_traced",
             res.n_superior == ref.n_superior,
             f"kernel {res.n_superior} traced {ref.n_superior}")

    def obj_pal(ids):
        return pal.objectives(SPACE.flat_to_idx(ids))

    def obj_traced(ids):
        return traced.objectives(SPACE.flat_to_idx(ids))

    n_a, n_b, ok = front_ties_ok(res.pareto_ids, ref.pareto_ids,
                                 obj_pal, obj_traced)
    sm.fact("kernel", "sweep_front_only_kernel", n_a)
    sm.fact("kernel", "sweep_front_only_traced", n_b)
    sm.check("kernel", "sweep_fronts_agree_up_to_ties", ok)


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------

def phase_sharded(sm):
    from repro.perfmodel import get_evaluator
    from repro.perfmodel.sweep import SweepEngine
    ev = get_evaluator("proxy")
    one = SweepEngine(ev, stall_topk=8).run()
    eng = SweepEngine(ev, stall_topk=8, shard=True)
    first, res = timed_sweep(eng)
    sm.fact("sharded", "chunk", eng.chunk_size)
    sm.fact("sharded", "chunk_devices",
            sorted(d.id for d in eng._iota.devices()))
    sm.fact("sharded", "peak_bytes_per_device",
            " ".join(str(mem_stat(d)) for d in jax.devices()))
    sm.fact("sharded", "first_chunk_s_incl_compile", f"{first:.2f}")
    sm.fact("sharded", "warm_designs_per_s", f"{res.points_per_sec:.0f}")
    sm.fact("sharded", "n_superior", res.n_superior)
    sm.fact("sharded", "front", len(res.pareto_ids))
    sm.check("sharded", "spans_all_devices",
             len(eng._iota.devices()) == len(jax.devices()))
    sm.check("sharded", "n_superior_identical",
             res.n_superior == one.n_superior,
             f"{res.n_superior} vs {one.n_superior}")
    sm.check("sharded", "front_identical",
             np.array_equal(res.pareto_ids, one.pareto_ids))
    sm.fact("sharded", "front_values_identical",
            np.array_equal(res.pareto_y, one.pareto_y))
    sm.check("sharded", "topk_identical",
             np.array_equal(res.topk_ids, one.topk_ids))
    sm.check("sharded", "stall_seeds_identical",
             np.array_equal(res.stall_topk_ids, one.stall_topk_ids))


def phase_device_pool(sm):
    from repro.perfmodel import EvalRequest, get_evaluator
    from repro.perfmodel.designspace import SPACE
    local = get_evaluator("proxy")
    pool = get_evaluator("proxy", workers=4, mode="device")
    idx = SPACE.sample(np.random.default_rng(4), 4096)
    req = EvalRequest(idx, detail="stalls")
    rep_l = local.evaluate(req)
    keys = ("num_allocs", "peak_bytes_in_use")
    before = {k: [mem_stat(d, k) for d in jax.devices()] for k in keys}
    rep_p = pool.evaluate(req)
    after = {k: [mem_stat(d, k) for d in jax.devices()] for k in keys}
    sm.fact("device_pool", "shards", pool.worker_dispatches)
    for k in keys:
        sm.fact("device_pool", f"{k}_before", " ".join(map(str, before[k])))
        sm.fact("device_pool", f"{k}_after", " ".join(map(str, after[k])))
    # each device allocated during the pooled call (num_allocs counts live
    # allocations, the peak only grows)
    grew = [any(after[k][i] > before[k][i] for k in keys)
            for i in range(len(jax.devices()))]
    sm.check("device_pool", "work_on_every_device", all(grew), grew)
    # the same shards evaluated locally, on the default device: placement
    # is then the only difference, and the pool must not change a bit
    from repro.distributed.sharded import concat_reports
    rep_s = concat_reports([local.evaluate(EvalRequest(s, "stalls"))
                            for s in np.array_split(idx, 4)])
    sm.check("device_pool", "bit_identical_to_local_shards",
             reports_equal(rep_p, rep_s))
    # against one local 4096-row call the per-row arithmetic may differ:
    # XLA:TPU compiles the 1024-row shard program differently
    err = max(max_rel(rep_p.area, rep_l.area),
              *(max_rel(getattr(rep_p, f)[w], getattr(rep_l, f)[w])
                for f in ("latency", "stall", "op_time")
                for w in rep_l.workloads))
    sm.fact("device_pool", "bit_identical_to_local_4096_call",
            reports_equal(rep_p, rep_l))
    sm.fact("device_pool", "max_rel_to_local_4096_call", f"{err:.3e}")
    sm.check("device_pool", "local_4096_call_within_tol", err <= TOL,
             f"{err:.3e}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths")
    args = ap.parse_args(argv)
    from repro.runtime.chip import enable_compile_cache
    enable_compile_cache()
    sm = Smoke()
    device = phase_device(sm, args.chips)
    if args.chips == 4:
        # the pool first: its per-device allocations are then not hidden
        # behind the sharded sweep's peaks
        sm.phase("device_pool", phase_device_pool)
        sm.phase("sharded", phase_sharded)
    else:
        cpu = jax.devices("cpu")[0]
        paper = sm.phase("paper", phase_paper, cpu)
        sm.phase("zoo", phase_zoo, cpu)
        sm.phase("dispatch", phase_dispatch, cpu)
        sm.phase("campaign", phase_campaign, paper)
        sm.phase("kernel", phase_kernel)
    if sm.failures:
        print("FAILED: " + " ".join(sm.failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
