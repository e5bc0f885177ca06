"""The comparison that decides ``correct``.

The helpers at the top (``max_rel``, ``front_ties_ok``'s tie rule and the
stall-class tie rule) are copies of the comparison in the repository's
``chip_smoke.py``, kept here so that the yardstick does not move when the
smoke changes.  Below them: the reference's own reductions of a full-space
sweep (superiority counts, top-k, per-class seeds, the exact Pareto
front), and the numbers a run compares against their limits.

Every number compared is a count or a relative error against the float64
reference of :mod:`reference`.  ``TIE`` is the relative distance within
which two float32 computations of one quantity may order differently; an
outcome that differs only inside it is a tie, not an error.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from harness import reference as R

TIE = 1e-5


# ------------------------------------------------------- copied from smoke
def max_rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if not a.size:
        return 0.0
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


def unexplained_front_ids(ids_a, ids_b, y_a, y_b, tol=TIE) -> int:
    """Ids on exactly one of fronts ``a`` and ``b`` that no tie explains.

    ``y_x`` holds the reference's objectives of the ids of front x.  An id
    only on ``a`` must be dominated on ``b``'s side only by points within
    ``tol`` of it on some objective (one ulp could flip the comparison);
    an id only on ``b`` must be covered within ``tol`` by a point of ``a``
    (``a`` dropped it for a near duplicate or a near dominator)."""
    ids_a, ids_b = np.asarray(ids_a), np.asarray(ids_b)
    only_a = ~np.isin(ids_a, ids_b)
    only_b = ~np.isin(ids_b, ids_a)
    bad = 0
    for row in y_a[only_a]:
        dom = (y_b <= row).all(axis=1) & (y_b < row).any(axis=1)
        near = ((row - y_b[dom]) <= tol * np.abs(row)).any(axis=1)
        bad += int(not near.all())
    for row in y_b[only_b]:
        covered = (y_a <= row * (1.0 + tol)).all(axis=1)
        bad += int(not covered.any())
    return bad


def class_flip_is_tie(terms: np.ndarray, tol=TIE) -> np.ndarray:
    """(n, k) candidate times -> (n,) True where the two largest lie within
    ``tol`` of each other, so an ulp can change which one is dominant."""
    top2 = np.sort(terms, axis=1)[:, -2:]
    return top2[:, 1] - top2[:, 0] <= tol * np.abs(top2[:, 1])


# --------------------------------------------------------- exact fronts
@jax.jit
def _dominated_tile(p, f):
    """(B, 3) points x (F, 3) filter rows -> (B,) dominated by some row."""
    le = jnp.ones((p.shape[0], f.shape[0]), bool)
    lt = jnp.zeros_like(le)
    for j in range(3):
        le &= f[None, :, j] <= p[:, None, j]
        lt |= f[None, :, j] < p[:, None, j]
    return (le & lt).any(axis=1)


def dominated_by(points: np.ndarray, filt: np.ndarray,
                 pb: int = 1 << 16) -> np.ndarray:
    """Mask of ``points`` strictly dominated by any row of ``filt``."""
    out = np.zeros(len(points), bool)
    if not len(filt) or not len(points):
        return out
    fb = min(1024, 1 << max(3, (len(filt) - 1).bit_length()))
    cpu = jax.devices("cpu")[0]
    fpad = np.full((-(-len(filt) // fb) * fb, 3), np.inf)
    fpad[:len(filt)] = filt
    with jax.default_device(cpu):
        for s in range(0, len(points), pb):
            blk = points[s:s + pb]
            pad = np.full((pb, 3), np.inf)
            pad[:len(blk)] = blk
            x = jnp.asarray(pad)
            acc = np.zeros(pb, bool)
            for t in range(0, len(fpad), fb):
                acc |= np.asarray(_dominated_tile(x, jnp.asarray(
                    fpad[t:t + fb])))
            out[s:s + len(blk)] = acc[:len(blk)]
    return out


def pareto_ids(y: np.ndarray, hint: np.ndarray = ()) -> np.ndarray:
    """Indices of the rows of ``y`` (N, 3) that no other row dominates
    (duplicates of a front point are all kept), ascending.

    Exact whatever ``hint`` holds: rows dominated by any real row are off
    the front, so the filter only needs good killers.  They come from the
    minima of 16 scalarizations, then from the front of those and of the
    ``hint`` rows (a claimed front, checked like any other row); the few
    survivors are compared pairwise."""
    n = len(y)
    if n == 0:
        return np.zeros(0, np.int64)
    logy = np.log(np.maximum(y, 1e-300))
    w = np.concatenate([np.eye(3),
                        np.random.default_rng(0).dirichlet(np.ones(3), 13)])
    first = np.unique(np.argmin(logy @ w.T, axis=0))
    alive = np.flatnonzero(~dominated_by(y, y[first]))
    cand = np.unique(np.concatenate([first, np.asarray(hint, np.int64)]))
    kill = cand[~dominated_by(y[cand], y[cand])]
    alive = alive[~dominated_by(y[alive], y[kill])]
    return alive[~dominated_by(y[alive], y[alive])]


# -------------------------------------------------- sweep reference result
class SweepReference:
    """The float64 reference's full-space reductions for every group of a
    sweep: the scenarios and, in a portfolio, the robust front."""

    def __init__(self, model: "R.Model", ids: np.ndarray, ys: np.ndarray,
                 dom: np.ndarray, robust: bool):
        self.model = model
        self.ids = ids                        # (N,) flat ids, ascending
        ref_idx = R.idx_to_flat(R.nearest_idx(R.A100))
        pos = int(np.searchsorted(ids, ref_idx))
        if pos < len(ids) and ids[pos] == ref_idx:
            refs = ys[pos]
        else:
            refs, _, _ = model.sweep(np.array([ref_idx]))
            refs = refs[0]
        self.refs = refs                       # (S, 3)
        self.groups: List[np.ndarray] = [ys[:, s, :] for s in
                                         range(ys.shape[1])]
        self.group_refs = [refs[s] for s in range(ys.shape[1])]
        if robust:
            ratio = ys[:, :, :2] / refs[None, :, :2]
            self.groups.append(np.concatenate(
                [ratio.max(axis=1), ys[:, 0, 2:3]], axis=1))
            self.group_refs.append(np.array([1.0, 1.0, refs[0, 2]]))
        self.dom = dom                         # (N, S)
        self._fronts: Dict[int, np.ndarray] = {}

    def front(self, g: int, hint: np.ndarray = ()) -> np.ndarray:
        """Exact front of group g (ids); ``hint`` ids speed it up only."""
        if g not in self._fronts:
            pos = np.searchsorted(self.ids, np.asarray(hint, np.int64))
            pos = pos[(pos < len(self.ids))]
            self._fronts[g] = self.ids[pareto_ids(self.groups[g], pos)]
        return self._fronts[g]

    def values(self, g: int, ids: np.ndarray) -> np.ndarray:
        pos = np.searchsorted(self.ids, np.asarray(ids, np.int64))
        return self.groups[g][pos]

    def superior_band(self, g: int, tol=TIE):
        y, ref = self.groups[g], self.group_refs[g]
        lo = int((y < ref * (1.0 - tol)).all(axis=1).sum())
        hi = int((y < ref * (1.0 + tol)).all(axis=1).sum())
        return lo, hi

    def topk(self, g: int, k: int) -> np.ndarray:
        y = self.groups[g]
        return np.stack([np.sort(np.partition(y[:, o], k - 1)[:k])
                         for o in range(3)])

    def stall_topk(self, s: int, c: int, k: int):
        """(values, ids) of the k lowest prefill latencies whose dominant
        prefill stall is class c, in scenario s."""
        mask = self.dom[:, s] == c
        vals = self.groups[s][mask, 0]
        ids = self.ids[mask]
        if len(vals) > k:
            sel = np.argpartition(vals, k - 1)[:k]
            vals, ids = vals[sel], ids[sel]
        order = np.argsort(vals, kind="stable")
        return vals[order], ids[order]


def sweep_result_groups(res, n_scen: int):
    """Per-group views of a program SweepResult: (n_superior, front ids,
    front values, top-k values (3, k), top-k ids (3, k)) per group, and
    per scenario the stall top-k (values (4, k), ids (4, k))."""
    if res.per_scenario is not None:
        parts = [res.scenario(nm) for nm in res.scenario_names] + [res]
    else:
        parts = [res]
    groups = [dict(n_superior=int(p.n_superior),
                   front_ids=np.asarray(p.pareto_ids, np.int64),
                   front_y=np.asarray(p.pareto_y, np.float64),
                   topk_val=np.asarray(p.topk_val, np.float64),
                   topk_ids=np.asarray(p.topk_ids, np.int64),
                   truncated=bool(p.archive_truncated))
              for p in parts]
    seeds = [dict(val=np.asarray(p.stall_topk_val, np.float64),
                  ids=np.asarray(p.stall_topk_ids, np.int64))
             if p.stall_topk_ids is not None else None
             for p in parts[:n_scen]]
    return groups, seeds


def compare_sweep(ref: SweepReference, groups: List[Dict],
                  seeds: List[Optional[Dict]], n_evaluated: int,
                  size: int) -> Dict[str, float]:
    """The numbers of one program sweep result against the reference."""
    rel = 0.0
    miscount = 0
    front_bad = 0
    seed_bad = 0
    for g, pg in enumerate(groups):
        # every front value, and each top-k value at its own id and at its
        # rank, against the reference
        rel = max(rel, max_rel(pg["front_y"], ref.values(g, pg["front_ids"])))
        k = pg["topk_val"].shape[1]
        rel = max(rel, max_rel(pg["topk_val"], ref.topk(g, k)))
        for o in range(3):
            ok = pg["topk_ids"][o] >= 0
            rel = max(rel, max_rel(pg["topk_val"][o][ok],
                                   ref.values(g, pg["topk_ids"][o][ok])[:, o]))
        # superiority: outside the tie band the count is exact; the whole
        # space must have been scored, and the front may not be truncated
        lo, hi = ref.superior_band(g)
        n = pg["n_superior"]
        miscount += max(0, lo - n, n - hi) + abs(size - n_evaluated)
        rf = ref.front(g, hint=pg["front_ids"])
        front_bad += unexplained_front_ids(
            pg["front_ids"], rf, ref.values(g, pg["front_ids"]),
            ref.values(g, rf)) + int(pg["truncated"])
    for s, sd in enumerate(seeds):
        if sd is None:
            continue
        bad, seed_rel = _seed_mismatches(ref, s, sd)
        seed_bad += bad
        rel = max(rel, seed_rel)
    return {"objective_rel_err": rel, "superior_miscount": miscount,
            "front_unexplained": front_bad,
            "stall_seed_unexplained": seed_bad}


def _seed_mismatches(ref: SweepReference, s: int, sd: Dict):
    """Per stall class, the program's seeds against the reference's: a
    seed of the wrong class, a missing seed, or a reference seed the
    program missed, counts unless a near tie (of two stall sums, or of the
    ranking latency at the k-th place) explains it.  Returns that count and
    the worst relative error of a seed's ranking value at its id."""
    k = sd["ids"].shape[1]
    bad = 0
    rel = 0.0
    names = ref.model.workload_tables()
    pre_name = list(names)[2 * s]
    for c in range(R.N_STALL):
        pid = sd["ids"][c][sd["ids"][c] >= 0]
        rv, rid = ref.stall_topk(s, c, k)
        wrong_class = ref.dom[np.searchsorted(ref.ids, pid), s] != c
        missed = ~np.isin(rid, pid)
        kth = sd["val"][c][len(pid) - 1] if len(pid) else np.inf
        missed &= rv < kth * (1.0 - TIE)
        suspects = np.concatenate([pid[wrong_class], rid[missed]])
        if len(pid) != len(rid):
            bad += abs(len(pid) - len(rid))
        if len(pid):
            rel = max(rel, max_rel(sd["val"][c][:len(pid)],
                                   ref.values(s, pid)[:, 0]))
        if len(suspects):
            rep = ref.model.reports(R.flat_to_idx(suspects), (pre_name,))
            bad += int((~class_flip_is_tie(
                rep["per"][pre_name]["stall"])).sum())
    return bad, rel


# ----------------------------------------------------------- campaign
def compare_reports(model: "R.Model", calls: Sequence[Dict]):
    """Every report an evaluator returned against the reference.

    ``calls`` holds one dict per call: ``idx`` (n, 8), ``names``, and the
    returned ``area``, ``latency`` and, at stalls detail, ``op_time``,
    ``stall`` and ``op_class``, each keyed by workload name.  Returns the
    worst numbers over all calls, and the numbers of each call.

    Relative errors: area and latencies against the reference's; per-op
    times and per-class stall sums against the workload's latency (a
    stall sum is compared only in rows where no op changed class).  A per-op
    class that differs from the reference's counts unless its two largest
    time terms are a near tie."""
    per_call = [{"report_rel_err": 0.0, "op_class_unexplained": 0}
                for _ in calls]
    by_names: Dict[tuple, List[int]] = {}
    for i, c in enumerate(calls):
        by_names.setdefault(tuple(c["names"]), []).append(i)
    for names, members in by_names.items():
        group = [calls[i] for i in members]
        offs = np.cumsum([0] + [len(c["area"]) for c in group])
        rep = model.reports(np.concatenate([c["idx"] for c in group]), names)
        row_err = _rel(np.concatenate([c["area"] for c in group]),
                       rep["area"])
        row_bad = np.zeros(len(row_err), np.int64)
        for nm in names:
            r = rep["per"][nm]
            lat = np.concatenate([c["latency"][nm] for c in group])
            row_err = np.maximum(row_err, _rel(lat, r["latency"]))
            detailed = [j for j, c in enumerate(group) if "op_time" in c]
            if not detailed:
                continue
            sel = np.concatenate([np.arange(offs[j], offs[j + 1])
                                  for j in detailed])
            op_t = np.concatenate([group[j]["op_time"][nm] for j in detailed])
            stall = np.concatenate([group[j]["stall"][nm] for j in detailed])
            cls = np.concatenate([group[j]["op_class"][nm] for j in detailed])
            scale = r["latency"][sel][:, None]
            err = (np.abs(op_t - r["op_time"][sel]) / scale).max(axis=1)
            diff = cls != r["op_class"][sel]
            st_err = (np.abs(stall - r["stall"][sel]) / scale).max(axis=1)
            err = np.maximum(err, np.where(diff.any(axis=1), 0.0, st_err))
            row_err[sel] = np.maximum(row_err[sel], err)
            rr, cc = np.nonzero(diff)
            if len(rr):
                terms = np.stack([r[t][sel][rr, cc] for t in
                                  ("t_compute", "t_memory", "t_comm")],
                                 axis=1)
                np.add.at(row_bad, sel[rr], ~class_flip_is_tie(terms))
        for j, i in enumerate(members):
            a, b = offs[j], offs[j + 1]
            per_call[i]["report_rel_err"] = float(row_err[a:b].max())
            per_call[i]["op_class_unexplained"] = int(row_bad[a:b].sum())
    worst = {"report_rel_err": max([u["report_rel_err"] for u in per_call],
                                   default=0.0),
             "op_class_unexplained": sum(u["op_class_unexplained"]
                                         for u in per_call)}
    return worst, per_call


def _rel(a, b) -> np.ndarray:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(np.abs(b), 1e-300)
