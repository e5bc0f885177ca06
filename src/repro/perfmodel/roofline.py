"""Vectorized roofline evaluation of (designs x workload ops).

Per-op time = max(compute-term, memory-term, interconnect-term) under an
effective-throughput model that couples every design-space parameter to the
metrics it physically influences:

* systolic utilization   <- sa_dim vs matmul dims (padding + pipeline fill),
  sublane/core tile parallelism, SRAM double-buffer capacity;
* HBM traffic            <- compulsory bytes vs blocked-matmul I/O lower
  bound 2*M*N*K/sqrt(gbuf) (global-buffer reuse);
* collectives            <- ring all-reduce / all-to-all on the ICI links.

Evaluating the *entire* 4.7M-point space takes a few seconds in the CPU
container (a CPU-container time, not a chip measurement; the paper reports
6000 CPU-hours per 1000 LLMCompass samples — this is the substrate speedup
that lets us run 1000-sample DSE campaigns in CI).

This module is the core of the surface :mod:`repro.analysis.influence`
parses: ``RooflineModel._op_terms`` defines the derived -> op-term edges,
``_dominant_class`` the term -> stall attribution (its ``jnp.where`` guard
tree becomes per-edge workload-kind constraints), and the division
denominators the per-class PEAK throughputs from which the AHK primary
stall -> parameter edges are derived.  After restructuring any of these,
re-run ``python -m repro.analysis.extract --check`` (CI does) and refresh
the artifact with ``--write`` if the edge change is intentional.
"""
from __future__ import annotations

import hashlib
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import NOOP
from repro.perfmodel import workload as W
from repro.perfmodel.designspace import DesignSpace, SPACE
from repro.perfmodel.hardware import derive_hardware, BYTES_FP16, LINK_LATENCY_S

# stall classes (aligned with critical_path.STALL_CLASSES)
TENSOR, VECTORU, MEMORY, INTERCONNECT = 0, 1, 2, 3

# SRAM operand-feed bandwidth: words/cycle supplied per KB of per-core SRAM
# (more capacity = more banks).  Calibrated so the A100 point (128 KB feeding
# a 16x16 array x 4 sublanes = 64 words/cycle) is exactly unconstrained while
# a 32x32 array x 4 sublanes on the same SRAM runs at 62.5% feed utilization
# — reproducing the Table-4 performance deltas of designs A/B.
SRAM_FEED_WORDS_PER_KB = 0.625


def _ceil_div(a, b):
    return jnp.ceil(a / b)


def matmul_utilization(hw: Dict[str, jnp.ndarray], m, n, k) -> jnp.ndarray:
    """Fraction of peak tensor throughput achieved on an (m,k)x(k,n) matmul.

    Three multiplicative effects:
      u_pad  — K and N pad to the sa_dim grid (weight-stationary mapping);
      u_pipe — pipeline fill: each output tile streams m rows through a
               sa-deep array (m / (m + sa));
      u_par  — not enough independent output tiles to fill cores*sublanes;
      u_sram — double-buffered A/B/C tiles must fit the per-core SRAM;
      u_feed — SRAM operand-feed bandwidth: a sa-wide array consumes
               sa*sublanes words/cycle; SRAM banks supply
               SRAM_FEED_WORDS_PER_KB * sram_kb words/cycle.  This is the
               paper's noted pitfall: enlarging the systolic array without
               scaling SRAM causes significant compute under-utilization.
    """
    sa = hw["sa_dim"]
    u_k = k / (_ceil_div(k, sa) * sa)
    u_n = n / (_ceil_div(n, sa) * sa)
    u_pipe = m / (m + sa)
    n_tiles = _ceil_div(m, sa) * _ceil_div(n, sa)
    u_par = jnp.minimum(1.0, n_tiles / (hw["core_count"] * hw["sublane_count"]))
    sram_need_kb = 3.0 * 2.0 * sa * sa * BYTES_FP16 / 1024.0   # A,B,C x dbuf
    u_sram = jnp.minimum(1.0, hw["sram_kb"] / sram_need_kb)
    u_feed = jnp.minimum(
        1.0, SRAM_FEED_WORDS_PER_KB * hw["sram_kb"]
        / (sa * hw["sublane_count"]))
    return u_k * u_n * u_pipe * u_par * u_sram * u_feed


def matmul_hbm_bytes(hw, compulsory, m, n, k) -> jnp.ndarray:
    """Blocked-matmul HBM traffic: max(compulsory, I/O lower bound given the
    global buffer as the reuse capacity)."""
    f_elems = jnp.maximum(hw["gbuf_bytes"] / BYTES_FP16, 1.0)
    bound = 2.0 * m * n * k / jnp.sqrt(f_elems) * BYTES_FP16
    return jnp.maximum(compulsory, bound)


def ring_allreduce_time(hw, nbytes, tp) -> jnp.ndarray:
    steps = 2.0 * (tp - 1.0)
    return steps / tp * nbytes / hw["ici_bw"] + steps * LINK_LATENCY_S


def a2a_time(hw, nbytes, tp) -> jnp.ndarray:
    return (tp - 1.0) / tp * nbytes / hw["ici_bw"] + (tp - 1.0) * LINK_LATENCY_S


# Shared compiled-evaluator cache.  Keyed by everything that changes the
# traced computation (model class + knobs, design space, workload op arrays,
# TP degree), so every RooflineModel/CompassModel built for the same workload
# — across baselines, DSE campaigns and benchmark modules — reuses one
# XLA executable per batch shape instead of re-tracing per instance.
_JIT_CACHE: Dict[tuple, "PackedFn"] = {}


def _space_key(space: DesignSpace) -> tuple:
    return tuple(tuple(float(v) for v in c) for c in space.choices)


def _workload_fingerprint(wl: W.Workload) -> str:
    a = wl.arrays()
    h = hashlib.sha1()
    for kk in sorted(a):
        h.update(kk.encode())
        h.update(np.ascontiguousarray(a[kk]).tobytes())
    return h.hexdigest()


def _batch_bucket(b: int) -> int:
    """Round a batch size up to the next power of two (min 8) so repeated
    odd-size calls hit a handful of compiled shapes instead of retracing."""
    bb = 8
    while bb < b:
        bb *= 2
    return bb


def _split_sinks(tree) -> tuple:
    """``(kept, sinks)``: the tree without its underscore-keyed entries
    (device-only materialization sinks like ``"_sink"``, never copied to
    the host), and those entries."""
    if not isinstance(tree, dict):
        return tree, []
    kept, sinks = {}, []
    for k, v in tree.items():
        if str(k).startswith("_"):
            sinks.append(v)
        else:
            kept[k], sub = _split_sinks(v)
            sinks += sub
    return kept, sinks


def _as_words(v: jnp.ndarray) -> jnp.ndarray:
    """A report leaf as flat uint32 words: 4-byte dtypes bit for bit, bool
    as 0/1."""
    if v.dtype == jnp.bool_:
        return v.astype(jnp.uint32).reshape(-1)
    if v.dtype.itemsize != 4:
        raise TypeError(
            f"a packed report carries 4-byte and bool leaves only; got a "
            f"{v.dtype} leaf of shape {v.shape}")
    return jax.lax.bitcast_convert_type(v, jnp.uint32).reshape(-1)


def _word_layout(leaves, rows: int) -> tuple:
    """Each leaf's ``(dtype, trailing shape, offset, words a row)`` in the
    packed buffer's uint32 words, leaf-major."""
    parts, lo = [], 0
    for v in leaves:
        if v.shape[:1] != (rows,):
            raise ValueError(f"report leaf of shape {v.shape} has no "
                             f"leading batch axis of {rows}")
        row = int(np.prod(v.shape[1:]))
        parts.append((np.dtype(v.dtype), tuple(v.shape[1:]), lo, row))
        lo += rows * row
    return tuple(parts)


class PackedFn:
    """A report function jitted with its pack step: ONE executable whose
    report comes back to the host as one flat uint32 buffer.

    The pack step puts every kept leaf through one optimization barrier
    together with the ``_``-keyed sinks, bitcasts each leaf to uint32 and
    concatenates them leaf-major.  Behind the barrier the computation
    fuses exactly as when the leaves were the outputs, so the reports stay
    bit-identical.  The sinks stay outputs of the executable and are never
    fetched: dropped inside the jit, dead-code removal would un-materialize
    ``t_op`` before the latency reduce (a ULP of drift; see
    :func:`stacked_workload_batches`).

    :meth:`layout` gives, per input shape, the stripped report's treedef
    and each leaf's dtype, trailing shape, offset and words a row.  The
    pack step records it while it traces, and ``jax.eval_shape`` of the
    jitted function shares that one trace with the call, so it is worked
    out once per (executable, bucket) and kept here — the object
    ``_JIT_CACHE`` and each :class:`~repro.perfmodel.evaluator.
    ModelEvaluator`'s ``_fns`` hold.
    """

    def __init__(self, fn: Callable):
        self.fn = fn                                  # unpacked, unjitted
        self.jitted = jax.jit(self._pack)
        self._layouts: Dict[tuple, tuple] = {}

    def _pack(self, x: jnp.ndarray):
        kept, sinks = _split_sinks(self.fn(x))
        leaves, treedef = jax.tree_util.tree_flatten(kept)
        self._layouts[(x.shape, x.dtype)] = (
            treedef, _word_layout(leaves, x.shape[0]))
        leaves, sinks = jax.lax.optimization_barrier((leaves, sinks))
        return jnp.concatenate([_as_words(v) for v in leaves]), sinks

    def layout(self, x: jnp.ndarray) -> tuple:
        """``(treedef, ((dtype, rest, offset, row), ...))`` for ``x``."""
        key = (x.shape, x.dtype)
        if key not in self._layouts:
            jax.eval_shape(self.jitted, jax.ShapeDtypeStruct(*key))
        return self._layouts[key]


def _unpack(words: np.ndarray, parts: tuple, b: int) -> list:
    """Read-only views of each leaf's first ``b`` rows, which lie
    contiguous in the fetched buffer (the batch axis leads)."""
    words.flags.writeable = False
    leaves = []
    for dtype, rest, lo, row in parts:
        w = words[lo:lo + b * row]
        if dtype == np.bool_:
            w = w.astype(bool)
            w.flags.writeable = False
        leaves.append(w.view(dtype).reshape((b,) + rest))
    return leaves


def _bucketed_call(fn: PackedFn, idx: np.ndarray, tracer=NOOP):
    """Pad an index batch to its power-of-two bucket, call a packed `fn`,
    copy its report back in ONE transfer, and cut every leaf back to the
    true batch size.

    The single pad/slice implementation behind the fused
    :class:`~repro.perfmodel.evaluator.ModelEvaluator` dispatch path.  The
    executable hands back one flat uint32 buffer (:class:`PackedFn`); the
    leaves are contiguous read-only views of its host copy, rebuilt into
    the report tree with ``fn``'s cached layout.  Sink outputs (keys
    starting with ``_``) stay on the device.  Its phases are ``tracer`` spans:
    ``eval.upload`` (pad + upload), ``eval.launch`` (the jitted call until
    it returns) and ``eval.fetch`` (the one blocking copy and the views;
    ``leaves`` counts the report leaves, ``copies`` the device-to-host
    transfers).
    """
    with tracer.span("eval.upload"):
        idx = np.atleast_2d(np.asarray(idx, dtype=np.int32))
        b = idx.shape[0]
        bb = _batch_bucket(b)
        if bb != b:                   # pad with the last row; slice back
            idx = np.concatenate([idx, np.repeat(idx[-1:], bb - b, axis=0)])
        x = jnp.asarray(idx)
    with tracer.span("eval.launch"):
        buf, _ = fn.jitted(x)            # ONE dispatch: (buffer, sinks)
    tree, parts = fn.layout(x)
    with tracer.span("eval.fetch", leaves=len(parts), copies=1):
        return jax.tree_util.tree_unflatten(
            tree, _unpack(np.asarray(buf), parts, b))


def _dominant_class(t: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Dominant-resource class per op from `_op_terms` components.

    THE attribution rule (ties: comm wins on >=, compute needs a strict >
    over memory; pure memcpy ops always attribute to MEMORY) — shared by
    :func:`_attribute` and the portfolio sweep's union-level stall pass so
    the two can never drift apart.
    """
    t_compute, t_memory, t_comm = t["t_compute"], t["t_memory"], t["t_comm"]
    dom_is_comm = (t_comm >= t_compute) & (t_comm >= t_memory)
    dom_is_compute = (t_compute > t_memory) & ~dom_is_comm
    dom_class = jnp.where(
        dom_is_comm, INTERCONNECT,
        jnp.where(dom_is_compute,
                  jnp.where(t["is_mm"], TENSOR, VECTORU),
                  MEMORY))
    return jnp.where(t["is_mem"], MEMORY, dom_class)


def _attribute(t: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stall attribution for `_op_terms` output: each op's time goes to its
    dominant resource.  Returns (dom_class (B, ops), stall (B, 4))."""
    dom_class = _dominant_class(t)
    t_op = t["t_op"]
    stall = jnp.stack(
        [jnp.where(dom_class == c, t_op, 0.0).sum(axis=1) for c in range(4)],
        axis=1)
    return dom_class, stall


class RooflineModel:
    """Per-workload op-term model: the traced building block that every
    :class:`~repro.perfmodel.evaluator.ModelEvaluator` composes via
    :func:`stacked_workload_batches` and the sweep engine's pair chunk step
    via :meth:`_workload_batch`.

    Evaluate through the unified Evaluator contract — a model instance on
    its own is just the op-term provider for one workload.
    """

    # Compass-tier knobs (overridden by CompassModel)
    op_overhead_s: float = 0.0        # fixed per-op launch overhead
    nonoverlap: float = 0.0           # fraction of the minor term not hidden
    mem_efficiency: float = 1.0       # achievable fraction of peak HBM bw

    def __init__(self, wl: W.Workload, space: DesignSpace = SPACE):
        self.wl = wl
        self.space = space
        a = wl.arrays()
        self._ops = {kk: jnp.asarray(vv) for kk, vv in a.items()}
        self._tp = float(wl.tp)

    # ------------------------------------------------------------------
    def _op_terms(self, hwb: Dict[str, jnp.ndarray],
                  ops: Optional[Dict[str, jnp.ndarray]] = None,
                  ) -> Dict[str, jnp.ndarray]:
        """Per-op time terms for (B, 1)-broadcast hardware dicts.

        Shared by the full eval path and the lean sweep/objectives path.
        ``ops`` overrides the model's own op table — the union pass feeds
        the deduped union of a :class:`~repro.perfmodel.workload.
        WorkloadStack` through the same traced math (``t_unit`` is the
        count-free per-op time the gather reassembly multiplies back out).
        """
        o = self._ops if ops is None else ops
        kind = o["kind"][None, :]
        flops = o["flops"][None, :]
        m, n, k = o["m"][None, :], o["n"][None, :], o["k"][None, :]
        comm = o["comm_bytes"][None, :]
        count = o["count"][None, :]
        tp = o["tp"][None, :]

        util = matmul_utilization(hwb, m, n, k)
        eff_tensor = hwb["tensor_flops"] * util
        is_mm = kind == W.MATMUL
        is_vec = kind == W.VECTOR
        is_mem = kind == W.MEMCPY
        is_ar = kind == W.ALLREDUCE
        is_p2p = kind == W.P2P

        bytes_eff = jnp.where(
            is_mm, matmul_hbm_bytes(hwb, o["bytes"][None, :], m, n, k),
            o["bytes"][None, :])

        t_compute = jnp.where(
            is_mm, flops / eff_tensor,
            jnp.where(is_vec, flops / hwb["vector_flops"], 0.0))
        t_memory = bytes_eff / (hwb["mem_bw"] * self.mem_efficiency)
        t_comm = jnp.where(
            is_ar, ring_allreduce_time(hwb, comm, tp),
            jnp.where(is_p2p, a2a_time(hwb, comm, tp), 0.0))

        major = jnp.maximum(jnp.maximum(t_compute, t_memory), t_comm)
        minor = t_compute + t_memory + t_comm - major
        t_unit = major + self.nonoverlap * minor + self.op_overhead_s
        t_op = t_unit * count
        return {
            "t_op": t_op, "t_unit": t_unit, "t_compute": t_compute,
            "t_memory": t_memory, "t_comm": t_comm, "count": count,
            "is_mm": is_mm, "is_mem": is_mem,
        }

    def _workload_batch(self, hwb: Dict[str, jnp.ndarray],
                        detail: str = "stalls") -> Dict[str, jnp.ndarray]:
        """Per-workload traced outputs for (B, 1)-broadcast hardware arrays.

        This is the unit the sweep's pair chunk step composes: the space
        decode and hardware derivation happen ONCE per chunk while each
        workload model contributes its op terms.

        detail: "objectives" -> latency only; "ppa" adds the per-op
        breakdown; "stalls" adds stall attribution on top of "ppa".
        """
        t = self._op_terms(hwb)
        latency = t["t_op"].sum(axis=1)
        if detail == "objectives":
            return {"latency": latency}
        count = t["count"]
        out = {
            "latency": latency,
            "op_time": t["t_op"],
            "t_compute": t["t_compute"] * count,
            "t_memory": t["t_memory"] * count,
            "t_comm": t["t_comm"] * count,
        }
        if detail == "stalls":
            dom_class, stall = _attribute(t)
            out["op_class"] = dom_class
            out["stall"] = stall            # (B, 4) seconds per stall class
        return out

    # The pre-PR-2 per-model shims (eval_ppa / latency / objectives) were
    # removed after their one-release deprecation window: evaluate through
    # repro.perfmodel.evaluator (ModelEvaluator fuses every workload into
    # one dispatch; evaluator_for_model wraps a single model).


# --------------------------------------------------------------------------
# stacked-workload evaluation: op terms ONCE over the deduped union
# --------------------------------------------------------------------------

def stacked_workload_batches(model: RooflineModel,
                             stack: "W.WorkloadStack",
                             hwb: Dict[str, jnp.ndarray],
                             detail: str = "stalls",
                             ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Every workload's ``_workload_batch`` outputs from ONE op-term pass.

    ``model`` supplies the op-term math (class + compass knobs — every
    workload in the stack must share them); its :meth:`RooflineModel.
    _op_terms` runs once over ``stack.unique`` (count-free ``t_unit``), and
    each workload's per-op arrays are reassembled by gathering its rows out
    of the union and multiplying its own counts back in: O(n_unique)
    instead of O(sum n_ops_w) traced op-term cost.  Every per-op value is
    elementwise in the op fields, so it equals the workload's own
    ``_workload_batch`` value; a latency sum may differ from another
    executable's in the last bit, as XLA fuses the reduce differently.

    ``detail`` is one level for all workloads.

    At the "objectives" level each workload also emits its per-op times
    under a ``"_sink"`` key.  At "ppa"/"stalls" ``t_op`` is an executable
    OUTPUT, and the latency reduce reads a materialized buffer; without
    the sink the objectives-only executable fuses gather+multiply into the
    latency reduce and drifts a ULP, so the "objectives" report would no
    longer be bit-identical to the latencies of the "ppa" and "stalls"
    reports.
    """
    ones = np.ones(stack.n_unique, dtype=np.float64)
    uops = {kk: jnp.asarray(vv) for kk, vv in stack.unique.items()}
    uops["count"] = jnp.asarray(ones)
    t = model._op_terms(hwb, ops=uops)
    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    for nm in stack.names:
        mp = jnp.asarray(stack.op_map[nm])
        cnt = jnp.asarray(stack.counts[nm])[None, :]
        t_op = t["t_unit"][:, mp] * cnt
        latency = t_op.sum(axis=1)
        if detail == "objectives":
            out[nm] = {"latency": latency, "_sink": t_op}
            continue
        ow = {
            "latency": latency,
            "op_time": t_op,
            "t_compute": t["t_compute"][:, mp] * cnt,
            "t_memory": t["t_memory"][:, mp] * cnt,
            "t_comm": t["t_comm"][:, mp] * cnt,
        }
        if detail == "stalls":
            tw = {
                "t_op": t_op,
                "t_compute": t["t_compute"][:, mp],
                "t_memory": t["t_memory"][:, mp],
                "t_comm": t["t_comm"][:, mp],
                "is_mm": t["is_mm"][:, mp],
                "is_mem": t["is_mem"][:, mp],
            }
            dom_class, stall = _attribute(tw)
            ow["op_class"] = dom_class
            ow["stall"] = stall
        out[nm] = ow
    return out
