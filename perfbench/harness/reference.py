"""Plain reference of the analytical GPU-node PPA model.

It restates, from the LUMINA paper's Table 1 design space and the model's
written semantics, everything a sweep or a campaign computes: the unranking
of a flat design id, the physical values of each parameter, the derived
hardware, the operator graphs of each workload, the per-op roofline terms
of the proxy tier and the LLMCompass-style knobs of the target tier, the
stall attribution, and the latency / area reduction.  It imports nothing of
the program under test, and takes nothing it made: the operator graphs are
built here from the widths in the configuration file.  An architecture
family this module does not restate itself (``OWN_FAMILIES``) is restated
by its own file, ``families/<family>.py``, under the same rule.

One function body serves two precisions: float64 (the reference, under
``jax.enable_x64``, on the host CPU), and bfloat16 (the control, which must
read as not correct).  Evaluation is blocked over design ids so that a full
4,741,632-point space fits in memory.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from harness import spec

# ---------------------------------------------------------------- the space
# LUMINA Table 1: (parameter, choices), last parameter fastest-varying in
# the flat id.  4 * 14 * 4 * 6 * 6 * 7 * 7 * 12 = 4,741,632 designs.
PARAMS: Tuple[Tuple[str, Tuple[int, ...]], ...] = (
    ("link_count", (6, 12, 18, 24)),
    ("core_count", (1, 2, 4, 8, 16, 32, 64, 96, 108, 128, 132, 136, 140,
                    256)),
    ("sublane_count", (1, 2, 4, 8)),
    ("sa_dim", (4, 8, 16, 32, 64, 128)),
    ("vector_width", (4, 8, 16, 32, 64, 128)),
    ("sram_kb", (32, 64, 128, 192, 256, 512, 1024)),
    ("gbuf_mb", (32, 64, 128, 256, 320, 512, 1024)),
    ("mem_channels", tuple(range(1, 13))),
)
NAMES = tuple(p for p, _ in PARAMS)
CARDS = tuple(len(c) for _, c in PARAMS)
SIZE = math.prod(CARDS)

# The A100 of the paper's Table 4; its 40 MB global buffer is outside the
# space and snaps to the nearest choice (32 MB) as the reference design.
A100 = {"link_count": 12, "core_count": 108, "sublane_count": 4,
        "sa_dim": 16, "vector_width": 32, "sram_kb": 128, "gbuf_mb": 40,
        "mem_channels": 5}

# ------------------------------------------------------- hardware constants
CLOCK_HZ = 1.41e9
BW_PER_CHANNEL = 311.0e9
BW_PER_LINK = 25.0e9
LINK_LATENCY_S = 1.0e-6
AREA = dict(base=140.0, per_mac=1.826e-4, per_vlane=0.008,
            per_sram_kb=0.0081, core_base=2.924, per_gbuf_mb=0.72,
            per_channel=15.0, per_link=1.8)
BYTES = 2.0                              # fp16 operands everywhere
SRAM_FEED_WORDS_PER_KB = 0.625

# the tiers: proxy = bare roofline, target = LLMCompass-calibrated knobs
TIER_KNOBS = {
    "proxy": dict(op_overhead_s=0.0, nonoverlap=0.0, mem_efficiency=1.0),
    "target": dict(op_overhead_s=2.0e-5, nonoverlap=0.5,
                   mem_efficiency=0.85),
}

MATMUL, VECTOR, MEMCPY, ALLREDUCE, P2P = 0, 1, 2, 3, 4
TENSOR, VECTORU, MEMORY, INTERCONNECT = 0, 1, 2, 3
N_STALL = 4


def nearest_idx(values: Dict[str, float]) -> np.ndarray:
    """Index vector of the design nearest ``values`` on every parameter."""
    return np.array([int(np.abs(np.asarray(ch, float) - values[nm]).argmin())
                     for nm, ch in PARAMS], dtype=np.int64)


def idx_to_flat(idx: np.ndarray) -> np.ndarray:
    idx = np.asarray(idx, dtype=np.int64)
    flat = np.zeros(idx.shape[:-1], dtype=np.int64)
    for i, c in enumerate(CARDS):
        flat = flat * c + idx[..., i]
    return flat


def flat_to_idx(flat: np.ndarray) -> np.ndarray:
    rem = np.asarray(flat, dtype=np.int64).copy()
    out = np.zeros(rem.shape + (len(CARDS),), dtype=np.int64)
    for i in range(len(CARDS) - 1, -1, -1):
        out[..., i] = rem % CARDS[i]
        rem //= CARDS[i]
    return out


# ------------------------------------------------------------ operator graph
class Graph:
    """One workload's operators as columns (kind, flops, bytes, m, n, k,
    collective bytes, count, tp)."""

    FIELDS = ("kind", "flops", "bytes", "m", "n", "k", "comm", "count", "tp")

    def __init__(self, tp: float):
        self.tp = float(tp)
        self.rows: List[tuple] = []

    def op(self, kind, flops=0.0, nbytes=0.0, m=1.0, n=1.0, k=1.0,
           comm=0.0, count=1.0):
        self.rows.append((kind, float(flops), float(nbytes), float(m),
                          float(n), float(k), float(comm), float(count),
                          self.tp))

    def matmul(self, m, k, n, count=1.0):
        self.op(MATMUL, 2.0 * m * k * n, (m * k + k * n + m * n) * BYTES,
                m, n, k, count=count)

    def vector(self, elems, flops_per_elem=5.0, passes=2.0, count=1.0):
        self.op(VECTOR, flops_per_elem * elems, passes * elems * BYTES,
                count=count)

    def memcpy(self, nbytes, count=1.0):
        self.op(MEMCPY, nbytes=nbytes, count=count)

    def allreduce(self, elems, count=1.0):
        self.op(ALLREDUCE, comm=elems * BYTES, count=count)

    def table(self) -> Dict[str, np.ndarray]:
        cols = np.array(self.rows, dtype=np.float64).T
        return dict(zip(self.FIELDS, cols))


def gpt3_prefill(w: Dict, batch: int, seq: int, tp: int) -> Graph:
    """One GPT-3 layer's prefill at tensor parallelism ``tp``."""
    d, H, hd, ff = w["d_model"], w["n_heads"], w["head_dim"], w["d_ff"]
    hl = H // tp
    M = batch * seq
    g = Graph(tp)
    g.vector(M * d, 8.0)
    g.matmul(M, d, 3 * d // tp)
    g.matmul(seq, hd, seq, count=batch * hl)
    g.vector(seq * seq * batch * hl, 6.0)
    g.matmul(seq, seq, hd, count=batch * hl)
    g.matmul(M, d // tp, d)
    g.allreduce(M * d)
    g.vector(M * d, 8.0)
    g.matmul(M, d, ff // tp)
    g.vector(M * ff // tp, 8.0)
    g.matmul(M, ff // tp, d)
    g.allreduce(M * d)
    g.memcpy(batch * seq * 2 * hl * hd * BYTES)
    return g


def gpt3_decode(w: Dict, batch: int, seq: int, out_pos: int,
                tp: int) -> Graph:
    """One GPT-3 layer's decode step at KV length ``seq + out_pos``."""
    d, H, hd, ff = w["d_model"], w["n_heads"], w["head_dim"], w["d_ff"]
    hl = H // tp
    kv = seq + out_pos
    M = batch
    g = Graph(tp)
    g.vector(M * d, 8.0)
    g.matmul(M, d, 3 * d // tp)
    g.memcpy(batch * kv * 2 * hl * hd * BYTES)
    g.op(MATMUL, 2.0 * batch * hl * kv * hd * 2,
         batch * hl * (kv * hd * 2 + kv + hd) * BYTES, batch, kv, hd)
    g.vector(batch * hl * kv, 6.0)
    g.matmul(M, d // tp, d)
    g.allreduce(M * d)
    g.vector(M * d, 8.0)
    g.matmul(M, d, ff // tp)
    g.vector(M * ff // tp, 8.0)
    g.matmul(M, ff // tp, d)
    g.allreduce(M * d)
    g.memcpy(batch * 2 * hl * hd * BYTES)
    return g


def attention(g: Graph, batch, q_len, kv_len, d, n_heads, n_kv, head_dim,
              tp, count, decode):
    hl = max(1, n_heads // tp)
    kvl = max(1, n_kv // tp)
    M = batch * q_len
    g.matmul(M, d, n_heads * head_dim // tp + 2 * n_kv * head_dim // tp,
             count=count)
    if decode:
        g.memcpy(batch * kv_len * 2 * kvl * head_dim * BYTES, count=count)
        g.op(MATMUL, 2.0 * batch * hl * kv_len * head_dim * 2,
             batch * hl * (kv_len + head_dim) * BYTES, batch, kv_len,
             head_dim, count=count)
        g.vector(batch * hl * kv_len, 6.0, count=count)
        g.memcpy(batch * 2 * kvl * head_dim * BYTES, count=count)
    else:
        g.matmul(q_len, head_dim, kv_len, count=count * batch * hl)
        g.vector(batch * hl * q_len * kv_len, 6.0, count=count)
        g.matmul(q_len, kv_len, head_dim, count=count * batch * hl)
        g.memcpy(batch * q_len * 2 * kvl * head_dim * BYTES, count=count)
    g.matmul(M, n_heads * head_dim // tp, d, count=count)
    g.allreduce(M * d, count=count)


def ffn(g: Graph, M, d, d_ff, tp, gated, count):
    g.matmul(M, d, (2 if gated else 1) * d_ff // tp, count=count)
    g.vector(M * d_ff // tp, 8.0, count=count)
    g.matmul(M, d_ff // tp, d, count=count)
    g.allreduce(M * d, count=count)


def moe(g: Graph, M, d, expert_ff, n_experts, top_k, n_shared, tp, count):
    """Router, top-k expert FFNs over an expert-parallel group of ``tp``,
    all-to-all dispatch and combine, then shared experts."""
    g.matmul(M, d, n_experts, count=count)
    g.vector(M * n_experts, 4.0, count=count)
    payload = M * top_k * d * BYTES
    g.op(P2P, comm=payload, count=count)
    m_eff = M * top_k / tp
    g.matmul(m_eff, d, 2 * expert_ff, count=count)
    g.vector(m_eff * expert_ff, 8.0, count=count)
    g.matmul(m_eff, expert_ff, d, count=count)
    g.op(P2P, comm=payload, count=count)
    if n_shared:
        ffn(g, M, d, expert_ff * n_shared, tp, True, count)


def _mamba(g: Graph, batch, q_len, d, d_state, tp, count, decode):
    d_in = 2 * d
    M = batch * q_len
    g.matmul(M, d, 2 * d_in // tp, count=count)
    g.vector(M * d_in // tp, 8.0, count=count)
    scan = M * (d_in // tp) * d_state
    g.op(VECTOR, 6.0 * scan,
         (2.0 if decode else 3.0) * M * (d_in // tp) * BYTES
         + 2 * batch * (d_in // tp) * d_state * BYTES, count=count)
    g.matmul(M, d_in // tp, d, count=count)
    g.allreduce(M * d, count=count)


def _rwkv(g: Graph, batch, q_len, d, d_ff, tp, count):
    M = batch * q_len
    head = 64
    n_heads = d // head
    g.matmul(M, d, 5 * d // tp, count=count)
    g.op(VECTOR, 4.0 * M * (d // tp) * head,
         (2 * batch * (n_heads // max(1, tp)) * head * head
          + 4 * M * d // tp) * BYTES, count=count)
    g.matmul(M, d // tp, d, count=count)
    g.allreduce(M * d, count=count)
    g.matmul(M, d, d_ff // tp, count=count)
    g.vector(M * d_ff // tp, 8.0, count=count)
    g.matmul(M, d_ff // tp, d, count=count)
    g.allreduce(M * d, count=count)


#: the families restated here: the transformer ones (``dense``, ``vlm``,
#: ``audio`` with its encoder where ``enc_layers`` is set, ``moe``), the
#: hybrid attention-Mamba-MoE stack, and the RWKV ``ssm``
OWN_FAMILIES = frozenset({"dense", "vlm", "audio", "moe", "hybrid", "ssm"})


def arch_graph(a: Dict, batch: int, seq: int, tp: int, decode: bool,
               kv_len: int) -> Graph:
    """Operator graph of a whole model (every layer, by multiplicity) for
    the prefill of ``seq`` tokens or one decode step at ``kv_len``.

    Every family shares the embedding copy and the logits matmul; the
    layers between them are restated here for ``OWN_FAMILIES``, and by
    ``layers(g, a, batch, q_len, kv_len, tp, decode)`` of the family's own
    file (``spec.family``) for any other."""
    q_len = 1 if decode else seq
    d = a["d_model"]
    M = batch * q_len
    g = Graph(tp)
    g.memcpy(M * d * BYTES)                                     # embedding
    if a["family"] in OWN_FAMILIES:
        _own_layers(g, a, batch, q_len, kv_len, tp, decode)
    else:
        spec.family(a["family"]).layers(g, a, batch, q_len, kv_len, tp,
                                        decode)
    g.matmul(M, d, a["vocab"] // tp)                            # logits
    return g


def _own_layers(g: Graph, a: Dict, batch: int, q_len: int, kv_len: int,
                tp: int, decode: bool) -> None:
    d = a["d_model"]
    M = batch * q_len
    L = a["n_layers"]
    fam = a["family"]
    attn = (a["n_heads"], a["n_kv_heads"], a["head_dim"])
    if fam == "ssm":
        g.vector(2 * M * d * L / L, 8.0, count=L)
        _rwkv(g, batch, q_len, d, a["d_ff"], tp, L)
    elif fam == "hybrid":
        n_attn = L // a["attn_every"]
        n_moe = L // 2
        g.vector(2 * M * d, 8.0, count=L)
        attention(g, batch, q_len, kv_len, d, *attn, tp, n_attn, decode)
        _mamba(g, batch, q_len, d, a["d_state"], tp, L - n_attn, decode)
        moe(g, M, d, a["expert_ff"], a["n_experts"], a["top_k"],
            a.get("n_shared_experts", 0), tp, n_moe)
        ffn(g, M, d, a["d_ff"], tp, True, L - n_moe)
    else:
        enc = a.get("enc_layers", 0)
        if enc and not decode:
            ctx = a["enc_ctx"]
            attention(g, batch, ctx, ctx, d, *attn, tp, enc, False)
            ffn(g, batch * ctx, d, a["d_ff"], tp, False, enc)
        g.vector(2 * M * d, 8.0, count=L)
        attention(g, batch, q_len, kv_len, d, *attn, tp, L, decode)
        if enc:
            attention(g, batch, q_len, a["enc_ctx"], d, *attn, tp, L,
                      decode)
        if fam == "moe":
            moe(g, M, d, a["expert_ff"], a["n_experts"], a["top_k"],
                a.get("n_shared_experts", 0), tp, L)
            if a.get("dense_residual", False):
                ffn(g, M, d, a["d_ff"], tp, True, L)
        else:
            ffn(g, M, d, a["d_ff"], tp, a.get("gated_mlp", True), L)


def scenarios(cfg: Dict) -> List[Tuple[str, Graph, Graph]]:
    """(name, prefill graph, decode graph) per scenario of a configuration
    file's ``suite``."""
    s = cfg["suite"]
    b, seq, tp, out = s["batch"], s["seq"], s["tp"], s["out_pos"]
    if s["kind"] == "paper":
        w = cfg["widths"]
        return [("gpt3", gpt3_prefill(w, b, seq, tp),
                 gpt3_decode(w, b, seq, out, tp))]
    out_l = []
    for name in sorted(cfg["archs"]):
        a = cfg["archs"][name]
        out_l.append((name,
                      arch_graph(a, b, seq, tp, False, seq),
                      arch_graph(a, b, seq, tp, True, seq + out)))
    return out_l


# -------------------------------------------------------------- evaluation
def _values(ids, dt) -> Dict[str, jnp.ndarray]:
    """Physical parameter values of flat design ids, shape (B, 1)."""
    out = {}
    rem = ids
    for (name, ch), c in zip(reversed(PARAMS), reversed(CARDS)):
        out[name] = jnp.asarray(np.asarray(ch, np.float64), dt)[rem % c]
        rem = rem // c
    return {k: v[:, None] for k, v in out.items()}


def _hardware(v, dt):
    c = lambda x: jnp.asarray(x, dt)           # noqa: E731 (constants in dt)
    cores, sub, sa, vw = (v["core_count"], v["sublane_count"], v["sa_dim"],
                          v["vector_width"])
    core_area = (c(AREA["core_base"]) + c(AREA["per_mac"]) * (sub * sa * sa)
                 + c(AREA["per_vlane"]) * (sub * vw)
                 + c(AREA["per_sram_kb"]) * v["sram_kb"])
    area = (c(AREA["base"]) + cores * core_area
            + c(AREA["per_gbuf_mb"]) * v["gbuf_mb"]
            + c(AREA["per_channel"]) * v["mem_channels"]
            + c(AREA["per_link"]) * v["link_count"])
    return dict(
        tensor=cores * sub * sa * sa * c(2.0) * c(CLOCK_HZ),
        vector=cores * sub * vw * c(2.0) * c(CLOCK_HZ),
        mem_bw=v["mem_channels"] * c(BW_PER_CHANNEL),
        ici_bw=v["link_count"] * c(BW_PER_LINK),
        gbuf_bytes=v["gbuf_mb"] * c(2.0 ** 20),
        sram_kb=v["sram_kb"], sa=sa, sub=sub, cores=cores, area=area[:, 0])


def _terms(hw, ops, knobs, dt):
    """Count-free per-op time and its compute / memory / comm parts, each
    (B, n_ops), plus each op's dominant stall class."""
    c = lambda x: jnp.asarray(x, dt)           # noqa: E731
    o = {k: jnp.asarray(v, dt)[None, :] for k, v in ops.items()}
    kind = jnp.asarray(ops["kind"])[None, :]
    m, n, k = o["m"], o["n"], o["k"]
    sa = hw["sa"]
    # systolic utilization: padding of K and N to the array, pipeline fill,
    # tile parallelism, SRAM double-buffer capacity and operand feed
    util = (k / (jnp.ceil(k / sa) * sa) * (n / (jnp.ceil(n / sa) * sa))
            * (m / (m + sa))
            * jnp.minimum(c(1.0), jnp.ceil(m / sa) * jnp.ceil(n / sa)
                          / (hw["cores"] * hw["sub"]))
            * jnp.minimum(c(1.0), hw["sram_kb"]
                          / (c(3.0 * 2.0 * BYTES / 1024.0) * sa * sa))
            * jnp.minimum(c(1.0), c(SRAM_FEED_WORDS_PER_KB) * hw["sram_kb"]
                          / (sa * hw["sub"])))
    is_mm = kind == MATMUL
    # blocked matmul HBM traffic: at least the I/O bound of the buffer
    elems = jnp.maximum(hw["gbuf_bytes"] / c(BYTES), c(1.0))
    mm_bytes = jnp.maximum(o["bytes"], c(2.0) * m * n * k / jnp.sqrt(elems)
                           * c(BYTES))
    nbytes = jnp.where(is_mm, mm_bytes, o["bytes"])
    t_compute = jnp.where(is_mm, o["flops"] / (hw["tensor"] * util),
                          jnp.where(kind == VECTOR, o["flops"] / hw["vector"],
                                    c(0.0)))
    t_memory = nbytes / (hw["mem_bw"] * c(knobs["mem_efficiency"]))
    tp = o["tp"]
    steps = c(2.0) * (tp - c(1.0))
    ring = (steps / tp * o["comm"] / hw["ici_bw"]
            + steps * c(LINK_LATENCY_S))
    a2a = ((tp - c(1.0)) / tp * o["comm"] / hw["ici_bw"]
           + (tp - c(1.0)) * c(LINK_LATENCY_S))
    t_comm = jnp.where(kind == ALLREDUCE, ring,
                       jnp.where(kind == P2P, a2a, c(0.0)))
    major = jnp.maximum(jnp.maximum(t_compute, t_memory), t_comm)
    minor = t_compute + t_memory + t_comm - major
    t_unit = (major + c(knobs["nonoverlap"]) * minor
              + c(knobs["op_overhead_s"]))
    # dominant resource: comm wins ties, compute needs a strict lead over
    # memory, pure memory copies always go to memory
    comm_dom = (t_comm >= t_compute) & (t_comm >= t_memory)
    comp_dom = (t_compute > t_memory) & ~comm_dom
    cls = jnp.where(comm_dom, INTERCONNECT,
                    jnp.where(comp_dom, jnp.where(is_mm, TENSOR, VECTORU),
                              MEMORY))
    cls = jnp.where(kind == MEMCPY, MEMORY, cls)
    return dict(t_unit=t_unit, t_compute=t_compute, t_memory=t_memory,
                t_comm=t_comm, cls=cls)


def workload_outputs(hw, graph_table, knobs, dt, detail=True):
    """Latency (B,) and, with ``detail``, per-op times (B, n_ops), per-op
    classes and per-class stall sums (B, 4) of one workload."""
    t = _terms(hw, graph_table, knobs, dt)
    cnt = jnp.asarray(graph_table["count"], dt)[None, :]
    t_op = t["t_unit"] * cnt
    lat = t_op.sum(axis=1)
    if not detail:
        return {"latency": lat}
    stall = jnp.stack([jnp.where(t["cls"] == s, t_op, 0.0).sum(axis=1)
                       for s in range(N_STALL)], axis=1)
    return {"latency": lat, "op_time": t_op, "op_class": t["cls"],
            "stall": stall, "t_compute": t["t_compute"],
            "t_memory": t["t_memory"], "t_comm": t["t_comm"]}


class Model:
    """A configuration's scenarios at one tier and one precision.

    ``dtype`` is ``"float64"`` for the reference (run it under
    ``jax.enable_x64(True)``) or ``"bfloat16"`` for the control."""

    def __init__(self, cfg: Dict, tier: str, dtype: str = "float64"):
        self.cfg = cfg
        self.tier = tier
        self.knobs = TIER_KNOBS[tier]
        self.dt = jnp.dtype(dtype)
        self.scen = [(nm, p.table(), d.table())
                     for nm, p, d in scenarios(cfg)]
        self._sweep_fn = jax.jit(self._sweep_block)
        self._report_fns = {}

    # ---- one block of a full-space sweep: per-scenario objectives and
    # ---- the prefill workload's dominant stall class
    def _sweep_block(self, ids):
        v = _values(ids, self.dt)
        hw = _hardware(v, self.dt)
        ys, dom = [], []
        for _, pre, dec in self.scen:
            op = workload_outputs(hw, pre, self.knobs, self.dt)
            od = workload_outputs(hw, dec, self.knobs, self.dt,
                                  detail=False)
            ys.append(jnp.stack([op["latency"], od["latency"], hw["area"]],
                                axis=1))
            dom.append(jnp.argmax(op["stall"], axis=1))
        return jnp.stack(ys, axis=1), jnp.stack(dom, axis=1)

    def sweep(self, ids: np.ndarray, block: int = 1 << 18,
              device=None) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Objectives (N, S, 3) as float64, each scenario's dominant
        prefill stall class (N, S), and the ids, for flat design ids."""
        ids = np.asarray(ids, dtype=np.int64)
        ys = np.empty((len(ids), len(self.scen), 3), np.float64)
        dom = np.empty((len(ids), len(self.scen)), np.int8)
        for s in range(0, len(ids), block):
            chunk = ids[s:s + block]
            pad = block - len(chunk)
            full = np.concatenate([chunk, np.repeat(chunk[-1:], pad)])
            x = (jax.device_put(full, device) if device is not None
                 else jnp.asarray(full))
            y, d = self._sweep_fn(x)
            ys[s:s + len(chunk)] = np.asarray(y, np.float64)[:len(chunk)]
            dom[s:s + len(chunk)] = np.asarray(d)[:len(chunk)]
        return ys, dom, ids

    # ---- full reports of named workloads, as the evaluator returns them
    def workload_tables(self) -> Dict[str, Dict[str, np.ndarray]]:
        """Workload name -> operator table, under the names the program's
        evaluator uses for this suite."""
        if self.cfg["suite"]["kind"] == "paper":
            _, pre, dec = self.scen[0]
            return {"ttft": pre, "tpot": dec}
        out = {}
        for nm, pre, dec in self.scen:
            out[f"{nm}:prefill"] = pre
            out[f"{nm}:decode"] = dec
        return out

    def reports(self, idx: np.ndarray, names: Sequence[str]) -> Dict:
        """Area and every named workload's full outputs for index vectors
        ``idx`` (n, 8), as float64 numpy (int for classes)."""
        names = tuple(names)
        fn = self._report_fns.get(names)
        tables = self.workload_tables()
        if fn is None:
            def f(ids):
                hw = _hardware(_values(ids, self.dt), self.dt)
                return hw["area"], {nm: workload_outputs(
                    hw, tables[nm], self.knobs, self.dt) for nm in names}
            fn = self._report_fns[names] = jax.jit(f)
        flat = idx_to_flat(np.atleast_2d(idx))
        area, per = fn(jnp.asarray(flat))
        cast = lambda a: (np.asarray(a) if a.dtype.kind in "iub"   # noqa
                          else np.asarray(a, np.float64))
        return {"area": cast(area),
                "per": {nm: {k: cast(v) for k, v in o.items()}
                        for nm, o in per.items()}}
