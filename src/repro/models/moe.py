"""Mixture-of-Experts layer: top-k routing with capacity-bounded dispatch.

TPU-native formulation (MaxText-style "dropping" dispatch): tokens are
sorted by assigned expert and scattered into a dense (E, C, d) buffer, so
the expert computation is ONE batched einsum with FLOPs proportional to
*active* tokens (times the capacity factor) — not n_experts.  The expert
dimension shards over the `model` mesh axis (expert parallelism); GSPMD
inserts the dispatch/combine all-to-alls.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import init_mlp, mlp


def init_moe(key, d_model: int, expert_ff: int, n_experts: int,
             n_shared: int, shared_ff: int, dtype=jnp.bfloat16,
             expert_pad: int = 0, router_bias: bool = False) -> Dict:
    """expert_pad adds zero-traffic experts so the expert-stack dim divides
    the TP axis (EP layout); the router only ever emits n_experts logits.
    ``router_bias`` adds the selection-only score bias of group-limited
    routing (DeepSeek-V3's ``e_score_correction_bias``), zero at init."""
    kr, ke1, ke2, ke3, ks = jax.random.split(key, 5)
    s_in = float(1.0 / np.sqrt(d_model))
    s_ff = float(1.0 / np.sqrt(expert_ff))
    e_tot = n_experts + expert_pad
    p = {
        "router": jax.random.normal(kr, (d_model, n_experts), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ke1, (e_tot, d_model, expert_ff), dtype) * s_in,
        "w_up": jax.random.normal(ke2, (e_tot, d_model, expert_ff), dtype) * s_in,
        "w_down": jax.random.normal(ke3, (e_tot, expert_ff, d_model), dtype) * s_ff,
    }
    if n_shared:
        p["shared"] = init_mlp(ks, d_model, shared_ff, gated=True, dtype=dtype)
    if router_bias:
        p["router_bias"] = jnp.zeros((n_experts,), jnp.float32)
    return p


def moe_block(params: Dict, x: jnp.ndarray, *, n_experts: int, top_k: int,
              capacity_factor: float = 1.25, n_groups: int = 1,
              buf_pspec=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out, aux_loss).

    Grouped capacity-bounded dispatch: tokens are split into `n_groups`
    groups (aligned with the data-parallel axis by the launcher), routing
    positions are computed WITHIN each group (parallel cumsum, local
    scatter), and the dispatch buffer is (G, E, C, d) with G sharded over
    the data axes and E over the model axis — so dispatch/combine lower to
    local scatters plus one all-to-all instead of global gathers (perf
    iteration, EXPERIMENTS.md §Perf qwen2-moe).  Per-group capacity
    C = ceil(Tg * top_k / E * capacity_factor); overflow tokens drop (their
    contribution is the shared-expert/residual path only).
    """
    import math
    b, s, d = x.shape
    t = b * s
    e_tot = params["w_up"].shape[0]       # includes zero-traffic pad experts
    g_n = max(1, math.gcd(n_groups, t))
    tg = t // g_n
    xg = x.reshape(g_n, tg, d)
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        params["router"])                         # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)             # (G, Tg, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    out, flat_expert = _dispatch_combine(params, xg, gate_idx, gate_vals,
                                         n_experts, capacity_factor,
                                         buf_pspec)
    out = out.reshape(b, s, d)

    # load-balancing auxiliary loss (Switch-style)
    me = probs.reshape(t, -1).mean(axis=0)[:n_experts]
    ce = jnp.zeros(e_tot).at[flat_expert.reshape(-1)].add(1.0)[:n_experts] \
        / (t * top_k)
    aux = n_experts * jnp.sum(me * ce)

    if "shared" in params:
        out = out + mlp(params["shared"], x, gated=True)
    return out, aux


def _dispatch_combine(params: Dict, xg: jnp.ndarray, gate_idx: jnp.ndarray,
                      gate_vals: jnp.ndarray, n_experts: int,
                      capacity_factor: float, buf_pspec=None):
    """Capacity-bounded dispatch of routed tokens ``xg`` (G, Tg, d) to
    their experts ``gate_idx`` (G, Tg, k), the expert FFNs as one batched
    einsum, and the gate-weighted combine.  Returns ((G, Tg, d), the flat
    (G, Tg*k) expert ids)."""
    g_n, tg, d = xg.shape
    top_k = gate_idx.shape[-1]
    e_tot = params["w_up"].shape[0]       # includes zero-traffic pad experts
    cap = int(np.ceil(tg * top_k / n_experts * capacity_factor))
    cap = max(cap, 1)

    # position of each (token, k) assignment within its (group, expert) slot
    flat_expert = gate_idx.reshape(g_n, tg * top_k)               # (G, Tg*k)
    onehot = jax.nn.one_hot(flat_expert, e_tot, dtype=jnp.int32)  # (G, ., E)
    pos_in_expert = jnp.cumsum(onehot, axis=1) - 1
    pos = jnp.take_along_axis(pos_in_expert, flat_expert[..., None],
                              axis=2)[..., 0]
    keep = pos < cap

    # scatter tokens into the (G, E, C, d) dispatch buffer (group-local)
    buf = jnp.zeros((g_n, e_tot, cap, d), xg.dtype)
    src = jnp.repeat(xg, top_k, axis=1)                           # (G, Tg*k, d)
    e_idx = jnp.where(keep, flat_expert, 0)
    c_idx = jnp.where(keep, pos, 0)
    src = jnp.where(keep[..., None], src, 0)
    g_idx = jnp.arange(g_n)[:, None] * jnp.ones_like(e_idx)
    buf = buf.at[g_idx, e_idx, c_idx].add(src)
    if buf_pspec is not None:
        buf = jax.lax.with_sharding_constraint(buf, buf_pspec)

    # expert FFN: one batched einsum over the (group, expert) dims
    gme = jnp.einsum("gecd,edf->gecf", buf, params["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", buf, params["w_up"])
    h = jax.nn.silu(gme.astype(jnp.float32)).astype(xg.dtype) * u
    y = jnp.einsum("gecf,efd->gecd", h, params["w_down"])         # (G, E, C, d)

    # combine: gather each assignment's expert output, weight by the gate
    out_flat = y[g_idx, e_idx, c_idx]                             # (G, Tg*k, d)
    w = (gate_vals.reshape(g_n, tg * top_k) * keep).astype(xg.dtype)
    out = (out_flat * w[..., None]).reshape(g_n, tg, top_k, d).sum(axis=2)
    return out, flat_expert


def group_limited_route(scores: jnp.ndarray, bias: jnp.ndarray, *,
                        n_group: int, topk_group: int,
                        top_k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """DeepSeek-V3 routing over sigmoid ``scores`` (..., E): rank the
    ``n_group`` groups of E/n_group experts by the sum of each group's top-2
    of ``scores + bias``, keep the top ``topk_group`` groups, and pick the
    ``top_k`` experts of highest ``scores + bias`` among them.  Returns
    (expert ids (..., k), their unbiased scores (..., k)); the bias steers
    selection only."""
    e = scores.shape[-1]
    sel = scores + bias
    grouped = sel.reshape(sel.shape[:-1] + (n_group, e // n_group))
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
    _, top_groups = jax.lax.top_k(group_score, topk_group)
    keep = jax.nn.one_hot(top_groups, n_group, dtype=jnp.bool_).any(-2)
    keep = jnp.repeat(keep, e // n_group, axis=-1)                # (..., E)
    _, idx = jax.lax.top_k(jnp.where(keep, sel, -jnp.inf), top_k)
    return idx, jnp.take_along_axis(scores, idx, axis=-1)


def deepseek_moe_block(params: Dict, x: jnp.ndarray, *, n_experts: int,
                       top_k: int, n_group: int, topk_group: int,
                       routed_scaling: float, capacity_factor: float = 1.25,
                       n_groups: int = 1,
                       buf_pspec=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out, aux): DeepSeek-V3's expert layer.

    Sigmoid router scores, group-limited top-k (``group_limited_route``),
    the chosen scores normalized to sum 1 and scaled by
    ``routed_scaling``, the same capacity-bounded dispatch as
    ``moe_block``, plus the shared experts.  The model balances load
    through the selection bias, not a loss, so aux is 0."""
    import math
    b, s, d = x.shape
    t = b * s
    g_n = max(1, math.gcd(n_groups, t))
    xg = x.reshape(g_n, t // g_n, d)
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        params["router"])
    scores = jax.nn.sigmoid(logits)
    gate_idx, gate_vals = group_limited_route(
        scores, params["router_bias"], n_group=n_group,
        topk_group=topk_group, top_k=top_k)
    gate_vals = (gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                         1e-20) * routed_scaling)
    out, _ = _dispatch_combine(params, xg, gate_idx, gate_vals, n_experts,
                               capacity_factor, buf_pspec)
    out = out.reshape(b, s, d)
    if "shared" in params:
        out = out + mlp(params["shared"], x, gated=True)
    return out, jnp.zeros((), jnp.float32)
