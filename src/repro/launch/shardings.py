"""Partition specs for parameters, optimizer state, batches and caches.

Strategy (see DESIGN.md §5):
  * DP over ('pod','data') on batch dims;
  * Megatron TP over 'model' on attention heads / d_ff / vocab / RWKV and
    Mamba channel dims;
  * EP over 'model' for MoE expert stacks (falling back to TP on the expert
    FF dim when n_experts doesn't divide the axis, e.g. qwen2-moe's 60);
  * SP (sequence sharding) for long_500k KV caches, for GQA caches whose
    kv-head count doesn't divide the model axis (flash-decode layout), and,
    via activation constraints, for residual streams (Megatron sequence
    parallelism);
  * latent attention (MLA): replicated latent down-projections and latent
    cache, head-split up-projections.

jax requires argument shardings to divide dims exactly, so every rule is
divisibility-checked against the actual leaf shape and falls back to the
next-best layout (documented inline) instead of relying on GSPMD padding.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig


def _div(n: int, size: int) -> bool:
    return size > 0 and n % size == 0


# ----------------------------------------------------------------- params
def _base_spec(keys, shape: Tuple[int, ...], model_size: int) -> Tuple:
    """Spec for a param leaf, by path rules + divisibility checks.

    `shape` is the full (possibly layer-stacked) leaf shape; rules address
    trailing dims and the result is left-padded with None by the caller.
    """
    last = keys[-1]

    def has(*names):
        return any(n in keys for n in names)

    def m(dim_from_end: int):
        """'model' if that trailing dim divides the axis, else None."""
        d = shape[len(shape) - dim_from_end]
        return "model" if _div(d, model_size) else None

    # shared-expert MLP inside MoE blocks: ordinary TP rules (check first —
    # its leaves are also named w_gate/w_up/w_down)
    if has("shared"):
        if last in ("w_gate", "w_up"):
            return (None, m(1))
        if last == "w_down":
            return (m(2), None)
        return (None,) * min(len(shape), 1)

    # MoE expert stacks: (E, d, f) / (E, f, d) -> EP on E when divisible,
    # else TP on the expert FF dim
    if has("moe") and last in ("w_gate", "w_up", "w_down"):
        e_dim = shape[-3]
        if _div(e_dim, model_size):
            return ("model", None, None)
        if last == "w_down":
            return (None, m(2), None)
        return (None, None, m(1))
    if last == "router":
        return (None, None)

    # latent attention: the latent down-projections are replicated (every
    # rank needs the whole latent); the up-projections split the heads
    if has("q_a", "kv_a") and last == "w":
        return (None, None)
    if has("q_b", "kv_b") and last == "w":
        return (None, m(1))

    # attention / rwkv / mamba linears
    if has("q", "k", "v", "g", "r", "w_proj", "cm_k", "in_proj") and last == "w":
        return (None, m(1))
    if has("q", "k", "v", "g", "r", "w_proj", "cm_k", "in_proj") and last == "b":
        return (m(1),)
    if has("o", "out", "cm_v", "out_proj", "x_proj") and last == "w":
        return (m(2), None)
    if has("o", "out", "cm_v", "out_proj", "x_proj") and last == "b":
        return (None,)
    if last == "conv_w":
        return (None, m(1))
    if last in ("conv_b", "dt_bias", "D"):
        return (m(1),)
    if last == "A_log":
        return (m(2), None)
    if last == "u":                       # rwkv bonus (H, hd)
        return (m(2), None)

    # MLP
    if last in ("w_gate", "w_up"):
        return (None, m(1))
    if last == "b_up":
        return (m(1),)
    if last == "w_down":
        return (m(2), None)
    if last == "b_down":
        return (None,)

    # embeddings / head: vocab-sharded when divisible, else d_model-sharded
    if last == "embed":
        v, d = shape[-2], shape[-1]
        if _div(v, model_size):
            return ("model", None)
        return (None, m(1))
    if has("lm_head") and last == "w":
        d, v = shape[-2], shape[-1]
        if _div(v, model_size):
            return (None, "model")
        return (m(2), None)
    if has("lm_head") and last == "b":
        return (m(1),)

    # norms, mixes, scalars
    return tuple([None] * len(shape))


def param_spec(path, leaf, model_size: int = 16) -> P:
    keys = [str(getattr(k, "key", k)) for k in path]
    shape = tuple(getattr(leaf, "shape", ()))
    ndim = len(shape)
    tail = _base_spec(keys, shape, model_size)
    tail = tuple(tail[-ndim:]) if len(tail) > ndim else tail
    pad = ndim - len(tail)
    return P(*([None] * pad + list(tail)))


def param_specs(params: Any, model_size: int = 16):
    """Pytree of PartitionSpec matching `params` (works on abstract trees)."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: param_spec(p, l, model_size), params)


# ----------------------------------------------------------------- batch
def batch_spec(cfg: ArchConfig, shape: ShapeConfig, dp, dp_size: int) -> Any:
    """Input-batch PartitionSpecs.  dp = data axes, dp_size = their product."""
    dp = tuple(dp)
    bdim = dp if _div(shape.global_batch, dp_size) and shape.global_batch > 1 \
        else None
    if shape.mode == "decode":
        tok = P(bdim)                     # (B,) one token per sequence
    else:
        tok = P(bdim, None)               # (B, S)
    out = {"tokens": tok, "labels": P(bdim, None)}
    if cfg.family == "vlm":
        out["embeds"] = P(bdim, None, "model")
    if cfg.family == "audio":
        out["frames"] = P(bdim, None, "model")
    return out


# ----------------------------------------------------------------- cache
def cache_spec(cfg: ArchConfig, shape: ShapeConfig, dp, dp_size: int,
               model_size: int) -> Any:
    """Decode-cache PartitionSpecs.

    KV layout decision tree:
      * kv-heads divide the model axis -> shard heads (classic TP serving);
      * else -> shard the KV sequence over 'model' (flash-decode layout);
      * batch==1 (long_500k) -> the data axes also land on the sequence dim;
      * latent attention caches one latent for all heads -> no 'model' dim.
    """
    dp = tuple(dp)
    seq_sharded = shape.global_batch == 1
    b_ax = None if seq_sharded else (dp if _div(shape.global_batch, dp_size)
                                     else None)
    heads_ok = _div(cfg.n_kv_heads, model_size)
    s_parts = []
    if seq_sharded:
        s_parts.extend(dp)
    if not heads_ok:
        s_parts.append("model")
    s_ax = tuple(s_parts) if s_parts else None
    h_ax = "model" if heads_ok else None

    kv = P(None, b_ax, s_ax, h_ax, None)          # (L, B, S, kvH, hd)
    if cfg.family == "mla_moe":
        # one latent a token, shared by every head: replicated over 'model'
        lat = P(None, b_ax, tuple(dp) if seq_sharded else None, None)
        return {"latent_dense": lat, "latent": lat, "len": P()}
    d_ax = "model" if _div(cfg.d_model, model_size) else None
    if cfg.family == "ssm":
        return {
            "layers": {
                "tm": {"wkv": P(None, b_ax, "model" if _div(
                            cfg.d_model // cfg.rwkv_head_size, model_size)
                            else None, None, None),
                       "shift": P(None, b_ax, None, d_ax)},
                "cm": {"shift": P(None, b_ax, None, d_ax)},
            },
            "len": P(),
        }
    if cfg.family == "hybrid":
        din_ax = "model" if _div(2 * cfg.d_model, model_size) else None
        return {
            "k": kv, "v": kv,
            "mamba": {"h": P(None, None, b_ax, din_ax, None),
                      "conv": P(None, None, b_ax, None, din_ax)},
            "len": P(),
        }
    out = {"k": kv, "v": kv, "len": P()}
    if cfg.family == "audio":
        out["enc"] = P(b_ax, None, d_ax)
    return out


def hidden_spec(dp) -> P:
    """Residual-stream constraint: Megatron sequence parallelism — batch
    over data axes AND sequence over model between blocks."""
    return P(tuple(dp), "model", None)


# ----------------------------------------------------------------- FSDP
def fsdp_param_spec(path, leaf, axes: Tuple[str, ...], size: int) -> P:
    """ZeRO-3/FSDP layout: shard the largest dim divisible by the FULL
    device count over all mesh axes; XLA all-gathers params at each use.

    Beats TP for small-dense models where per-token TP collectives dwarf
    the per-step parameter traffic (see EXPERIMENTS.md §Perf iteration 1).
    """
    shape = tuple(getattr(leaf, "shape", ()))
    if not shape:
        return P()
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if shape[i] % size == 0 and shape[i] >= size:
            parts: list = [None] * len(shape)
            parts[i] = tuple(axes)
            return P(*parts)
    return P(*([None] * len(shape)))


def fsdp_param_specs(params: Any, axes: Tuple[str, ...], size: int):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: fsdp_param_spec(p, l, axes, size), params)
