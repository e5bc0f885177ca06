"""Multi-head latent attention (DeepSeek-V2/V3), in plain ``jax.numpy``.

Queries go through a ``q_lora_rank`` latent; keys and values through one
``kv_lora_rank`` latent ``c`` plus a ``qk_rope_head_dim`` RoPE key ``k_pe``
that every head shares.  The cache holds ``[c, k_pe]`` a token (576
values at DeepSeek-V3's sizes), with ``c`` already normalized and ``k_pe``
already rotated.

- Prefill (``attention_block``) decompresses the latent: ``kv_b`` maps
  ``c`` to each head's ``k_nope`` and ``v``, and attention runs at q/k
  width ``nope + rope`` and v width ``v_head_dim``.
- Decode (``cached_attention_step``) absorbs the up-projections: W_UK goes
  into the query (``q_nope @ W_UK`` lives in latent space), the scores are
  one product of each head's ``[q_lat, q_pe]`` with the cached ``[c,
  k_pe]``, AV runs in latent space over ``c``, and W_UV follows.  Both
  products read the cache once, for all heads.

``attend_decompressed`` and ``attend_absorbed`` compute the same attention
over a given latent; the tests hold them equal.  Departure from the
published model: RoPE runs at ``rope_theta`` without YaRN scaling (the
softmax scale is ``1 / sqrt(nope + rope)``).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import NEG_INF
from repro.models.layers import apply_rope, init_linear, linear, rms_norm


def init_mla(key, cfg, dtype=jnp.bfloat16) -> Dict:
    H = cfg.n_heads
    nope, dr, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    kqa, kqb, kkva, kkvb, ko = jax.random.split(key, 5)
    return {
        "q_a": init_linear(kqa, cfg.d_model, cfg.q_lora_rank, dtype=dtype),
        "q_a_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
        "q_b": init_linear(kqb, cfg.q_lora_rank, H * (nope + dr),
                           dtype=dtype),
        "kv_a": init_linear(kkva, cfg.d_model, r + dr, dtype=dtype),
        "kv_a_norm": jnp.ones((r,), jnp.float32),
        "kv_b": init_linear(kkvb, r, H * (nope + vd), dtype=dtype),
        "o": init_linear(ko, H * vd, cfg.d_model, dtype=dtype),
    }


def queries(p: Dict, x: jnp.ndarray, pos: jnp.ndarray,
            cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, d) -> q_nope (B, S, H, nope), rotated q_pe (B, S, H, rope)."""
    b, s, _ = x.shape
    nope, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = linear(p["q_b"], rms_norm(p["q_a_norm"], linear(p["q_a"], x),
                                  cfg.norm_eps))
    q = q.reshape(b, s, cfg.n_heads, nope + dr)
    return q[..., :nope], apply_rope(q[..., nope:], pos, cfg.rope_theta)


def latent(p: Dict, x: jnp.ndarray, pos: jnp.ndarray, cfg) -> jnp.ndarray:
    """x (B, S, d) -> the cached latent (B, S, kv_lora_rank + rope):
    the normalized ``c`` and the rotated shared ``k_pe``."""
    r = cfg.kv_lora_rank
    kv = linear(p["kv_a"], x)
    c = rms_norm(p["kv_a_norm"], kv[..., :r], cfg.norm_eps)
    k_pe = apply_rope(kv[..., None, r:], pos, cfg.rope_theta)[..., 0, :]
    return jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)


def _scale(cfg) -> float:
    return 1.0 / float(np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))


def _softmax(logits: jnp.ndarray, mask: jnp.ndarray, dtype) -> jnp.ndarray:
    logits = jnp.where(mask, logits.astype(jnp.float32), NEG_INF)
    return jax.nn.softmax(logits, axis=-1).astype(dtype)


def attend_decompressed(p: Dict, q_nope: jnp.ndarray, q_pe: jnp.ndarray,
                        lat: jnp.ndarray, mask: jnp.ndarray,
                        cfg) -> jnp.ndarray:
    """Attention of q (B, Sq, H, .) over the latent (B, Sk, r + rope),
    decompressed per head through ``kv_b``; ``mask`` (Sq, Sk) or
    broadcastable.  Returns (B, Sq, H * v_head_dim)."""
    b, sk, _ = lat.shape
    H, nope, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    kv = linear(p["kv_b"], lat[..., :r]).reshape(b, sk, H, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = jnp.broadcast_to(lat[:, :, None, r:],
                            (b, sk, H, cfg.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], axis=-1)
    k = jnp.concatenate([k_nope, k_pe.astype(k_nope.dtype)], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * _scale(cfg)
    w = _softmax(s, mask, v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v)
    return o.reshape(o.shape[0], o.shape[1], -1)


def attend_absorbed(p: Dict, q_nope: jnp.ndarray, q_pe: jnp.ndarray,
                    lat: jnp.ndarray, mask: jnp.ndarray, cfg) -> jnp.ndarray:
    """The same attention with ``kv_b``'s halves absorbed: W_UK into the
    query, W_UV after the latent-space AV.  Returns (B, Sq, H * v)."""
    H, nope, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    w_kv = p["kv_b"]["w"].reshape(r, H, -1)
    w_uk, w_uv = w_kv[..., :nope], w_kv[..., nope:]
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
    q = jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)
    s = jnp.einsum("bqhc,bkc->bhqk", q, lat) * _scale(cfg)
    w = _softmax(s, mask, lat.dtype)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", w, lat[..., :r])
    o = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv)
    return o.reshape(o.shape[0], o.shape[1], -1)


def attention_block(p: Dict, x: jnp.ndarray, cfg) -> jnp.ndarray:
    """Causal self-attention of a whole sequence (train / prefill)."""
    s = x.shape[1]
    pos = jnp.arange(s)[None, :]
    q_nope, q_pe = queries(p, x, pos, cfg)
    lat = latent(p, x, pos, cfg)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    return linear(p["o"], attend_decompressed(p, q_nope, q_pe, lat, causal,
                                              cfg))


def cached_attention_step(p: Dict, x: jnp.ndarray, cache: jnp.ndarray,
                          length: jnp.ndarray,
                          cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode step: x (B, 1, d), cache (B, S, r + rope) holding
    ``length`` tokens -> (out (B, 1, d), the cache with this token's
    latent at ``length``), attention absorbed."""
    pos = length[None, None]
    q_nope, q_pe = queries(p, x, pos, cfg)
    new = latent(p, x, pos, cfg).astype(cache.dtype)             # (B, 1, .)
    # one-hot masked insert, as the GQA cache does (keeps any sharding)
    hot = (jnp.arange(cache.shape[1]) == length).astype(cache.dtype)
    cache = cache * (1 - hot[None, :, None]) + hot[None, :, None] * new
    mask = jnp.arange(cache.shape[1])[None, :] <= length
    o = attend_absorbed(p, q_nope, q_pe, cache, mask, cfg)
    return linear(p["o"], o), cache
