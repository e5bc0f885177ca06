"""Model assembly for every assigned architecture family.

One public entry point: :func:`build_model` -> :class:`Model`, exposing

* ``init(rng)``                          -> params pytree
* ``forward(params, batch)``             -> logits (train / prefill)
* ``loss(params, batch)``                -> scalar LM loss (+ MoE aux)
* ``init_cache(batch, max_len)``         -> decode cache pytree
* ``decode_step(params, cache, tokens)`` -> (logits, cache)

Layer stacks are ``jax.lax.scan`` over stacked parameters (leading layer
dim) so the HLO stays compact at 72 layers; the scan body is rematerialized
(``jax.checkpoint``) in training mode.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import mla as MLA
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.layers import (init_linear, init_mlp, layer_norm, linear,
                                 mlp, rms_norm)

PyTree = Any


def _stack_init(key, n: int, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


@dataclasses.dataclass
class Model:
    cfg: ArchConfig
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # optional PartitionSpec for the residual stream (sequence parallelism);
    # set by the launcher, applied between blocks when the (batch, seq) dims
    # divide the mesh axes exactly (jax rejects uneven shardings)
    hidden_pspec: Any = None
    hidden_divisors: Any = None          # (dp_size, model_size)
    # MoE token-dropping capacity factor; set to n_experts to disable drops
    moe_capacity: float = 1.25
    # grouped-dispatch group count (launcher sets to the DP degree) and the
    # dispatch-buffer PartitionSpec (P(dp, 'model', None, None))
    moe_groups: int = 1
    moe_buf_pspec: Any = None
    # MoE implementation: "dense" (pjit-partitioned scatter) or "shard_map"
    # (manual-collective expert parallelism — the production train/prefill
    # path, see repro.models.moe_shard); decode always uses "dense"
    moe_impl: str = "dense"
    moe_mesh: Any = None
    moe_dp_axes: Any = ("data",)
    # fully unroll layer scans (used by shallow-depth dry-run compiles so
    # cost_analysis sees every layer; scans count their body once)
    scan_unroll: bool = False

    def _moe(self, lp_moe, hin):
        cfg = self.cfg
        if self.moe_impl == "shard_map" and self.moe_mesh is not None:
            from repro.models import moe_shard as MS
            return MS.moe_block_sharded(
                lp_moe, hin, n_experts=cfg.n_experts, top_k=cfg.top_k,
                mesh=self.moe_mesh, dp_axes=self.moe_dp_axes,
                capacity_factor=self.moe_capacity)
        return M.moe_block(lp_moe, hin, n_experts=cfg.n_experts,
                           top_k=cfg.top_k,
                           capacity_factor=self.moe_capacity,
                           n_groups=self.moe_groups,
                           buf_pspec=self.moe_buf_pspec)

    def _scan(self, body, init, xs):
        return jax.lax.scan(body, init, xs, unroll=True if self.scan_unroll
                            else 1)

    def _constrain(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.hidden_pspec is None or x.ndim != 3:
            return x
        dp, mp = self.hidden_divisors or (1, 1)
        if x.shape[0] % max(dp, 1) == 0 and x.shape[1] % max(mp, 1) == 0 \
                and x.shape[1] >= mp > 1:
            return jax.lax.with_sharding_constraint(x, self.hidden_pspec)
        return x

    # ------------------------------------------------------------------
    def init(self, rng) -> PyTree:
        cfg = self.cfg
        k_emb, k_layers, k_head, k_enc = jax.random.split(rng, 4)
        params: Dict[str, PyTree] = {
            "embed": jax.random.normal(
                k_emb, (cfg.vocab, cfg.d_model), self.dtype) * 0.02,
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init_linear(k_head, cfg.d_model, cfg.vocab,
                                            dtype=self.dtype)
        fam = cfg.family
        if fam == "ssm":
            params["layers"] = _stack_init(k_layers, cfg.n_layers,
                                           self._init_rwkv_layer)
        elif fam == "hybrid":
            n_blocks = cfg.n_layers // cfg.attn_every
            params["layers"] = _stack_init(k_layers, n_blocks,
                                           self._init_jamba_block)
        elif fam == "mla_moe":
            n_dense = self._n_dense
            if n_dense:
                params["dense_layers"] = _stack_init(
                    k_enc, n_dense, lambda k: self._init_mla_layer(k, False))
            params["layers"] = _stack_init(
                k_layers, cfg.n_layers - n_dense,
                lambda k: self._init_mla_layer(k, True))
        elif fam == "audio":
            params["enc_layers"] = _stack_init(k_enc, cfg.enc_layers,
                                               self._init_encoder_layer)
            params["enc_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
            params["layers"] = _stack_init(k_layers, cfg.n_layers,
                                           self._init_decoder_layer)
        else:  # dense / moe / vlm
            params["layers"] = _stack_init(k_layers, cfg.n_layers,
                                           self._init_decoder_layer)
        return params

    # ---------------- per-layer initializers ----------------
    def _init_attn(self, key):
        cfg = self.cfg
        return A.init_attention(key, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                cfg.head_dim, cfg.qkv_bias, self.dtype)

    def _init_decoder_layer(self, key):
        cfg = self.cfg
        ka, kf, kx = jax.random.split(key, 3)
        p = {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
             "ln2": jnp.ones((cfg.d_model,), jnp.float32),
             "attn": self._init_attn(ka)}
        if cfg.family == "audio":
            p["ln_x"] = jnp.ones((cfg.d_model,), jnp.float32)
            p["xattn"] = self._init_attn(kx)
        if cfg.n_experts:
            p["moe"] = M.init_moe(kf, cfg.d_model, cfg.expert_ff,
                                  cfg.n_experts, cfg.n_shared_experts,
                                  cfg.d_ff, self.dtype,
                                  expert_pad=cfg.expert_pad)
            if cfg.dense_residual:
                kd = jax.random.fold_in(kf, 1)
                p["mlp"] = init_mlp(kd, cfg.d_model, cfg.d_ff,
                                    gated=cfg.gated_mlp, dtype=self.dtype)
        else:
            p["mlp"] = init_mlp(kf, cfg.d_model, cfg.d_ff,
                                gated=cfg.gated_mlp, dtype=self.dtype)
        return p

    @property
    def _n_dense(self) -> int:
        """Leading dense layers of an ``mla_moe`` stack."""
        return min(self.cfg.first_k_dense, self.cfg.n_layers)

    def _init_mla_layer(self, key, moe: bool):
        cfg = self.cfg
        ka, kf = jax.random.split(key)
        p = {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
             "ln2": jnp.ones((cfg.d_model,), jnp.float32),
             "attn": MLA.init_mla(ka, cfg, self.dtype)}
        if moe:
            p["moe"] = M.init_moe(kf, cfg.d_model, cfg.expert_ff,
                                  cfg.n_experts, cfg.n_shared_experts,
                                  cfg.expert_ff * cfg.n_shared_experts,
                                  self.dtype, router_bias=True)
        else:
            p["mlp"] = init_mlp(kf, cfg.d_model, cfg.d_ff, gated=True,
                                dtype=self.dtype)
        return p

    def _init_encoder_layer(self, key):
        cfg = self.cfg
        ka, kf = jax.random.split(key)
        return {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
                "ln2": jnp.ones((cfg.d_model,), jnp.float32),
                "attn": self._init_attn(ka),
                "mlp": init_mlp(kf, cfg.d_model, cfg.d_ff, gated=False,
                                dtype=self.dtype)}

    def _init_rwkv_layer(self, key):
        cfg = self.cfg
        return {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
                "ln2": jnp.ones((cfg.d_model,), jnp.float32),
                **{"rwkv_" + k: v for k, v in S.init_rwkv(
                    key, cfg.d_model, cfg.rwkv_head_size, cfg.d_ff,
                    self.dtype).items()}}

    def _init_jamba_block(self, key):
        """One Jamba period: `attn_every` sub-layers; sub-layer 0 is
        attention, the rest are Mamba; FFN alternates MoE/dense."""
        cfg = self.cfg
        per = cfg.attn_every
        keys = jax.random.split(key, 2 * per + 1)
        p: Dict[str, PyTree] = {
            "attn": self._init_attn(keys[0]),
            "attn_ln": jnp.ones((cfg.d_model,), jnp.float32),
        }
        mamba = [S.init_mamba(keys[1 + i], cfg.d_model, cfg.d_state,
                              cfg.d_conv, self.dtype) for i in range(per - 1)]
        p["mamba"] = jax.tree.map(lambda *xs: jnp.stack(xs), *mamba)
        p["mamba_ln"] = jnp.ones((per - 1, cfg.d_model), jnp.float32)
        n_moe = per // 2
        moe = [M.init_moe(keys[per + i], cfg.d_model, cfg.expert_ff,
                          cfg.n_experts, 0, 0, self.dtype,
                          expert_pad=cfg.expert_pad)
               for i in range(n_moe)]
        p["moe"] = jax.tree.map(lambda *xs: jnp.stack(xs), *moe)
        dense = [init_mlp(keys[per + n_moe + i], cfg.d_model, cfg.d_ff,
                          gated=True, dtype=self.dtype)
                 for i in range(per - n_moe)]
        p["mlp"] = jax.tree.map(lambda *xs: jnp.stack(xs), *dense)
        p["ffn_ln"] = jnp.ones((per, cfg.d_model), jnp.float32)
        return p

    # ==================================================================
    # forward (train / prefill)
    # ==================================================================
    def forward(self, params: PyTree, batch: Dict[str, jnp.ndarray],
                collect_aux: bool = False):
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        aux_total = jnp.zeros((), jnp.float32)

        fam = cfg.family
        if fam == "ssm":
            x, aux_total = self._rwkv_stack(params, x)
        elif fam == "hybrid":
            x, aux_total = self._jamba_stack(params, x)
        elif fam == "mla_moe":
            x, aux_total = self._mla_stack(params, x)
        elif fam == "audio":
            enc = self._encoder_stack(params, batch["frames"].astype(self.dtype))
            x, aux_total = self._decoder_stack(params, x, enc=enc)
        else:
            x, aux_total = self._decoder_stack(params, x)

        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self._logits(params, x)
        if collect_aux:
            return logits, aux_total
        return logits

    def loss(self, params: PyTree, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        logits, aux = self.forward(params, batch, collect_aux=True)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        nll = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return nll + 0.01 * aux

    # ---------------- shared pieces ----------------
    def _embed_inputs(self, params, batch):
        if "embeds" in batch:                       # vlm/audio-style stub input
            return batch["embeds"].astype(self.dtype)
        return params["embed"][batch["tokens"]]

    def _logits(self, params, x):
        if self.cfg.tie_embeddings:
            return jnp.einsum("...d,vd->...v", x, params["embed"])
        return linear(params["lm_head"], x)

    def _maybe_remat(self, f):
        return jax.checkpoint(f) if self.remat else f

    # ---------------- dense / moe / vlm decoder stack ----------------
    def _decoder_stack(self, params, x, enc=None):
        cfg = self.cfg

        def body(carry, lp):
            h, aux = carry
            a = A.attention_block(
                lp["attn"], rms_norm(lp["ln1"], h, cfg.norm_eps),
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim,
                rope_theta=None if cfg.family == "audio" else cfg.rope_theta)
            h = h + a
            if enc is not None:
                c = A.attention_block(
                    lp["xattn"], rms_norm(lp["ln_x"], h, cfg.norm_eps),
                    n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.head_dim, rope_theta=None, kv=enc)
                h = h + c
            hin = rms_norm(lp["ln2"], h, cfg.norm_eps)
            if cfg.n_experts:
                f, a_loss = self._moe(lp["moe"], hin)
                aux = aux + a_loss
                if cfg.dense_residual:
                    f = f + mlp(lp["mlp"], hin, gated=cfg.gated_mlp)
            else:
                f = mlp(lp["mlp"], hin, gated=cfg.gated_mlp)
            return (self._constrain(h + f), aux), None

        (x, aux), _ = self._scan(self._maybe_remat(body),
                                 (x, jnp.zeros((), jnp.float32)),
                                 params["layers"])
        return x, aux

    # ---------------- latent-attention (DeepSeek-V3) stack ----------------
    def _mla_ffn(self, lp, hin):
        cfg = self.cfg
        if "moe" not in lp:
            return mlp(lp["mlp"], hin, gated=True), jnp.zeros((), jnp.float32)
        return M.deepseek_moe_block(
            lp["moe"], hin, n_experts=cfg.n_experts, top_k=cfg.top_k,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            routed_scaling=cfg.routed_scaling,
            capacity_factor=self.moe_capacity, n_groups=self.moe_groups,
            buf_pspec=self.moe_buf_pspec)

    def _mla_stacks(self):
        """(layer-param stack, cache entry) of the dense layers, then of
        the MoE layers."""
        dense = [("dense_layers", "latent_dense")] if self._n_dense else []
        return dense + [("layers", "latent")]

    def _mla_stack(self, params, x):
        cfg = self.cfg

        def body(carry, lp):
            h, aux = carry
            h = h + MLA.attention_block(
                lp["attn"], rms_norm(lp["ln1"], h, cfg.norm_eps), cfg)
            f, a_loss = self._mla_ffn(lp, rms_norm(lp["ln2"], h,
                                                   cfg.norm_eps))
            return (self._constrain(h + f), aux + a_loss), None

        aux = jnp.zeros((), jnp.float32)
        for name, _ in self._mla_stacks():
            (x, aux), _ = self._scan(self._maybe_remat(body), (x, aux),
                                     params[name])
        return x, aux

    def _encoder_stack(self, params, frames):
        cfg = self.cfg

        def body(h, lp):
            a = A.attention_block(
                lp["attn"], rms_norm(lp["ln1"], h, cfg.norm_eps),
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, rope_theta=None, causal=False)
            h = h + a
            f = mlp(lp["mlp"], rms_norm(lp["ln2"], h, cfg.norm_eps), gated=False)
            return self._constrain(h + f), None

        h, _ = self._scan(self._maybe_remat(body), frames,
                          params["enc_layers"])
        return rms_norm(params["enc_norm"], h, cfg.norm_eps)

    # ---------------- rwkv stack ----------------
    def _rwkv_stack(self, params, x):
        cfg = self.cfg

        def body(h, lp):
            rp = {k[5:]: v for k, v in lp.items() if k.startswith("rwkv_")}
            t, _ = S.rwkv_time_mix(rp, rms_norm(lp["ln1"], h, cfg.norm_eps),
                                   cfg.rwkv_head_size)
            h = h + t
            c, _ = S.rwkv_channel_mix(rp, rms_norm(lp["ln2"], h, cfg.norm_eps))
            return self._constrain(h + c), None

        x, _ = self._scan(self._maybe_remat(body), x, params["layers"])
        return x, jnp.zeros((), jnp.float32)

    # ---------------- jamba stack ----------------
    def _jamba_stack(self, params, x):
        cfg = self.cfg
        per = cfg.attn_every

        def block(carry, bp):
            h, aux = carry
            n_moe = per // 2
            mi = di = 0
            for i in range(per):
                if i == 0:
                    a = A.attention_block(
                        bp["attn"], rms_norm(bp["attn_ln"], h, cfg.norm_eps),
                        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta)
                    h = h + a
                else:
                    mp = jax.tree.map(lambda v, j=i - 1: v[j], bp["mamba"])
                    m, _ = S.mamba_block(
                        mp, rms_norm(bp["mamba_ln"][i - 1], h, cfg.norm_eps))
                    h = h + m
                hin = rms_norm(bp["ffn_ln"][i], h, cfg.norm_eps)
                if i % 2 == 0:
                    ep = jax.tree.map(lambda v, j=mi: v[j], bp["moe"])
                    f, al = self._moe(ep, hin)
                    aux = aux + al
                    mi += 1
                else:
                    dp = jax.tree.map(lambda v, j=di: v[j], bp["mlp"])
                    f = mlp(dp, hin, gated=True)
                    di += 1
                h = h + f
            return (self._constrain(h), aux), None

        (x, aux), _ = self._scan(self._maybe_remat(block),
                                 (x, jnp.zeros((), jnp.float32)),
                                 params["layers"])
        return x, aux

    # ==================================================================
    # decode path
    # ==================================================================
    def init_cache(self, batch_size: int, max_len: int,
                   enc_out: Optional[jnp.ndarray] = None) -> PyTree:
        cfg = self.cfg
        fam = cfg.family

        def kv(n):
            return {"k": jnp.zeros((n, batch_size, max_len, cfg.n_kv_heads,
                                    cfg.head_dim), self.dtype),
                    "v": jnp.zeros((n, batch_size, max_len, cfg.n_kv_heads,
                                    cfg.head_dim), self.dtype)}

        if fam == "ssm":
            st = S.rwkv_init_state(batch_size, cfg.d_model,
                                   cfg.rwkv_head_size, self.dtype)
            return {"layers": jax.tree.map(
                lambda a: jnp.stack([a] * cfg.n_layers), st),
                "len": jnp.zeros((), jnp.int32)}
        if fam == "hybrid":
            nb = cfg.n_layers // cfg.attn_every
            ms = S.mamba_init_state(batch_size, cfg.d_model, cfg.d_state,
                                    cfg.d_conv, self.dtype)
            stacked_m = jax.tree.map(
                lambda a: jnp.stack([jnp.stack([a] * (cfg.attn_every - 1))] * nb), ms)
            return {**kv(nb), "mamba": stacked_m, "len": jnp.zeros((), jnp.int32)}
        if fam == "mla_moe":
            width = cfg.kv_lora_rank + cfg.qk_rope_head_dim

            def lat(n):
                return jnp.zeros((n, batch_size, max_len, width), self.dtype)
            return {"latent_dense": lat(self._n_dense),
                    "latent": lat(cfg.n_layers - self._n_dense),
                    "len": jnp.zeros((), jnp.int32)}
        cache = {**kv(cfg.n_layers), "len": jnp.zeros((), jnp.int32)}
        if fam == "audio":
            cache["enc"] = enc_out
        return cache

    def decode_step(self, params: PyTree, cache: PyTree,
                    tokens: jnp.ndarray) -> Tuple[jnp.ndarray, PyTree]:
        """tokens: (B,) int32 -> logits (B, vocab), updated cache."""
        cfg = self.cfg
        x = params["embed"][tokens][:, None, :]           # (B, 1, d)
        fam = cfg.family
        if fam == "ssm":
            x, cache = self._rwkv_decode(params, cache, x)
        elif fam == "hybrid":
            x, cache = self._jamba_decode(params, cache, x)
        elif fam == "mla_moe":
            x, cache = self._mla_decode(params, cache, x)
        else:
            x, cache = self._decoder_decode(params, cache, x)
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self._logits(params, x)[:, 0]
        return logits, cache

    def _decoder_decode(self, params, cache, x):
        cfg = self.cfg
        enc = cache.get("enc")

        def body(carry, lp_and_cache):
            h = carry
            lp, kc, vc = lp_and_cache
            a, new_c = A.cached_attention_step(
                lp["attn"], rms_norm(lp["ln1"], h, cfg.norm_eps),
                {"k": kc, "v": vc, "len": cache["len"]},
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim,
                rope_theta=None if cfg.family == "audio" else cfg.rope_theta)
            h = h + a
            if enc is not None:
                c = A.attention_block(
                    lp["xattn"], rms_norm(lp["ln_x"], h, cfg.norm_eps),
                    n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.head_dim, rope_theta=None, kv=enc)
                h = h + c
            hin = rms_norm(lp["ln2"], h, cfg.norm_eps)
            if cfg.n_experts:
                f, _ = M.moe_block(lp["moe"], hin, n_experts=cfg.n_experts,
                                   top_k=cfg.top_k,
                                   capacity_factor=self.moe_capacity,
                                        n_groups=self.moe_groups,
                                        buf_pspec=self.moe_buf_pspec)
                if cfg.dense_residual:
                    f = f + mlp(lp["mlp"], hin, gated=cfg.gated_mlp)
            else:
                f = mlp(lp["mlp"], hin, gated=cfg.gated_mlp)
            return h + f, (new_c["k"], new_c["v"])

        h, (new_k, new_v) = self._scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = dict(cache, k=new_k, v=new_v, len=cache["len"] + 1)
        return h, new_cache

    def _mla_decode(self, params, cache, x):
        cfg = self.cfg
        length = cache["len"]

        def body(h, lp_and_cache):
            lp, lat = lp_and_cache
            a, lat = MLA.cached_attention_step(
                lp["attn"], rms_norm(lp["ln1"], h, cfg.norm_eps), lat,
                length, cfg)
            h = h + a
            f, _ = self._mla_ffn(lp, rms_norm(lp["ln2"], h, cfg.norm_eps))
            return h + f, lat

        new = dict(cache, len=length + 1)
        for name, key in self._mla_stacks():
            x, new[key] = self._scan(body, x, (params[name], cache[key]))
        return x, new

    def _rwkv_decode(self, params, cache, x):
        cfg = self.cfg

        def body(h, lp_and_state):
            lp, st = lp_and_state
            rp = {k[5:]: v for k, v in lp.items() if k.startswith("rwkv_")}
            t, tm = S.rwkv_time_mix(rp, rms_norm(lp["ln1"], h, cfg.norm_eps),
                                    cfg.rwkv_head_size, state=st["tm"])
            h = h + t
            c, cm = S.rwkv_channel_mix(
                rp, rms_norm(lp["ln2"], h, cfg.norm_eps), state=st["cm"])
            return h + c, {"tm": tm, "cm": cm}

        h, new_state = self._scan(body, x,
                                  (params["layers"], cache["layers"]))
        return h, {"layers": new_state, "len": cache["len"] + 1}

    def _jamba_decode(self, params, cache, x):
        cfg = self.cfg
        per = cfg.attn_every

        def block(h, bp_and_cache):
            bp, kc, vc, mstates = bp_and_cache
            new_m = []
            for i in range(per):
                if i == 0:
                    a, new_kv = A.cached_attention_step(
                        bp["attn"], rms_norm(bp["attn_ln"], h, cfg.norm_eps),
                        {"k": kc, "v": vc, "len": cache["len"]},
                        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta)
                    h = h + a
                else:
                    mp = jax.tree.map(lambda v, j=i - 1: v[j], bp["mamba"])
                    st = jax.tree.map(lambda v, j=i - 1: v[j], mstates)
                    m, ns = S.mamba_block(
                        mp, rms_norm(bp["mamba_ln"][i - 1], h, cfg.norm_eps),
                        state=st)
                    new_m.append(ns)
                    h = h + m
                hin = rms_norm(bp["ffn_ln"][i], h, cfg.norm_eps)
                if i % 2 == 0:
                    ep = jax.tree.map(lambda v, j=i // 2: v[j], bp["moe"])
                    f, _ = M.moe_block(ep, hin, n_experts=cfg.n_experts,
                                       top_k=cfg.top_k,
                                       capacity_factor=self.moe_capacity,
                                        n_groups=self.moe_groups,
                                        buf_pspec=self.moe_buf_pspec)
                else:
                    dp = jax.tree.map(lambda v, j=i // 2: v[j], bp["mlp"])
                    f = mlp(dp, hin, gated=True)
                h = h + f
            stacked_m = jax.tree.map(lambda *xs: jnp.stack(xs), *new_m)
            return h, (new_kv["k"], new_kv["v"], stacked_m)

        h, (nk, nv, nm) = self._scan(
            block, x, (params["layers"], cache["k"], cache["v"],
                       cache["mamba"]))
        return h, {"k": nk, "v": nv, "mamba": nm, "len": cache["len"] + 1}


def build_model(cfg: ArchConfig, dtype=jnp.bfloat16, remat: bool = True,
                moe_capacity: float = 1.25) -> Model:
    return Model(cfg=cfg, dtype=dtype, remat=remat, moe_capacity=moe_capacity)
