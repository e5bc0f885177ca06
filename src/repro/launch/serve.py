"""Batched serving driver: prefill + decode with a KV/state cache.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --smoke \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.launch.mesh import activate_mesh
from repro.launch.train import choose_mesh
from repro.models import build_model
from repro.runtime.chip import enable_compile_cache


def serve(arch: str, batch: int, prompt_len: int, gen: int, smoke: bool,
          dtype=jnp.float32, greedy: bool = True, seed: int = 0):
    cfg = get_arch(arch)
    if smoke:
        cfg = cfg.smoke()
    mesh = choose_mesh()
    model = build_model(cfg, dtype=dtype, remat=False)

    with activate_mesh(mesh):
        params = jax.jit(model.init)(jax.random.key(seed))
        rng = np.random.default_rng(seed)
        prompts = jnp.asarray(rng.integers(0, cfg.vocab, (batch, prompt_len)),
                              jnp.int32)

        enc = None
        if cfg.family == "audio":
            frames = jnp.asarray(
                rng.standard_normal((batch, cfg.enc_ctx, cfg.d_model)), dtype)
            enc = model._encoder_stack(params, frames)

        max_len = prompt_len + gen + 1
        cache = model.init_cache(batch, max_len, enc_out=enc)

        step = jax.jit(model.decode_step, donate_argnums=(1,))
        # prefill via repeated decode steps for cache-correctness (a fused
        # prefill kernel is the production path; see repro.kernels)
        t0 = time.time()
        logits = None
        for t in range(prompt_len):
            logits, cache = step(params, cache, prompts[:, t])
        ttft = time.time() - t0

        toks = []
        t0 = time.time()
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for _ in range(gen):
            toks.append(tok)        # stays on device: no per-token sync
            logits, cache = step(params, cache, tok)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(logits)
        tpot = (time.time() - t0) / max(gen, 1)
        out = np.asarray(jnp.stack(toks, axis=1))
        return {"tokens": out, "ttft_s": ttft, "tpot_s": tpot}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    r = serve(args.arch, args.batch, args.prompt_len, args.gen, args.smoke)
    print(f"generated {r['tokens'].shape} tokens; "
          f"TTFT {r['ttft_s'] * 1e3:.1f}ms TPOT {r['tpot_s'] * 1e3:.2f}ms")
    print("first row:", r["tokens"][0][:16])


if __name__ == "__main__":
    main()
