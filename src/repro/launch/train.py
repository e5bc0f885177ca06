"""End-to-end training driver.

Runs on whatever devices exist (1 CPU here; a 256/512-chip mesh in
production — same code path, the mesh shape adapts).  Features exercised:
sharded train step, deterministic replayable data pipeline with prefetch,
async checkpointing, step retries with checkpoint restore, straggler
monitoring, optional int8 gradient compression, elastic restart.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --smoke --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.data import SyntheticLMDataset, make_batch_iter
from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.launch import steps as ST
from repro.launch.mesh import make_mesh, data_axes, activate_mesh
from repro.optim import AdamWConfig, adamw_init
from repro.models import build_model
from repro.runtime import StragglerMonitor
from repro.runtime.chip import enable_compile_cache


def choose_mesh():
    n = len(jax.devices())
    # largest (data, model) grid on the available devices, model <= 16
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0 and n >= m:
            model = m
            break
    return make_mesh((n // model, model), ("data", "model"))


def train(arch: str, steps: int, batch: int, seq: int, smoke: bool,
          ckpt_dir: Optional[str], ckpt_every: int = 50,
          lr: float = 3e-4, log_every: int = 10, resume: bool = True,
          dtype=jnp.float32, compress_grads: bool = False):
    cfg = get_arch(arch)
    if smoke:
        cfg = cfg.smoke()
    mesh = choose_mesh()
    model = build_model(cfg, dtype=dtype, remat=not smoke)
    opt_cfg = AdamWConfig(lr=lr, total_steps=steps,
                          warmup_steps=max(10, steps // 20))

    from repro.configs.base import ShapeConfig
    shape = ShapeConfig("cli", seq, batch, "train")
    sh = ST.shardings_for(mesh, model, cfg, shape, zero1=True)
    model.hidden_pspec = sh["hidden"]
    model.hidden_divisors = sh["divisors"]

    with activate_mesh(mesh):
        params = jax.jit(model.init)(jax.random.key(0))
        opt_state = adamw_init(params)
        start = 0
        ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        if ckpt_dir and resume:
            s = latest_step(ckpt_dir)
            if s is not None:
                state = restore_checkpoint(ckpt_dir, s,
                                           {"params": params, "opt": opt_state})
                params, opt_state = state["params"], state["opt"]
                start = s
                print(f"resumed from step {s}")

        if compress_grads:
            # int8 + error feedback on the DP gradient exchange
            from repro.optim import (compress_grads as cg,
                                     decompress_grads as dg, ef_init)
            from repro.optim import adamw_update

            def step_with_compression(params, opt_state, ef, batch):
                loss, grads = jax.value_and_grad(model.loss)(params, batch)
                comp, ef = cg(grads, ef)
                grads = dg(comp, grads)
                params, opt_state, metrics = adamw_update(
                    opt_cfg, grads, opt_state, params)
                return params, opt_state, ef, {"loss": loss, **metrics}

            ef_state = ef_init(params)
            raw_fn = jax.jit(step_with_compression, donate_argnums=(0, 1, 2))

            def step_fn(params, opt_state, batch, _ef=[ef_state]):
                params, opt_state, _ef[0], metrics = raw_fn(
                    params, opt_state, _ef[0], batch)
                return params, opt_state, metrics
        else:
            step_fn = jax.jit(ST.make_train_step(model, opt_cfg),
                              donate_argnums=(0, 1))
        ds = SyntheticLMDataset(cfg.vocab, seq, batch)
        it = make_batch_iter(ds, start, steps - start, mesh=mesh,
                             dp_axes=data_axes(mesh))
        mon = StragglerMonitor()
        losses = []
        for i, host_batch in zip(range(start, steps), it):
            t0 = time.time()
            params, opt_state, metrics = step_fn(params, opt_state, host_batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            mon.record(i, dt)
            if i % log_every == 0 or i == steps - 1:
                print(f"step {i:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} {dt * 1e3:.0f}ms",
                      flush=True)
            if ckpt and (i + 1) % ckpt_every == 0:
                ckpt.save(i + 1, {"params": params, "opt": opt_state})
        if ckpt:
            ckpt.save(steps, {"params": params, "opt": opt_state})
            ckpt.wait()
        if mon.flagged:
            print(f"straggler steps flagged: {len(mon.flagged)}")
        return losses


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8 gradient compression with error feedback")
    args = ap.parse_args()
    losses = train(args.arch, args.steps, args.batch, args.seq, args.smoke,
                   args.ckpt_dir, args.ckpt_every, args.lr,
                   compress_grads=args.compress_grads)
    first = np.mean(losses[:10])
    last = np.mean(losses[-10:])
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
