"""Kernel microbenchmarks: wall time of the jnp oracle on CPU (the kernels
themselves are TPU-target; interpret mode is correctness-only, so the CSV
reports oracle timings + kernel-vs-oracle max error)."""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref


def _time(fn, *args, reps: int = 3) -> float:
    fn(*args)                                   # compile
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6      # us


def run() -> List[str]:
    rng = np.random.default_rng(0)
    lines = []

    b, s, h, hd = 2, 256, 4, 64
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
               for _ in range(3))
    ref_fn = jax.jit(lambda q, k, v: attention_ref(
        q.transpose(0, 2, 1, 3).reshape(b * h, s, hd),
        k.transpose(0, 2, 1, 3).reshape(b * h, s, hd),
        v.transpose(0, 2, 1, 3).reshape(b * h, s, hd)))
    us = _time(ref_fn, q, k, v)
    out = flash_attention(q, k, v, interpret=True, block_q=128, block_k=128)
    ref = ref_fn(q, k, v).reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    err = float(jnp.abs(out - ref).max())
    lines.append(f"kernels,flash_attention_oracle,{us:.1f},maxerr={err:.2e}")

    t = 128
    r2 = jnp.asarray(rng.standard_normal((b, t, h, hd)) * .5, jnp.float32)
    w2 = jnp.asarray(rng.uniform(.3, .99, (b, t, h, hd)), jnp.float32)
    u2 = jnp.asarray(rng.standard_normal((h, hd)) * .1, jnp.float32)
    fl = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    uf = jnp.broadcast_to(u2[None], (b, h, hd)).reshape(b * h, 1, hd)
    ref_fn = jax.jit(lambda r: rwkv6_scan_ref(fl(r), fl(r), fl(r), fl(w2), uf))
    us = _time(ref_fn, r2)
    y = rwkv6_scan(r2, r2, r2, w2, u2, interpret=True)
    ref = ref_fn(r2).reshape(b, h, t, hd).transpose(0, 2, 1, 3)
    err = float(jnp.abs(y - ref).max())
    lines.append(f"kernels,rwkv6_scan_oracle,{us:.1f},maxerr={err:.2e}")

    d, n = 64, 16
    u3 = jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
    dt3 = jnp.asarray(rng.uniform(.001, .1, (b, t, d)), jnp.float32)
    a3 = -jnp.asarray(rng.uniform(.5, 2., (d, n)), jnp.float32)
    B3 = jnp.asarray(rng.standard_normal((b, t, n)), jnp.float32)
    C3 = jnp.asarray(rng.standard_normal((b, t, n)), jnp.float32)
    ref_fn = jax.jit(ssm_scan_ref)
    us = _time(ref_fn, u3, dt3, a3, B3, C3)
    y = ssm_scan(u3, dt3, a3, B3, C3, interpret=True)
    err = float(jnp.abs(y - ref_fn(u3, dt3, a3, B3, C3)).max())
    lines.append(f"kernels,ssm_scan_oracle,{us:.1f},maxerr={err:.2e}")
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
