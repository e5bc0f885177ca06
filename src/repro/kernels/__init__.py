"""Pallas TPU kernels for the perf-critical hot spots.

Each kernel package has:
  kernel.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (with interpret-mode fallback on CPU)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels:
  flash_attention — tiled online-softmax attention (prefill hot spot)
  rwkv6_scan      — RWKV6 data-dependent-decay recurrence (Finch time-mix)
  ssm_scan        — Mamba selective scan (Jamba hot spot)
"""

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.ssm_scan.ops import ssm_scan

__all__ = ["flash_attention", "rwkv6_scan", "ssm_scan"]
