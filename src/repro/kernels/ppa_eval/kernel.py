"""Batched design-point PPA evaluation kernel (TPU Pallas).

The Lumina substrate hot loop: evaluate a block of candidate architectures
against a workload operator table (roofline tier).  This is the computation
the paper reports costing 6000 CPU-hours per 1000 LLMCompass samples; the
vectorized JAX model brings it to seconds, and this kernel is the TPU-native
tiling of that evaluation for full-space (4.7M-point) sweeps.

Tiling: grid = (n_design_blocks,); each step loads a (block_b, 8) tile of
decoded design values into VMEM, keeps the whole (n_ops, 8) operator table
(tiny — every workload here is < 128 ops) in SMEM, and runs a fori_loop
over ops that reads each op's fields as scalars straight from the SMEM ref
(Mosaic cannot lower a dynamic slice of a loaded vector value), accumulating
latency and the four per-stall-class times in registers/VMEM.  Output tile:
(block_b, 8) = [latency, 4 stalls, area, 0, 0].

Math mirrors repro.perfmodel.roofline exactly (ref.py delegates to it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.perfmodel.hardware import (
    AREA_BASE, AREA_CORE_BASE, AREA_PER_CHANNEL, AREA_PER_GBUF_MB,
    AREA_PER_LINK, AREA_PER_MAC, AREA_PER_SRAM_KB, AREA_PER_VLANE,
    BW_PER_CHANNEL, BW_PER_LINK, BYTES_FP16, CLOCK_HZ, LINK_LATENCY_S)
from repro.perfmodel.roofline import SRAM_FEED_WORDS_PER_KB
from repro.perfmodel import workload as W

# design-value column order (matches designspace.PARAM_NAMES)
LINKS, CORES, SUBLANES, SA, VW, SRAM, GBUF, CHAN = range(8)
# op-table column order
OP_KIND, OP_FLOPS, OP_BYTES, OP_M, OP_N, OP_K, OP_COMM, OP_COUNT = range(8)


def _ceil_div(a, b):
    return jnp.ceil(a / b)


def _ppa_kernel(dv_ref, ops_ref, out_ref, *, n_ops: int, tp: float):
    dv = dv_ref[...].astype(jnp.float32)          # (bb, 8)

    cores, sub, sa, vw = dv[:, CORES], dv[:, SUBLANES], dv[:, SA], dv[:, VW]
    sram, gbuf_mb, chan, links = dv[:, SRAM], dv[:, GBUF], dv[:, CHAN], dv[:, LINKS]

    tensor = cores * sub * sa * sa * 2.0 * CLOCK_HZ
    vector = cores * sub * vw * 2.0 * CLOCK_HZ
    mem_bw = chan * BW_PER_CHANNEL
    ici_bw = links * BW_PER_LINK
    gbuf_elems = jnp.maximum(gbuf_mb * 2.0 ** 20 / BYTES_FP16, 1.0)

    bb = dv.shape[0]
    lat0 = jnp.zeros((bb,), jnp.float32)
    stalls0 = jnp.zeros((bb, 4), jnp.float32)

    def body(i, carry):
        lat, stalls = carry
        kind = ops_ref[i, OP_KIND]                # SMEM scalar reads
        flops, nbytes = ops_ref[i, OP_FLOPS], ops_ref[i, OP_BYTES]
        m, n, k = ops_ref[i, OP_M], ops_ref[i, OP_N], ops_ref[i, OP_K]
        comm, count = ops_ref[i, OP_COMM], ops_ref[i, OP_COUNT]

        # matmul utilization (mirrors roofline.matmul_utilization)
        u_k = k / (_ceil_div(k, sa) * sa)
        u_n = n / (_ceil_div(n, sa) * sa)
        u_pipe = m / (m + sa)
        n_tiles = _ceil_div(m, sa) * _ceil_div(n, sa)
        u_par = jnp.minimum(1.0, n_tiles / (cores * sub))
        sram_need = 3.0 * 2.0 * sa * sa * BYTES_FP16 / 1024.0
        u_sram = jnp.minimum(1.0, sram / sram_need)
        u_feed = jnp.minimum(1.0, SRAM_FEED_WORDS_PER_KB * sram / (sa * sub))
        util = u_k * u_n * u_pipe * u_par * u_sram * u_feed

        is_mm = kind == W.MATMUL
        is_vec = kind == W.VECTOR
        is_ar = kind == W.ALLREDUCE
        is_p2p = kind == W.P2P

        bytes_eff = jnp.where(
            is_mm,
            jnp.maximum(nbytes, 2.0 * m * n * k / jnp.sqrt(gbuf_elems) * BYTES_FP16),
            nbytes)
        t_c = jnp.where(is_mm, flops / (tensor * util),
                        jnp.where(is_vec, flops / vector, 0.0))
        t_m = bytes_eff / mem_bw
        steps_ar = 2.0 * (tp - 1.0)
        t_ar = steps_ar / tp * comm / ici_bw + steps_ar * LINK_LATENCY_S
        t_p2p = (tp - 1.0) / tp * comm / ici_bw + (tp - 1.0) * LINK_LATENCY_S
        t_x = jnp.where(is_ar, t_ar, jnp.where(is_p2p, t_p2p, 0.0))

        t_op = jnp.maximum(jnp.maximum(t_c, t_m), t_x) * count
        dom_comm = (t_x >= t_c) & (t_x >= t_m)
        dom_compute = (t_c > t_m) & ~dom_comm
        cls = jnp.where(dom_comm, 3,
                        jnp.where(dom_compute, jnp.where(is_mm, 0, 1), 2))
        onehot = (cls[:, None] == jnp.arange(4)[None, :]).astype(jnp.float32)
        return lat + t_op, stalls + onehot * t_op[:, None]

    lat, stalls = jax.lax.fori_loop(0, n_ops, body, (lat0, stalls0))

    macs = sub * sa * sa
    core_area = (AREA_CORE_BASE + AREA_PER_MAC * macs + AREA_PER_VLANE * sub * vw
                 + AREA_PER_SRAM_KB * sram)
    area = (AREA_BASE + cores * core_area + AREA_PER_GBUF_MB * gbuf_mb
            + AREA_PER_CHANNEL * chan + AREA_PER_LINK * links)

    out = jnp.concatenate(
        [lat[:, None], stalls, area[:, None],
         jnp.zeros((bb, 2), jnp.float32)], axis=1)
    out_ref[...] = out


def ppa_eval_fwd(design_values: jnp.ndarray, op_table: jnp.ndarray, *,
                 tp: float = 8.0, block_b: int = 256,
                 interpret: bool = False) -> jnp.ndarray:
    """design_values: (B, 8) decoded physical values (PARAM_NAMES order);
    op_table: (n_ops, 8).  Returns (B, 8): [latency, s0..s3, area, 0, 0]."""
    b = design_values.shape[0]
    block_b = min(block_b, b)
    assert b % block_b == 0, (b, block_b)
    n_ops = op_table.shape[0]
    kernel = functools.partial(_ppa_kernel, n_ops=n_ops, tp=tp)
    return pl.pallas_call(
        kernel,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, 8), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),    # whole op table
        ],
        out_specs=pl.BlockSpec((block_b, 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 8), jnp.float32),
        interpret=interpret,
    )(design_values, op_table.astype(jnp.float32))
