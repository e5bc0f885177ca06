"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref

RNG = np.random.default_rng(0)


def _randn(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,hd,bq,bk,causal", [
    (2, 128, 2, 64, 64, 64, True),
    (1, 256, 4, 128, 128, 64, True),
    (2, 64, 2, 32, 32, 32, False),
    (1, 128, 1, 64, 128, 128, True),
])
def test_flash_attention(b, s, h, hd, bq, bk, causal, dtype):
    q, k, v = (_randn((b, s, h, hd), dtype) for _ in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)

    def fl(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)

    ref = attention_ref(fl(q), fl(k), fl(v), causal=causal) \
        .reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,hd,bt", [
    (2, 64, 2, 16, 16), (1, 128, 4, 32, 64), (2, 32, 1, 64, 32),
])
def test_rwkv6_scan(b, t, h, hd, bt, dtype):
    r = _randn((b, t, h, hd), dtype) * 0.5
    k = _randn((b, t, h, hd), dtype) * 0.5
    v = _randn((b, t, h, hd), dtype) * 0.5
    w = jnp.asarray(RNG.uniform(0.3, 0.99, (b, t, h, hd)), dtype)
    u = jnp.asarray(RNG.standard_normal((h, hd)) * 0.1, jnp.float32)
    y = rwkv6_scan(r, k, v, w, u, block_t=bt, interpret=True)

    def fl(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, hd)

    uf = jnp.broadcast_to(u[None], (b, h, hd)).reshape(b * h, 1, hd)
    ref = rwkv6_scan_ref(fl(r), fl(k), fl(v), fl(w), uf) \
        .reshape(b, h, t, hd).transpose(0, 2, 1, 3)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,d,n,bt,bd", [
    (2, 64, 32, 8, 16, 16), (1, 128, 64, 16, 64, 32), (2, 32, 16, 4, 32, 16),
])
def test_ssm_scan(b, t, d, n, bt, bd, dtype):
    u = _randn((b, t, d), dtype)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (b, t, d)), dtype)
    a = -jnp.asarray(RNG.uniform(0.5, 2.0, (d, n)), jnp.float32)
    B = _randn((b, t, n), dtype)
    C = _randn((b, t, n), dtype)
    y = ssm_scan(u, dt, a, B, C, block_t=bt, block_d=bd, interpret=True)
    ref = ssm_scan_ref(u, dt, a, B, C)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_model_uses_chunked_for_long_seq():
    """The auto dispatch threshold guards prefill_32k memory."""
    from repro.models.attention import CHUNKED_THRESHOLD
    assert CHUNKED_THRESHOLD <= 8192
