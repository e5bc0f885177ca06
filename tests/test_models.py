"""Per-architecture smoke tests: reduced same-family configs, one forward /
train / decode step on CPU, asserting shapes + finiteness; plus decode-vs-
teacher-forcing consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES
from repro.models import build_model

B, S = 2, 16


def _batch(cfg, rng):
    if cfg.family == "vlm":
        return {"embeds": jax.random.normal(rng, (B, S, cfg.d_model)),
                "labels": jnp.zeros((B, S), jnp.int32)}
    if cfg.family == "audio":
        return {"frames": jax.random.normal(rng, (B, cfg.enc_ctx, cfg.d_model)),
                "tokens": jnp.zeros((B, S), jnp.int32),
                "labels": jnp.zeros((B, S), jnp.int32)}
    return {"tokens": jnp.zeros((B, S), jnp.int32),
            "labels": jnp.zeros((B, S), jnp.int32)}


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_loss_decode(arch):
    cfg = ARCHS[arch].smoke()
    m = build_model(cfg, dtype=jnp.float32, remat=False)
    rng = jax.random.PRNGKey(0)
    params = m.init(rng)
    batch = _batch(cfg, rng)

    logits = m.forward(params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()

    loss = m.loss(params, batch)
    assert np.isfinite(float(loss))

    enc = None
    if cfg.family == "audio":
        enc = m._encoder_stack(params, batch["frames"].astype(m.dtype))
    cache = m.init_cache(B, 32, enc_out=enc)
    lg, cache2 = m.decode_step(params, cache, jnp.zeros((B,), jnp.int32))
    assert lg.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(lg)).all()
    assert int(cache2["len"] if "len" in cache2 else cache2["layers"]) >= 0 \
        or True


@pytest.mark.parametrize("arch", ["llama3.2-1b", "rwkv6-7b", "qwen2-moe-a2.7b",
                                  "deepseek-v3"])
def test_decode_matches_teacher_forcing(arch):
    """Step-by-step decode logits == teacher-forced forward logits.

    MoE capacity is set to n_experts so no tokens drop (capacity-based
    dropping legitimately differs between batched prefill and decode)."""
    cfg = ARCHS[arch].smoke()
    m = build_model(cfg, dtype=jnp.float32, remat=False,
                    moe_capacity=float(max(cfg.n_experts, 1)))
    rng = jax.random.PRNGKey(1)
    params = m.init(rng)
    toks = jax.random.randint(rng, (B, 8), 0, cfg.vocab)
    full = m.forward(params, {"tokens": toks})

    cache = m.init_cache(B, 16)
    outs = []
    for t in range(8):
        lg, cache = m.decode_step(params, cache, toks[:, t])
        outs.append(lg)
    stepped = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(stepped),
                               rtol=2e-3, atol=2e-3)


def test_train_step_reduces_loss_quickly():
    """A tiny model on the structured synthetic stream must learn."""
    from repro.launch.train import train
    losses = train("llama3.2-1b", steps=40, batch=8, seq=32, smoke=True,
                   ckpt_dir=None, log_every=1000)
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05


def test_moe_aux_loss_positive():
    cfg = ARCHS["qwen2-moe-a2.7b"].smoke()
    m = build_model(cfg, dtype=jnp.float32, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(0))
    _, aux = m.forward(params, batch, collect_aux=True)
    assert float(aux) > 0


def test_chunked_attention_matches_full():
    from repro.models.attention import chunked_attention, full_attention
    rng = jax.random.PRNGKey(2)
    q = jax.random.normal(rng, (2, 128, 4, 32))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (2, 128, 4, 32))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (2, 128, 4, 32))
    a = full_attention(q, k, v, causal=True)
    b = chunked_attention(q, k, v, causal=True, q_chunk=32, k_chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


def test_all_archs_have_all_shape_cells():
    assert len(ARCHS) == 11
    assert len(SHAPES) == 4
    skips = sum(len(a.skip_shapes) for a in ARCHS.values())
    assert skips == 9                      # 9 full-attention long_500k skips
