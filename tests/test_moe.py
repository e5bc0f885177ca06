"""MoE dispatch invariants (hypothesis property tests) + shard_map parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.moe import init_moe, moe_block


@given(st.integers(0, 10_000), st.sampled_from([1, 2, 4]),
       st.sampled_from([4, 8]), st.sampled_from([1, 2]))
@settings(max_examples=15, deadline=None)
def test_moe_capacity_conservation(seed, top_k, n_experts, groups):
    """With capacity >= T*k (no drops), every (token, k) assignment lands in
    the buffer exactly once: the output equals the explicit dense mixture."""
    rng = np.random.default_rng(seed)
    b, s, d, f = 2, 4, 8, 16
    params = init_moe(jax.random.PRNGKey(seed % 1000), d, f, n_experts, 0, 0,
                      dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
    out, aux = moe_block(params, x, n_experts=n_experts, top_k=top_k,
                         capacity_factor=float(n_experts), n_groups=groups)

    # explicit dense mixture oracle
    xf = x.reshape(-1, d)
    logits = xf @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gv, gi = jax.lax.top_k(probs, top_k)
    gv = gv / gv.sum(-1, keepdims=True)
    ys = []
    for e in range(n_experts):
        g = xf @ params["w_gate"][e]
        u = xf @ params["w_up"][e]
        h = jax.nn.silu(g) * u
        ys.append(h @ params["w_down"][e])
    ys = jnp.stack(ys, axis=1)                       # (T, E, d)
    w = jnp.zeros((xf.shape[0], n_experts))
    for k in range(top_k):
        w = w.at[jnp.arange(xf.shape[0]), gi[:, k]].add(gv[:, k])
    ref = (ys * w[..., None]).sum(axis=1).reshape(b, s, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert float(aux) >= 0


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_moe_group_invariance(seed):
    """With ample capacity the group count must not change the output."""
    rng = np.random.default_rng(seed)
    b, s, d, f, E, k = 2, 8, 8, 16, 4, 2
    params = init_moe(jax.random.PRNGKey(seed % 997), d, f, E, 0, 0,
                      dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
    outs = [moe_block(params, x, n_experts=E, top_k=k,
                      capacity_factor=float(E), n_groups=g)[0]
            for g in (1, 2, 4)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=2e-4, atol=2e-4)


def test_expert_pad_never_selected():
    """Padding experts receive zero tokens (router has no logit for them)."""
    params = init_moe(jax.random.PRNGKey(0), 8, 16, n_experts=6, n_shared=0,
                      shared_ff=0, dtype=jnp.float32, expert_pad=2)
    assert params["w_up"].shape[0] == 8
    assert params["router"].shape[1] == 6
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 8))
    out, _ = moe_block(params, x, n_experts=6, top_k=2,
                       capacity_factor=6.0)
    assert np.isfinite(np.asarray(out)).all()
