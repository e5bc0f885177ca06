"""DeepSeek-V3 (family ``mla_moe``) through the normal path.

- The plain model's absorbed latent-attention decode equals decompressed
  attention over the same cache (float32, highest matmul precision).
- The operator graph of ``from_arch`` counts the matmul FLOPs of the plain
  model's forward pass and decode step, at the same shapes.
- The closed form for the distinct experts a rank serves agrees with a
  seeded Monte-Carlo over the model's own group-limited routing.
- The evaluator built by ``zoo_suite`` drives a small-space sweep and a
  budget-5 campaign at both tiers.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import build_model
from repro.models import mla as MLA
from repro.models.moe import group_limited_route
from repro.perfmodel import workload as W

CFG = ARCHS["deepseek-v3"].smoke()        # 1 dense + 1 MoE layer, all MLA
B, S, KV = 2, 16, 24


def test_absorbed_decode_equals_decompressed_attention():
    cfg = CFG
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    p = MLA.init_mla(k[0], cfg, jnp.float32)
    H = cfg.n_heads
    q_nope = jax.random.normal(k[1], (B, 1, H, cfg.qk_nope_head_dim))
    q_pe = jax.random.normal(k[2], (B, 1, H, cfg.qk_rope_head_dim))
    lat = jax.random.normal(
        k[3], (B, KV, cfg.kv_lora_rank + cfg.qk_rope_head_dim))
    mask = (jnp.arange(KV) < 17)[None, :]          # 17 of 24 slots filled
    with jax.default_matmul_precision("highest"):
        absorbed = MLA.attend_absorbed(p, q_nope, q_pe, lat, mask, cfg)
        full = MLA.attend_decompressed(p, q_nope, q_pe, lat, mask, cfg)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(full),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------- FLOP count
def _dot_flops(jaxpr) -> float:
    """Matmul FLOPs of a jaxpr: 2 x output elements x contracted size of
    every ``dot_general``, a scan's body counted ``length`` times."""
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += (2.0 * math.prod(eqn.outvars[0].aval.shape)
                      * math.prod(lhs[i] for i in lc))
        mult = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += mult * _dot_flops(inner)
    return total


def _graph_flops(wl: W.Workload) -> float:
    return sum(o.flops * o.count for o in wl.ops if o.kind == W.MATMUL)


def _capacity_rows(tokens: int, cfg, capacity: float) -> int:
    """Rows of the model's dense dispatch buffers: every capacity slot of
    every expert is computed, routed or not."""
    cap = max(1, math.ceil(tokens * cfg.top_k / cfg.n_experts * capacity))
    return cfg.n_experts * cap


@pytest.mark.parametrize("decode", [False, True])
def test_graph_flops_equal_the_plain_model(decode):
    """``from_arch`` at tp 1 counts the matmul FLOPs of the plain model:
    one MLA layer with a dense FFN, one with the expert layer, the logits.

    Departure, written into the comparison: the model's capacity-bounded
    dispatch computes ``E * C`` buffer rows, ``C = ceil(T * k / E *
    capacity)``, where the graph counts the ``T * k`` routed rows; the
    difference (no rows at prefill here, 4 at decode) is added to the
    graph.  Decode attends over every slot of the model's cache, so the
    graph's ``kv_len`` is the cache length.  Tolerance: float rounding of
    the distinct-expert count (rtol 1e-9)."""
    cfg, capacity = CFG, 1.0
    m = build_model(cfg, dtype=jnp.float32, remat=False,
                    moe_capacity=capacity)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    if decode:
        cache = jax.eval_shape(lambda: m.init_cache(B, KV))
        toks = jax.ShapeDtypeStruct((B,), jnp.int32)
        jaxpr = jax.make_jaxpr(m.decode_step)(params, cache, toks)
        wl = W.from_arch(cfg, B, KV, tp=1, decode=True, kv_len=KV)
        tokens = B
    else:
        toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
        jaxpr = jax.make_jaxpr(m.forward)(params, {"tokens": toks})
        wl = W.from_arch(cfg, B, S, tp=1, decode=False)
        tokens = B * S
    n_moe = cfg.n_layers - cfg.first_k_dense
    extra_rows = _capacity_rows(tokens, cfg, capacity) - tokens * cfg.top_k
    assert extra_rows == (4 if decode else 0)
    extra = extra_rows * 2.0 * 3 * cfg.d_model * cfg.expert_ff * n_moe
    assert _dot_flops(jaxpr.jaxpr) == pytest.approx(
        _graph_flops(wl) + extra, rel=1e-9)


def test_latent_cache_read_is_the_model_cache():
    """The decode graph reads the whole latent cache once a layer, whole
    on every rank: its bytes are the model's fp16 cache at the same shape,
    divided by nothing at tp 8."""
    cfg = ARCHS["deepseek-v3"]
    m = build_model(cfg, dtype=jnp.float16)
    cache = jax.eval_shape(lambda: m.init_cache(B, KV))
    per_layer = (cache["latent"].size * 2) / cache["latent"].shape[0]
    wl = W.from_arch(cfg, B, KV, tp=8, decode=True, kv_len=KV)
    read, = [o for o in wl.ops if o.name == "mla.latent_read"]
    assert read.bytes == per_layer and read.count == cfg.n_layers
    assert cache["latent_dense"].shape[0] == cfg.first_k_dense


# ---------------------------------------------------- distinct experts
@pytest.mark.parametrize("M,trials", [(32, 2000), (1024, 64)])
def test_distinct_experts_closed_form_matches_monte_carlo(M, trials):
    """Uniform group-limited routing at DeepSeek-V3's 256 experts, 8 groups,
    top-4 groups, top-8 experts: the model's router on seeded iid scores.
    Expert-parallel rank r holds group r's 32 experts."""
    E, G, TG, K, TP = 256, 8, 4, 8, 8
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(M),
                                              (trials, M, E)))
    idx, _ = group_limited_route(scores, jnp.zeros(E), n_group=G,
                                 topk_group=TG, top_k=K)
    idx = np.asarray(idx)
    groups = idx // (E // G)
    assert max(len(set(row)) for row in groups.reshape(-1, K)) <= TG
    hit = np.zeros((trials, E), bool)
    hit[np.arange(trials)[:, None], idx.reshape(trials, -1)] = True
    mc = hit.reshape(trials, TP, E // TP).sum(-1).mean()
    assert mc == pytest.approx(W.distinct_experts(M, E, K, TP), rel=0.01)


# ----------------------------------------------------- the normal path
@pytest.fixture(scope="module")
def suite():
    return W.zoo_suite(batch=32, seq=32768, tp=8, out_pos=1024,
                       archs=("deepseek-v3",))


@pytest.mark.parametrize("tier", ["proxy", "target"])
def test_sweep_and_campaign_on_the_deepseek_evaluator(suite, tier):
    from repro.core.campaign import CampaignRunner
    from repro.core.pareto import pareto_mask
    from repro.perfmodel.designspace import SPACE
    from repro.perfmodel.evaluator import make_evaluator
    from repro.perfmodel.sweep import SweepEngine
    wls, scen = suite
    ev = make_evaluator(wls, tier=tier, scenarios=scen)
    assert ev.workloads[:2] == ("deepseek-v3:prefill", "deepseek-v3:decode")
    eng = SweepEngine(ev, stall_topk=2, chunk_size=4096)
    assert eng.op_rows == len(wls["deepseek-v3:prefill"].ops) + len(
        wls["deepseek-v3:decode"].ops)
    n = 12_000
    res = eng.run(0, n)
    assert res.n_evaluated == n
    ys = ev.objectives(SPACE.flat_to_idx(np.arange(n)))
    np.testing.assert_array_equal(np.sort(res.pareto_ids),
                                  np.flatnonzero(pareto_mask(ys)))
    runner = CampaignRunner(ev, proxy=ev, seed=0)
    camp = runner.run(budget=5, sweep=res)
    assert len(camp.samples) == 5
