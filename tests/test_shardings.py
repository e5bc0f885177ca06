"""Sharding-rule tests: every sharded dim of every (arch x shape) spec must
divide the production mesh axes exactly (jax rejects uneven arg shardings —
these tests catch rule regressions without needing 512 devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES
from repro.launch import shardings as SH
from repro.models import build_model

MODEL = 16
DATA = {"single": 16, "multi": 32}
DP = {"single": ("data",), "multi": ("pod", "data")}


def _axis_size(ax, mesh_kind):
    if ax is None:
        return 1
    axes = (ax,) if isinstance(ax, str) else tuple(ax)
    n = 1
    for a in axes:
        n *= MODEL if a == "model" else (2 if a == "pod" else 16)
    return n


def _check_tree(spec_tree, shape_tree, tag, mesh_kind):
    specs = jax.tree_util.tree_leaves_with_path(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    shapes = jax.tree_util.tree_leaves(shape_tree)
    assert len(specs) == len(shapes), tag
    for (path, spec), leaf in zip(specs, shapes):
        shp = tuple(getattr(leaf, "shape", ()))
        parts = list(spec)
        assert len(parts) <= len(shp), (tag, path, spec, shp)
        for dim, ax in zip(shp, parts):
            size = _axis_size(ax, mesh_kind)
            assert dim % size == 0, \
                f"{tag} {jax.tree_util.keystr(path)}: dim {dim} % {size} != 0 ({spec}, {shp})"


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_specs_divisible(arch):
    cfg = ARCHS[arch]
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    spec = SH.param_specs(params, MODEL)
    _check_tree(spec, params, arch, "single")


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("mesh_kind", ["single", "multi"])
def test_cache_specs_divisible(arch, mesh_kind):
    import functools
    cfg = ARCHS[arch]
    model = build_model(cfg)
    for sname, shape in SHAPES.items():
        if shape.mode != "decode" or sname in cfg.skip_shapes:
            continue
        spec = SH.cache_spec(cfg, shape, DP[mesh_kind], DATA[mesh_kind], MODEL)
        fn = functools.partial(model.init_cache, shape.global_batch,
                               shape.seq_len)
        if cfg.family == "audio":
            enc = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.enc_ctx, cfg.d_model), jnp.bfloat16)
            cache = jax.eval_shape(lambda e: fn(enc_out=e), enc)
        else:
            cache = jax.eval_shape(fn)
        _check_tree(spec, cache, f"{arch}/{sname}", mesh_kind)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_batch_specs_divisible(arch):
    cfg = ARCHS[arch]
    for sname, shape in SHAPES.items():
        if sname in cfg.skip_shapes:
            continue
        for mesh_kind in ("single", "multi"):
            spec = SH.batch_spec(cfg, shape, DP[mesh_kind], DATA[mesh_kind])
            b = shape.global_batch
            bspec = spec["tokens"][0]
            size = _axis_size(bspec, mesh_kind)
            assert b % size == 0, (arch, sname, mesh_kind)


def test_moe_ep_rules():
    """qwen2-moe pads 60 -> 64 experts so EP applies on a 16-mesh (perf
    iteration); arctic (128) EP-shards natively.  A hypothetical unpadded
    60-expert stack falls back to TP on the expert FF dim."""
    import dataclasses
    qcfg, acfg = ARCHS["qwen2-moe-a2.7b"], ARCHS["arctic-480b"]
    qm = jax.eval_shape(build_model(qcfg).init, jax.random.key(0))
    am = jax.eval_shape(build_model(acfg).init, jax.random.key(0))
    qs = SH.param_specs(qm, MODEL)["layers"]["moe"]["w_up"]
    as_ = SH.param_specs(am, MODEL)["layers"]["moe"]["w_up"]
    assert qs[1] == "model"                        # EP via padding (60 -> 64)
    assert as_[1] == "model"                       # EP natively
    raw = dataclasses.replace(qcfg, expert_pad=0)
    rm = jax.eval_shape(build_model(raw).init, jax.random.key(0))
    rs = SH.param_specs(rm, MODEL)["layers"]["moe"]["w_up"]
    assert rs[1] is None and rs[-1] == "model"     # fallback: TP on ff dim


def test_whisper_vocab_fallback():
    """51865 doesn't divide 16: embed falls back to d_model sharding."""
    cfg = ARCHS["whisper-medium"]
    params = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    spec = SH.param_specs(params, MODEL)
    assert spec["embed"] == P(None, "model")


def test_gqa_cache_fallback():
    """kv=8 archs shard the KV sequence (flash-decode), kv>=16 shard heads."""
    nemo = SH.cache_spec(ARCHS["mistral-nemo-12b"], SHAPES["decode_32k"],
                         ("data",), 16, MODEL)
    cq = SH.cache_spec(ARCHS["codeqwen1.5-7b"], SHAPES["decode_32k"],
                       ("data",), 16, MODEL)
    assert nemo["k"][2] in ("model", ("model",)) and nemo["k"][3] is None
    assert cq["k"][3] == "model" and cq["k"][2] is None


def test_mla_rules():
    """DeepSeek-V3's latent attention: the latent down-projections and the
    latent cache are replicated over 'model' (every rank needs the whole
    latent), the up-projections split the heads, experts shard by EP."""
    cfg = ARCHS["deepseek-v3"]
    params = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    spec = SH.param_specs(params, MODEL)
    attn = spec["layers"]["attn"]
    assert attn["q_a"]["w"] == P(None, None, None)
    assert attn["kv_a"]["w"] == P(None, None, None)
    assert attn["q_b"]["w"] == P(None, None, "model")
    assert attn["kv_b"]["w"] == P(None, None, "model")
    assert attn["o"]["w"] == P(None, "model", None)
    assert spec["layers"]["moe"]["w_up"][1] == "model"
    assert spec["dense_layers"]["mlp"]["w_up"] == P(None, None, "model")
    cache = SH.cache_spec(cfg, SHAPES["decode_32k"], ("data",), 16, MODEL)
    assert cache["latent"] == cache["latent_dense"] == P(
        None, ("data",), None, None)
