"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed with jax, so it refuses here what it would
refuse on the chip: the ``ppa_eval`` kernel must lower to a Mosaic
``tpu_custom_call`` at the sweep's chunk width and at campaign batch, and
the zoo portfolio step's count-matrix contractions must carry HIGHEST
precision (a DEFAULT f32 dot may run as one bf16 pass on the chip).

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ppa_eval.kernel import ppa_eval_fwd
from repro.kernels.ppa_eval.ref import op_table
from repro.perfmodel import get_evaluator
from repro.perfmodel.sweep import SweepEngine
from repro.perfmodel.workload import gpt3_layer_decode, gpt3_layer_prefill


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # described-chip executables cannot be read back from a persistent
        # cache on a CPU host; keep them out of any configured one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as exc:
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{exc}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("wl_fn", [gpt3_layer_prefill, gpt3_layer_decode])
@pytest.mark.parametrize("batch", [131_072, 32])
def test_ppa_eval_compiles_for_v5e(one_chip, wl_fn, batch):
    wl = wl_fn()
    tab = op_table(wl)
    dv = jax.ShapeDtypeStruct((batch, 8), jnp.float32, sharding=one_chip)
    ops = jax.ShapeDtypeStruct(tab.shape, jnp.float32, sharding=one_chip)
    fn = jax.jit(functools.partial(ppa_eval_fwd, tp=float(wl.tp),
                                   block_b=256))
    compiled = fn.lower(dv, ops).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_portfolio_step_contractions_are_highest_precision(one_chip):
    eng = SweepEngine(get_evaluator("proxy", suite="zoo"), stall_topk=4)
    st = eng._fresh_state(0)

    def spec(x):
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                    sharding=one_chip)

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    filt = jax.ShapeDtypeStruct((eng._n_groups, eng._pf_rows, 3),
                                jnp.float32, sharding=one_chip)
    text = eng._step.lower(jax.tree.map(spec, st["carry"]), scalar, scalar,
                           filt).as_text()
    dots = re.findall(r"stablehlo\.dot_general.*", text)
    # one latency contraction + one per stall class
    assert len(dots) == 5
    for dot in dots:
        assert "precision = [HIGHEST, HIGHEST]" in dot, dot


def test_deepseek_pair_step_compiles_for_v5e(one_chip):
    """The ``dsv3-sweep`` cell's chunk step: the pair step over DeepSeek-V3's
    58-row table, at the default chunk and ``stall_topk`` 8, fits one v5e
    chip."""
    from repro.perfmodel.evaluator import make_evaluator
    from repro.perfmodel.workload import zoo_suite
    wls, scen = zoo_suite(batch=32, seq=32768, tp=8, out_pos=1024,
                          archs=("deepseek-v3",))
    eng = SweepEngine(make_evaluator(wls, tier="proxy", scenarios=scen),
                      stall_topk=8)
    assert eng.chunk_size == 131_072 and eng.op_rows == 58
    st = eng._fresh_state(0)

    def spec(x):
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                    sharding=one_chip)

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    filt = jax.ShapeDtypeStruct((eng.filter_size, 3), jnp.float32,
                                sharding=one_chip)
    compiled = eng._step.lower(jax.tree.map(spec, st["carry"]), scalar,
                               scalar, filt).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2 ** 30
