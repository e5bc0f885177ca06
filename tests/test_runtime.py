"""Substrate tests: optimizer, compression, data, checkpointing, fault
tolerance, elastic planning."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.optim import (AdamWConfig, adamw_init, adamw_update, cosine_lr,
                         compress_grads, decompress_grads, ef_init)
from repro.data import SyntheticLMDataset, make_batch_iter
from repro.checkpoint import (save_checkpoint, restore_checkpoint,
                              AsyncCheckpointer, latest_step)
from repro.runtime import (Heartbeat, PoolPlan, RetryPolicy, run_with_retries,
                           StragglerMonitor, plan_elastic_mesh,
                           plan_elastic_pool)


# ----------------------------------------------------------------- optimizer
def test_adamw_minimizes_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                      total_steps=200)
    params = {"x": jnp.ones((4,)) * 5.0}
    opt = adamw_init(params)
    loss = lambda p: jnp.sum(p["x"] ** 2)
    for _ in range(150):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw_update(cfg, g, opt, params)
    assert float(loss(params)) < 0.1


def test_cosine_lr_schedule():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100)
    assert float(cosine_lr(cfg, jnp.asarray(0))) == pytest.approx(0.0)
    assert float(cosine_lr(cfg, jnp.asarray(10))) == pytest.approx(1.0)
    assert float(cosine_lr(cfg, jnp.asarray(100))) == pytest.approx(0.0, abs=1e-6)


def test_grad_clipping_bounds_update():
    cfg = AdamWConfig(lr=1e-2, clip_norm=1.0, weight_decay=0.0)
    params = {"x": jnp.zeros((3,))}
    opt = adamw_init(params)
    huge = {"x": jnp.ones((3,)) * 1e6}
    _, _, m = adamw_update(cfg, huge, opt, params)
    assert float(m["grad_norm"]) > 1e5          # reported pre-clip


# ----------------------------------------------------------------- compression
@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_compression_error_feedback_is_unbiased_over_time(seed):
    """Repeatedly compressing the SAME gradient with error feedback must
    converge so the accumulated applied update matches the true sum."""
    rng = np.random.default_rng(seed)
    g = {"w": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)}
    ef = ef_init(g)
    applied = jnp.zeros_like(g["w"])
    n = 20
    for _ in range(n):
        comp, ef = compress_grads(g, ef)
        applied = applied + decompress_grads(comp, g)["w"]
    true = g["w"] * n
    # residual is bounded by one quantization step, not growing with n
    err = np.abs(np.asarray(applied - true)).max()
    scale = float(jnp.abs(g["w"]).max()) / 127.0
    assert err <= 2 * scale + 1e-6


def test_compression_ratio():
    g = {"w": jnp.ones((64, 64), jnp.float32)}
    comp, _ = compress_grads(g, ef_init(g))
    raw = 64 * 64 * 4
    sent = comp["w"]["q"].size + comp["w"]["scale"].size * 4
    assert sent < raw / 3.5                     # ~4x wire reduction


# ----------------------------------------------------------------- data
def test_dataset_deterministic_replay():
    ds = SyntheticLMDataset(vocab=256, seq_len=32, global_batch=4, seed=1)
    a = ds.batch_at(7)
    b = ds.batch_at(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = ds.batch_at(8)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_prefetch_iterator_order():
    ds = SyntheticLMDataset(vocab=64, seq_len=8, global_batch=2)
    it = make_batch_iter(ds, start_step=3, num_steps=5)
    got = [b["tokens"] for b in it]
    assert len(got) == 5
    np.testing.assert_array_equal(got[0], ds.batch_at(3)["tokens"])
    np.testing.assert_array_equal(got[4], ds.batch_at(7)["tokens"])


# ----------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((2,), jnp.int32)}}
    save_checkpoint(str(tmp_path), 5, tree)
    assert latest_step(str(tmp_path)) == 5
    back = restore_checkpoint(str(tmp_path), 5, tree)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(back["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_async_checkpointer_gc(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    tree = {"x": jnp.ones((4,))}
    for s in (1, 2, 3, 4):
        ck.save(s, tree)
    ck.wait()
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path))
    assert steps == [3, 4]


def test_restore_with_different_sharding(tmp_path):
    """Elastic-restart path: restore onto explicit (single-device) sharding."""
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    save_checkpoint(str(tmp_path), 1, tree)
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    back = restore_checkpoint(str(tmp_path), 1, tree, shardings={"w": sh})
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(tree["w"]))


# ----------------------------------------------------------------- fault
def test_retries_then_success():
    calls = {"n": 0, "restores": 0}

    def step():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("simulated preemption")
        return "ok"

    out = run_with_retries(step, lambda a: calls.__setitem__(
        "restores", calls["restores"] + 1), RetryPolicy(max_retries=3))
    assert out == "ok"
    assert calls["restores"] == 2


def test_retries_exhausted():
    def step():
        raise RuntimeError("dead host")

    with pytest.raises(RuntimeError, match="after 2 retries"):
        run_with_retries(step, lambda a: None, RetryPolicy(max_retries=2))


def test_retry_policy_fresh_default_per_call():
    """run_with_retries(policy=None) builds a NEW default policy per call —
    the old module-level default instance was shared by every caller."""
    import repro.runtime.fault as fault_mod
    import inspect
    sig = inspect.signature(run_with_retries)
    assert sig.parameters["policy"].default is None
    assert isinstance(fault_mod.RetryPolicy(), RetryPolicy)
    # and None still retries with the default budget
    calls = {"n": 0}

    def step():
        calls["n"] += 1
        if calls["n"] < 2:
            raise RuntimeError("flake")
        return calls["n"]

    assert run_with_retries(step, lambda a: None) == 2


def test_retry_policy_backoff_capped_exponential():
    p = RetryPolicy(backoff_s=0.5, max_backoff_s=3.0, jitter=0.0)
    assert p.delay(0) == pytest.approx(0.5)
    assert p.delay(1) == pytest.approx(1.0)
    assert p.delay(2) == pytest.approx(2.0)
    assert p.delay(3) == pytest.approx(3.0)      # capped
    assert p.delay(10) == pytest.approx(3.0)
    assert RetryPolicy(backoff_s=0.0).delay(5) == 0.0


def test_retry_policy_jitter_spreads_and_bounds():
    import random as _random
    p = RetryPolicy(backoff_s=1.0, max_backoff_s=8.0, jitter=0.25)
    rng = _random.Random(0)
    ds = [p.delay(1, rng=rng) for _ in range(200)]
    assert all(2.0 * 0.75 <= d <= 2.0 * 1.25 for d in ds)
    assert len({round(d, 6) for d in ds}) > 50    # actually randomized


def test_retry_policy_retryable_is_typed_tuple():
    p = RetryPolicy()
    assert isinstance(p.retryable, tuple)
    assert all(isinstance(t, type) for t in p.retryable)
    # non-retryable exceptions propagate unchanged
    with pytest.raises(KeyError):
        run_with_retries(lambda: (_ for _ in ()).throw(KeyError("x")),
                         lambda a: None,
                         RetryPolicy(retryable=(RuntimeError,)))
    # frozen: policies are shareable without aliasing state
    with pytest.raises(Exception):
        p.max_retries = 99


def test_heartbeat_file_liveness(tmp_path):
    path = str(tmp_path / "hb")
    hb = Heartbeat(path, interval_s=0.0)
    assert not Heartbeat.is_alive(path, timeout_s=10.0)   # no file yet
    hb.beat(step=3)
    assert Heartbeat.is_alive(path, timeout_s=10.0)
    assert not Heartbeat.is_alive(path, timeout_s=0.0)    # already expired


def test_straggler_monitor():
    mon = StragglerMonitor(window=16, threshold=2.0)
    for i in range(12):
        assert not mon.record(i, 0.1)
    assert mon.record(12, 0.5)             # 5x the median
    assert len(mon.flagged) == 1


# ----------------------------------------------------------------- elastic
def test_elastic_plan_shrinks_data_axis():
    p = plan_elastic_mesh(512, model_axis=16)
    assert p.shape == (2, 16, 16)
    p = plan_elastic_mesh(496, model_axis=16)   # lost one host of 16
    assert p.dp_degree == 31 - 0                # 496 // 16
    assert p.devices_used == 496
    p = plan_elastic_mesh(8, model_axis=16)
    assert p is None


def test_elastic_mesh_edge_cases():
    # fewer devices than one model group -> no plan at all
    assert plan_elastic_mesh(15, model_axis=16) is None
    # exactly one group: single pod, DP degree 1
    p = plan_elastic_mesh(16, model_axis=16)
    assert p.shape == (1, 16) and p.dp_degree == 1 and p.devices_used == 16
    # odd group count (5 groups of 16): cannot split into 2 balanced pods
    p = plan_elastic_mesh(80, model_axis=16)
    assert p.shape == (5, 16) and p.axes == ("data", "model")
    assert "single pod" in p.note
    # even group count >= 4 prefers two pods
    p = plan_elastic_mesh(96, model_axis=16)    # 6 groups -> 2 pods x 3
    assert p.shape == (2, 3, 16) and p.dp_degree == 6
    # pod preference off: stays a single flat mesh
    p = plan_elastic_mesh(96, model_axis=16, prefer_pods=False)
    assert p.shape == (6, 16)
    # leftover devices are dropped, not oversubscribed
    p = plan_elastic_mesh(50, model_axis=16)
    assert p.devices_used == 48 and p.dp_degree == 3


def test_elastic_pool_plan():
    # no backlog: shrink to the survivors, never below min_workers
    p = plan_elastic_pool(3, 0, min_workers=1, max_workers=8)
    assert isinstance(p, PoolPlan)
    assert p.workers == 3 and not p.grow and "hold" in p.note
    p = plan_elastic_pool(0, 0, min_workers=2, max_workers=8)
    assert p.workers == 2                        # clamped up to min
    # backlog pressure grows toward the cap
    p = plan_elastic_pool(2, 12, max_workers=8, target_queue=2.0)
    assert p.workers == 6 and p.grow and "grow" in p.note
    p = plan_elastic_pool(2, 100, max_workers=8)
    assert p.workers == 8                        # clamped to max
    # light backlog after worker loss: shrink instead of oversubscribing
    p = plan_elastic_pool(6, 2, min_workers=1, max_workers=8)
    assert p.workers == 1 and "shrink" in p.note
    with pytest.raises(ValueError, match="min_workers"):
        plan_elastic_pool(2, 0, min_workers=0)
    with pytest.raises(ValueError, match="max_workers"):
        plan_elastic_pool(2, 0, min_workers=4, max_workers=2)
