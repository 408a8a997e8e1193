"""Distributed substrate tests on a forced 8-device host mesh:
MoE EP paths vs local oracle, DDP + int8 gradient compression, sharded
GSPMD train step, elastic checkpoint restore.

NOTE: this file must run in its own pytest process if other tests already
initialized jax with 1 device; we force the device count via conftest
fixtures by spawning where needed. Simpler: the whole test session sets
XLA_FLAGS in conftest BEFORE jax import IF REPRO_TEST_DEVICES is set.
These tests self-skip when only 1 device is available.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

AUTO2 = (AxisType.Auto,) * 2

multi = pytest.mark.skipif(len(jax.devices()) < 8,
                           reason="needs 8 host devices "
                                  "(run tests/run_multidevice.sh)")


def _mesh():
    return jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO2)


@multi
def test_moe_ep_a2a_matches_local():
    from repro.configs.registry import get_config
    from repro.models.model import init_params
    from repro.models.moe import ShardingCtx, moe_ffn, _moe_local
    cfg = get_config("olmoe-1b-7b", smoke=True)
    mesh = _mesh()
    params = init_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model),
                          jnp.float32).astype(cfg.dtype)
    y_local = _moe_local(x, lp, cfg)
    ctx = ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                      seq_sharded=True)
    y_ep = moe_ffn(x, lp, cfg, ctx)
    if cfg.shared_expert:
        from repro.models.layers import swiglu
        y_local = y_local + swiglu(x, lp["shared"])
    # EP capacity is per-shard, local capacity is global: with the smoke
    # configs' capacity_factor=8 nothing drops, so results must agree.
    np.testing.assert_allclose(
        np.asarray(y_ep, np.float32), np.asarray(y_local, np.float32),
        rtol=5e-2, atol=5e-3)


@multi
def test_moe_ep_replicated_matches_local():
    from repro.configs.registry import get_config
    from repro.models.model import init_params
    from repro.models.moe import ShardingCtx, _moe_local, _moe_ep_replicated
    cfg = get_config("olmoe-1b-7b", smoke=True)
    mesh = _mesh()
    params = init_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 1, cfg.d_model),
                          jnp.float32).astype(cfg.dtype)
    ctx = ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                      seq_sharded=False)
    y_rep = _moe_ep_replicated(x, lp, cfg, ctx)
    y_local = _moe_local(x, lp, cfg)
    np.testing.assert_allclose(
        np.asarray(y_rep, np.float32), np.asarray(y_local, np.float32),
        rtol=5e-2, atol=5e-3)


@multi
def test_gspmd_train_step_runs_and_learns():
    from repro.configs.registry import get_config
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import (init_train_state, jit_train_step)
    cfg = get_config("qwen3-14b", smoke=True)
    mesh = _mesh()
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    state_shape = jax.eval_shape(lambda: state)
    batch = {
        "tokens": jnp.ones((8, 32), jnp.int32),
        "labels": jnp.ones((8, 32), jnp.int32),
    }
    batch_shape = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    step = jit_train_step(cfg, OptConfig(lr=1e-2, warmup_steps=1), mesh,
                          state_shape, batch_shape, donate=False)
    from repro.train.train_step import state_shardings
    sh = state_shardings(mesh, state_shape, cfg)
    state = jax.device_put(state, sh)
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # constant batch must be memorized


@multi
def test_ddp_compressed_matches_uncompressed_direction():
    from repro.configs.registry import get_config
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import init_ddp_state, make_ddp_train_step
    cfg = get_config("mamba2-130m", smoke=True)
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    batch = {"tokens": jnp.ones((8, 32), jnp.int32),
             "labels": jnp.ones((8, 32), jnp.int32)}
    opt = OptConfig(lr=1e-2, warmup_steps=1)
    s_c = init_ddp_state(cfg, jax.random.PRNGKey(0))
    s_u = jax.tree.map(lambda x: x, s_c)
    step_c = jax.jit(make_ddp_train_step(cfg, opt, mesh, compress=True))
    step_u = jax.jit(make_ddp_train_step(cfg, opt, mesh, compress=False))
    with jax.set_mesh(mesh):
        losses_c, losses_u = [], []
        for _ in range(6):
            s_c, m_c = step_c(s_c, batch)
            s_u, m_u = step_u(s_u, batch)
            losses_c.append(float(m_c["loss"]))
            losses_u.append(float(m_u["loss"]))
    # both learn the constant batch; compression must not break descent
    assert losses_c[-1] < losses_c[0]
    assert losses_u[-1] < losses_u[0]
    assert abs(losses_c[-1] - losses_u[-1]) < 0.5 * abs(losses_u[0])


@multi
def test_checkpoint_elastic_restore(tmp_path):
    """Save on a (4,2) mesh, restore onto (2,4) and (8,1): elastic."""
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mesh_a = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO2)
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            "step": jnp.int32(7)}
    tree = jax.device_put(tree, {
        "w": NamedSharding(mesh_a, P("data", "model")),
        "step": NamedSharding(mesh_a, P())})
    mgr.save(100, tree)
    assert mgr.latest_step() == 100
    mesh_b = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO2)
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    sh_b = {"w": NamedSharding(mesh_b, P("data", "model")),
            "step": NamedSharding(mesh_b, P())}
    restored = mgr.restore(100, target, sh_b)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(64).reshape(8, 8))
    assert int(restored["step"]) == 7


@multi
def test_checkpoint_async_and_gc(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.ones((4, 4))}
    for s in (1, 2, 3, 4):
        mgr.save_async(s, jax.tree.map(lambda x: x * s, tree))
    mgr.wait()
    assert mgr.latest_step() == 4
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps == [3, 4]          # gc kept last 2
    restored = mgr.restore(4, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree))
    np.testing.assert_allclose(np.asarray(restored["w"]), 4.0)


@multi
def test_compressed_psum_accuracy():
    from repro.train.grad_compress import compressed_psum_mean
    from jax import shard_map
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4096), jnp.float32)

    def body(xl):
        m, err = compressed_psum_mean(xl[0], "data")
        return m[None], err[None]

    mean_c, err = shard_map(body, mesh=mesh, in_specs=P("data", None),
                            out_specs=P("data", None))(x)
    want = jnp.mean(x, axis=0)
    got = np.asarray(mean_c[0])
    # int8 block quantization: ~1% of the per-block dynamic range
    scale = float(jnp.max(jnp.abs(x))) / 127
    assert np.max(np.abs(got - np.asarray(want))) < 8 * scale
