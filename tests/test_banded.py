"""Banded sliding-window attention == masked full attention (§Perf)."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType

from repro.configs.registry import get_config
from repro.models.attention import attention, banded_attention
from repro.models.model import forward, init_params, layer_segments


def _p(cfg, key):
    from repro.models.model import _attn_p
    return jax.tree.map(lambda x: x, _attn_p(key, 0, cfg))


@pytest.mark.parametrize("n_meta", [0, 8])
def test_banded_matches_masked(n_meta):
    cfg = dataclasses.replace(
        get_config("hymba-1.5b", smoke=True), meta_tokens=n_meta,
        dtype=jnp.float32)
    p = _p(cfg, jax.random.PRNGKey(0))
    B, S = 2, 64 + n_meta
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    w = cfg.sliding_window  # 16 in smoke
    ref = attention(x, p, cfg, pos, window=w, n_meta=n_meta)
    band = banded_attention(x, p, cfg, pos, window=w, n_meta=n_meta)
    np.testing.assert_allclose(np.asarray(band), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_banded_non_divisible_seq():
    cfg = dataclasses.replace(get_config("hymba-1.5b", smoke=True),
                              meta_tokens=0, dtype=jnp.float32)
    p = _p(cfg, jax.random.PRNGKey(0))
    B, S = 1, 53   # not a multiple of window=16
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                          jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ref = attention(x, p, cfg, pos, window=cfg.sliding_window)
    band = banded_attention(x, p, cfg, pos, window=cfg.sliding_window)
    np.testing.assert_allclose(np.asarray(band), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_layer_segments():
    cfg = get_config("hymba-1.5b")   # global at 0, 15, 31 of 32
    segs = layer_segments(cfg)
    assert segs[0] == (0, 1, "global")
    assert segs[1] == (1, 15, "window")
    assert segs[2] == (15, 16, "global")
    assert segs[-1] == (31, 32, "global")
    assert sum(b - a for a, b, _ in segs) == cfg.n_layers


def test_banded_forward_matches_baseline_forward():
    """Full hymba smoke forward: banded segmented stack == baseline."""
    from repro.models.moe import ShardingCtx
    cfg = get_config("hymba-1.5b", smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                          0, cfg.vocab)}
    base = forward(params, batch, cfg, None)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    ctx = ShardingCtx(mesh=mesh, dp_axes=("data",), banded=True)
    band = forward(params, batch, cfg, ctx)
    np.testing.assert_allclose(
        np.asarray(band, np.float32), np.asarray(base, np.float32),
        rtol=3e-2, atol=3e-2)
