"""Mamba-2 SSD: chunked algorithm vs naive sequential recurrence."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.models.model import _ssm_p
from repro.models.ssm import ssd_decode, ssd_forward


def _naive_ssd(x, p, cfg):
    """Literal per-step recurrence h_t = exp(dt A) h_{t-1} + dt B x_t."""
    B, S, D = x.shape
    di, G, N, H, P = (cfg.d_inner, cfg.ssm_groups, cfg.ssm_state,
                      cfg.ssm_heads, cfg.ssm_headdim)
    cache = {"state": jnp.zeros((B, H, N, P), jnp.float32),
             "conv": jnp.zeros((B, cfg.ssm_conv - 1, cfg.conv_dim),
                               jnp.float32)}
    outs = []
    for t in range(S):
        y, cache = ssd_decode(x[:, t:t + 1], p, cfg, cache)
        outs.append(y)
    return jnp.concatenate(outs, axis=1), cache


@pytest.mark.parametrize("S", [16, 24])   # chunk-aligned and ragged
def test_chunked_matches_sequential(S):
    cfg = dataclasses.replace(get_config("mamba2-130m", smoke=True),
                              dtype=jnp.float32, ssm_chunk=8)
    p = _ssm_p(jax.random.PRNGKey(0), 0, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model),
                          jnp.float32) * 0.5
    y_chunk, cache_c = ssd_forward(x, p, cfg)
    y_seq, cache_s = _naive_ssd(x, p, cfg)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(cache_c["state"]),
                               np.asarray(cache_s["state"]),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(cache_c["conv"]),
                               np.asarray(cache_s["conv"]),
                               rtol=1e-5, atol=1e-5)


def test_decode_continues_forward():
    """ssd_forward cache -> ssd_decode continuation == one longer forward."""
    cfg = dataclasses.replace(get_config("mamba2-130m", smoke=True),
                              dtype=jnp.float32, ssm_chunk=8)
    p = _ssm_p(jax.random.PRNGKey(0), 0, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 17, cfg.d_model),
                          jnp.float32) * 0.5
    y_full, _ = ssd_forward(x, p, cfg)
    y_pre, cache = ssd_forward(x[:, :16], p, cfg)
    y_step, _ = ssd_decode(x[:, 16:17], p, cfg, cache)
    np.testing.assert_allclose(np.asarray(y_step[:, 0]),
                               np.asarray(y_full[:, 16]),
                               rtol=2e-3, atol=2e-3)


def test_state_decay_bounded():
    """|state| stays bounded for long inputs (A < 0 guarantees decay)."""
    cfg = dataclasses.replace(get_config("mamba2-130m", smoke=True),
                              dtype=jnp.float32, ssm_chunk=16)
    p = _ssm_p(jax.random.PRNGKey(0), 0, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, cfg.d_model),
                          jnp.float32)
    _, cache = ssd_forward(x, p, cfg)
    assert bool(jnp.all(jnp.isfinite(cache["state"])))
    assert float(jnp.max(jnp.abs(cache["state"]))) < 1e4
