"""Per-architecture smoke tests: reduced configs, one forward + one grad
step + one prefill/decode roundtrip on CPU. Shape + finiteness asserts.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCH_IDS, get_config
from repro.models import (decode_step, forward, init_cache, init_params,
                          loss_fn, prefill)

B, S = 2, 32


def _batch(cfg, key):
    ks = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab),
    }
    if cfg.mrope:
        pos = jnp.broadcast_to(jnp.arange(S)[None, :, None], (B, S, 3))
        batch["positions"] = pos.astype(jnp.int32)
    if cfg.encoder_layers:
        batch["enc_input"] = jax.random.normal(
            ks[2], (B, cfg.encoder_ctx, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits = forward(params, batch, cfg)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_grad_finite(arch):
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg)
    assert bool(jnp.isfinite(loss))
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
               for g in flat)
    # embedding must receive gradient
    assert float(jnp.max(jnp.abs(grads["embed"]))) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch):
    """Teacher-forced decode after prefill must match full forward logits."""
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    full = forward(params, batch, cfg).astype(jnp.float32)

    pre = {k: v for k, v in batch.items() if k != "labels"}
    pre["tokens"] = batch["tokens"][:, : S - 4]
    if cfg.mrope:
        pre["positions"] = batch["positions"][:, : S - 4]
    logits_last, cache = prefill(params, pre, cfg, max_len=S + 8)
    np.testing.assert_allclose(
        np.asarray(logits_last[:, 0]), np.asarray(full[:, S - 5]),
        rtol=2e-2, atol=2e-2)

    enc = None
    if cfg.encoder_layers:
        from repro.models.model import encode
        enc = encode(params, batch["enc_input"], cfg)
    for t in range(S - 4, S):
        step_logits, cache = decode_step(
            params, batch["tokens"][:, t:t + 1], cache, cfg, enc=enc)
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]), np.asarray(full[:, t]),
            rtol=5e-2, atol=5e-2)


def test_param_count_matches_init():
    """Analytic 6ND-side param counts equal the real pytree sizes."""
    for arch in ARCH_IDS:
        cfg = get_config(arch, smoke=True)
        params = init_params(cfg, jax.random.PRNGKey(0))
        n_real = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        n_calc = cfg.param_count()
        assert n_real == n_calc, (arch, n_real, n_calc)
