"""LM serving: prefill + greedy/temperature decode loop with the
layer-stacked KV(/SSM) cache."""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.configs import ModelConfig
from repro.models.model import decode_step, prefill

Array = jax.Array


def generate(params: Any, cfg: ModelConfig, prompt: Array,
             max_new_tokens: int = 32, temperature: float = 0.0,
             key: Optional[Array] = None, ctx=None,
             enc_input: Optional[Array] = None) -> Array:
    """Greedy/temperature decoding. prompt: (B, S) -> (B, S + new)."""
    B, S = prompt.shape
    batch = {"tokens": prompt}
    if cfg.encoder_layers:
        batch["enc_input"] = enc_input
    logits, cache = prefill(params, batch, cfg,
                            max_len=S + max_new_tokens, ctx=ctx)
    enc = None
    if cfg.encoder_layers:
        from repro.models.model import encode
        enc = encode(params, enc_input, cfg, ctx)

    step_fn = jax.jit(partial(decode_step, cfg=cfg, ctx=ctx))
    toks = [prompt]
    cur = _sample(logits[:, -1], temperature, key)
    for t in range(max_new_tokens):
        toks.append(cur)
        if t == max_new_tokens - 1:
            break
        logits, cache = (step_fn(params, cur, cache, enc=enc)
                         if enc is not None else
                         step_fn(params, cur, cache))
        if key is not None:
            key, _ = jax.random.split(key)
        cur = _sample(logits[:, -1], temperature, key)
    return jnp.concatenate(toks, axis=1)


def _sample(logits: Array, temperature: float,
            key: Optional[Array]) -> Array:
    if temperature <= 0.0 or key is None:
        return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1)[:, None].astype(jnp.int32)
