"""Pallas TPU kernel: flash attention (forward) for the LM substrate.

This is the kernel §Perf identified as the remaining lever for every
memory-bound train/prefill cell: the XLA-level attention materializes
S x S score tensors in HBM; this kernel keeps (bq x bk) score TILES in
VMEM with the online-softmax recurrence, so HBM traffic is O(S*hd), not
O(S^2) -- the same BRAM-residency insight the paper's FPGA pipeline uses
for HOG cells (DESIGN.md §2), applied to attention.

Layout: q (B, H, S, hd); k, v (B, K, S, hd) with H = K*rep (GQA: the kv
block index maps h -> h // rep, so KV heads are never materialized
repeated). Grid (B*H, nQ, nK) with the K axis innermost: the output
block (bq, hd) is revisited across the K sweep while the running
(max, sum, acc) state lives in VMEM scratch.

Causal masking skips fully-masked K blocks (no compute, no traffic).
Validated against kernels/ref.py (pure-jnp oracle) in interpret mode;
sized for v5e VMEM: default (bq, bk) = (512, 512), fp32 accumulators.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, nk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    q0 = i * bq
    k0 = j * bk

    def compute():
        q = q_ref[0]                                   # (bq, hd)
        k = k_ref[0]                                   # (bk, hd)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (bq, bk)
        if causal:
            qi = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            ki = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(ki <= qi, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])                     # (bq, bk)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    if causal:
        # skip K blocks entirely above the diagonal band
        pl.when(k0 <= q0 + bq - 1)(compute)
    else:
        compute()

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 512,
                    block_k: int = 512,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, S, hd); k, v: (B, K, S, hd), H % K == 0 -> (B, H, S, hd)."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    rep = H // K
    bq = min(block_q, S)
    bk = min(block_k, S)
    nq = cdiv(S, bq)
    nk = cdiv(S, bk)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = 1.0 / math.sqrt(hd)

    grid = (B * H, nq, nk)

    def qmap(h, i, j):
        return (h, i, 0)

    def kvmap(h, i, j):
        return ((h % H) // rep + (h // H) * K, j, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), qmap),
            pl.BlockSpec((1, bk, hd), kvmap),
            pl.BlockSpec((1, bk, hd), kvmap),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), qmap),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running sum
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(q.reshape(B * H, S, hd),
      k.reshape(B * K, S, hd),
      v.reshape(B * K, S, hd))
    return out.reshape(B, H, S, hd)
