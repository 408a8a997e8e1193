"""Pallas TPU kernel: batched linear-SVM scoring (eq. 6, SVMCLASSIFY block).

Input : feats (B, F) f32, w (F,) f32, b () f32     (paper: F = 3780)
Output: scores (B,) f32

The FPGA evaluates W.X serially (one MAC per cycle); the TPU evaluates a
(TB, TF) x (TF, 1) matmul per grid step on the MXU. F = 3780 is padded to
3840 = 30*128 so every K tile is lane-aligned; the K grid dimension
accumulates partial products into the output block (revisited-block
accumulation, the canonical Pallas matmul pattern).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE, cdiv, resolve_interpret, round_up


def _kernel(x_ref, w_ref, out_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]                                  # (TB, TF)
    w = w_ref[...]                                  # (TF, 1)
    out_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # (TB, 1) on the MXU


@partial(jax.jit, static_argnames=("block_b", "block_f", "interpret"))
def svm_scores(feats: jax.Array, w: jax.Array, bias: jax.Array,
               block_b: int = 128, block_f: int = 512,
               interpret: Optional[bool] = None) -> jax.Array:
    B, F = feats.shape
    Bp = round_up(B, 8)
    tb = min(block_b, Bp)
    tf = min(block_f, round_up(F, LANE))
    # every K tile must be in-bounds: pad F to a multiple of the K tile
    # (zero padding contributes exactly 0 to the accumulation)
    Fp = round_up(F, tf)
    feats = jnp.pad(feats, ((0, Bp - B), (0, Fp - F)))
    wp = jnp.pad(w, (0, Fp - F)) if Fp != F else w
    out = pl.pallas_call(
        _kernel,
        grid=(cdiv(Bp, tb), cdiv(Fp, tf)),
        in_specs=[
            pl.BlockSpec((tb, tf), lambda i, k: (i, k)),
            pl.BlockSpec((tf, 1), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(feats, wp.reshape(Fp, 1))
    return out[:B, 0] + bias


# ---------------------------------------------------------- dense scoring
# The dense detector scores every window position at cell stride; the
# 15x7x36 "conv" over the scene's block grid factors into ONE matmul
# (P block positions x 36) @ (36 x 105 window offsets) followed by 105
# cheap shifted adds (core/detector.py:score_blocks). This kernel is the
# matmul half on the MXU, grid over M tiles with the full (K, N) weight
# tile resident -- K=36, N=105 pad to one (40, 128) sublane/lane tile.


def _score_kernel(x_ref, w_ref, out_ref):
    out_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@partial(jax.jit, static_argnames=("block_m", "interpret"))
def score_matmul(flat: jax.Array, wt: jax.Array, block_m: int = 512,
                 interpret: Optional[bool] = None) -> jax.Array:
    """(M, K) block rows @ (K, N) per-offset weights -> (M, N) f32.

    Accepts f32 or bf16 inputs (the perf preset's bf16 descriptors);
    accumulation is always f32 (`preferred_element_type`).
    """
    M, K = flat.shape
    K2, N = wt.shape
    assert K == K2, (flat.shape, wt.shape)
    Mp = round_up(M, 8)
    Kp = round_up(K, 8)
    Np = round_up(N, LANE)
    tm = min(block_m, Mp)
    Mp = round_up(Mp, tm)
    flat = jnp.pad(flat, ((0, Mp - M), (0, Kp - K)))
    wt = jnp.pad(wt, ((0, Kp - K), (0, Np - N)))
    out = pl.pallas_call(
        _score_kernel,
        grid=(cdiv(Mp, tm),),
        in_specs=[
            pl.BlockSpec((tm, Kp), lambda i: (i, 0)),
            pl.BlockSpec((Kp, Np), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, Np), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(flat, wt)
    return out[:M, :N]


def _score_kernel_i8(x_ref, w_ref, out_ref):
    out_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


@partial(jax.jit, static_argnames=("block_m", "interpret"))
def score_matmul_int8(q: jax.Array, wq: jax.Array, block_m: int = 512,
                      interpret: Optional[bool] = None) -> jax.Array:
    """(M, K) int8 block rows @ (K, N) int8 weights -> (M, N) int32.

    The fixed-mode twin of `score_matmul`: codes in [-127, 127] over
    K = 36 accumulate to at most 36 * 127^2 < 2^20, so the int32 MXU
    accumulation is EXACT -- which is why quantized scoring is
    byte-identical under any M blocking, tiling, or sharding (integer
    adds are associative; there is no rounding to reorder). Padding is
    zeros, contributing exact 0s. int8 min tile is (32, 128), hence the
    32-row/col alignment.
    """
    M, K = q.shape
    K2, N = wq.shape
    assert K == K2, (q.shape, wq.shape)
    assert q.dtype == jnp.int8 and wq.dtype == jnp.int8, (q.dtype, wq.dtype)
    Mp = round_up(M, 32)
    Kp = round_up(K, 32)
    Np = round_up(N, LANE)
    tm = min(block_m, Mp)
    Mp = round_up(Mp, tm)
    q = jnp.pad(q, ((0, Mp - M), (0, Kp - K)))
    wq = jnp.pad(wq, ((0, Kp - K), (0, Np - N)))
    out = pl.pallas_call(
        _score_kernel_i8,
        grid=(cdiv(Mp, tm),),
        in_specs=[
            pl.BlockSpec((tm, Kp), lambda i: (i, 0)),
            pl.BlockSpec((Kp, Np), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, Np), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q, wq)
    return out[:M, :N]
