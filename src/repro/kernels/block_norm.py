"""Pallas TPU kernel: block L2 normalization (HOG stages 4-5, eq. 5).

Input : hist (B, ch, cw, 9) f32         (paper: 16 x 8 x 9)
Output: blocks (B, bh, bw, 36) f32      (paper: 15 x 7 x 36), normalized

v_i / sqrt(||v||^2 + eps^2) per 2x2-cell block. The paper's hardware
approximates the reciprocal sqrt with a Newton-Raphson unit (47-cycle
block latency); mode="nr" reproduces those numerics (2 NR iterations
from an exponent-halved seed), mode="rsqrt" uses the VPU's native
rsqrt -- the same approximation baked into silicon (DESIGN.md §2).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import numerics as N
from repro.kernels.common import cdiv, resolve_interpret

#: back-compat alias -- the canonical NR rsqrt (and the whole normalize
#: tail) lives in core/numerics.py, shared by every backend
_nr_rsqrt = N.nr_rsqrt


def _kernel(hist_ref, out_ref, *, block: int, eps: float, mode: str):
    h = hist_ref[...]                                # (TB, ch, cw, bins)
    tb, ch, cw, bins = h.shape
    bh, bw = ch - block + 1, cw - block + 1
    parts = [h[:, i:i + bh, j:j + bw, :]
             for i in range(block) for j in range(block)]
    v = jnp.concatenate(parts, axis=-1)              # (TB, bh, bw, 36)
    # shared normalize tail: rsqrt flavor + int8 quantize for "fixed"
    out_ref[...] = N.finish_blocks(v, eps, mode)


@partial(jax.jit, static_argnames=("block", "eps", "mode", "block_b",
                                   "interpret"))
def block_norm(hist: jax.Array, block: int = 2, eps: float = 1e-2,
               mode: str = "rsqrt", block_b: int = 8,
               interpret: Optional[bool] = None) -> jax.Array:
    B, ch, cw, bins = hist.shape
    bh, bw = ch - block + 1, cw - block + 1
    bd = block * block * bins
    tb = min(block_b, B)
    return pl.pallas_call(
        partial(_kernel, block=block, eps=eps, mode=mode),
        grid=(cdiv(B, tb),),
        in_specs=[pl.BlockSpec((tb, ch, cw, bins), lambda i: (i, 0, 0, 0))],
        out_specs=pl.BlockSpec((tb, bh, bw, bd), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, bh, bw, bd), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(hist)
