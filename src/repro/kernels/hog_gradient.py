"""Pallas TPU kernel: gradient + magnitude + orientation-bin (HOG stage 3).

Input : gray windows (B, H, W) float32   (paper: H=130, W=66)
Output: magnitude   (B, H-2, W-2) float32
        bin index   (B, H-2, W-2) int32  (9 unsigned-orientation bins)

Adaptation of the paper's CORDIC stage (Figs. 7-8) to the TPU VPU:
  * mode="sector": the classifier consumes only the BIN, so the angle is
    never materialized -- 8 cross-multiplication boundary tests replace
    the 15-iteration CORDIC rotation (see DESIGN.md §2). No trig, no
    division, branch-free: pure VPU mul/cmp/add.
  * mode="cordic": the faithful datapath -- 15 LUT-driven shift-add
    rotations, gain-corrected magnitude, then binning. Kept as the
    validation mode for the paper's numerics.

Grid: one program per TB-window slab; W sits in the lane dimension
(66 -> 128 lane padding; the fused kernel in fused_hog.py repacks to
recover this, see §Perf).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cordic import (ANG_180, ATAN_LUT_DEG, ATAN_LUT_FIXED,
                               MAG_FRAC_BITS, _INV_GAIN_HALF, cordic_gain)
from repro.kernels.common import cdiv, resolve_interpret

_BOUNDARIES = tuple((math.cos(math.radians(20.0 * (k + 1))),
                     math.sin(math.radians(20.0 * (k + 1))))
                    for k in range(8))


def _mag_bin_sector(fx, fy):
    mag = jnp.sqrt(fx * fx + fy * fy)
    flip = fy < 0
    ux = jnp.where(flip, -fx, fx)
    uy = jnp.where(flip, -fy, fy)
    on_axis = (uy == 0) & (ux < 0)
    ux = jnp.where(on_axis, -ux, ux)
    b = jnp.zeros(fx.shape, jnp.int32)
    for cb, sb in _BOUNDARIES:
        b += ((uy * cb - ux * sb) >= 0.0).astype(jnp.int32)
    return mag, b


def _mag_bin_cordic(fx, fy, iters: int = 15):
    neg_x = fx < 0
    x0 = jnp.where(neg_x, -fx, fx)
    y0 = jnp.where(neg_x, -fy, fy)
    z0 = jnp.zeros_like(fx)
    x, y, z = x0, y0, z0
    for i in range(iters):                       # fixed-depth HW pipeline
        p = 2.0 ** (-i)
        d = jnp.where(y < 0, -1.0, 1.0)
        x, y, z = x + d * y * p, y - d * x * p, z + d * ATAN_LUT_DEG[i]
    mag = x * (1.0 / cordic_gain(iters))
    # on-axis pin (fy == 0 -> angle exactly 0/180): without it the
    # +-atan(2^-14) iteration residual leaks through the unsigned fold
    # below as mod(180 + eps, 180) ~= 179.997 -> bin 8 where the arctan2
    # oracle says bin 0 (the 180-degree off-by-one this PR sweeps)
    z = jnp.where(fy == 0, 0.0, z)
    ang = jnp.where(neg_x, jnp.where(fy >= 0, z + 180.0, z - 180.0), z)
    both_zero = (fx == 0) & (fy == 0)
    mag = jnp.where(both_zero, 0.0, mag)
    ang = jnp.where(both_zero, 0.0, ang)
    theta = jnp.mod(ang, 180.0)
    b = jnp.clip(jnp.floor(theta / 20.0), 0, 8).astype(jnp.int32)
    return mag, b


def _mag_bin_fixed(fx, fy, iters: int = 15):
    """Integer shift-add CORDIC (core/cordic.py:cordic_mag_bin_fixed,
    unrolled for the Mosaic pipeline). fx/fy must be integer-valued f32;
    returns (mag int32 in half-gray units, bin int32)."""
    xi = jnp.round(fx).astype(jnp.int32)
    yi = jnp.round(fy).astype(jnp.int32)
    neg_x = xi < 0
    x = jnp.where(neg_x, -xi, xi) << MAG_FRAC_BITS
    y = jnp.where(neg_x, -yi, yi) << MAG_FRAC_BITS
    z = jnp.zeros_like(x)
    for i in range(iters):                       # static shifts + LUT ints
        xs, ys = x >> i, y >> i
        d = y < 0
        x, y, z = (jnp.where(d, x - ys, x + ys),
                   jnp.where(d, y + xs, y - xs),
                   jnp.where(d, z - ATAN_LUT_FIXED[i], z + ATAN_LUT_FIXED[i]))
    z = jnp.where(yi == 0, 0, z)                 # same on-axis pin
    ang = jnp.where(neg_x, jnp.where(yi >= 0, z + ANG_180, z - ANG_180), z)
    theta = jnp.mod(ang, ANG_180)
    b = jnp.minimum(theta // (ANG_180 // 9), 8).astype(jnp.int32)
    mag = jnp.rint(x.astype(jnp.float32)
                   * jnp.float32(_INV_GAIN_HALF)).astype(jnp.int32)
    both_zero = (xi == 0) & (yi == 0)
    return jnp.where(both_zero, 0, mag), jnp.where(both_zero, 0, b)


#: numerics-mode -> mag/bin implementation, the Pallas twin of
#: core/hog.py:_MAG_BIN. Every kernel (staged gradient, dense grad+hist,
#: both fused variants) dispatches through mag_bin_impl, so a mode that
#: exists in one backend exists in all of them (core/numerics.py).
MAG_BIN_IMPLS = {
    "sector": _mag_bin_sector,
    "cordic": _mag_bin_cordic,
    "fixed": _mag_bin_fixed,
}


def mag_bin_impl(mode: str):
    try:
        return MAG_BIN_IMPLS[mode]
    except KeyError:
        raise ValueError(
            f"unknown kernel numerics mode {mode!r}; expected one of "
            f"{sorted(MAG_BIN_IMPLS)}") from None


def mag_dtype(mode: str):
    """Magnitude dtype a mode's mag/bin impl produces (int32 for the
    fixed-point chain, f32 otherwise)."""
    mag_bin_impl(mode)
    return jnp.int32 if mode == "fixed" else jnp.float32


def _kernel(gray_ref, mag_ref, bin_ref, *, mode: str):
    g = gray_ref[...]                            # (TB, H, W)
    fx = g[:, 1:-1, 2:] - g[:, 1:-1, :-2]        # eq. (1)
    fy = g[:, 2:, 1:-1] - g[:, :-2, 1:-1]        # eq. (2)
    mag, b = mag_bin_impl(mode)(fx, fy)
    mag_ref[...] = mag
    bin_ref[...] = b


@partial(jax.jit, static_argnames=("mode", "block_b", "interpret"))
def hog_gradient(gray: jax.Array, mode: str = "sector",
                 block_b: int = 8, interpret: Optional[bool] = None):
    """(B, H, W) f32 -> (mag, bin) each (B, H-2, W-2)."""
    B, H, W = gray.shape
    tb = min(block_b, B)
    grid = (cdiv(B, tb),)
    out_shape = (
        jax.ShapeDtypeStruct((B, H - 2, W - 2), mag_dtype(mode)),
        jax.ShapeDtypeStruct((B, H - 2, W - 2), jnp.int32),
    )
    return pl.pallas_call(
        partial(_kernel, mode=mode),
        grid=grid,
        in_specs=[pl.BlockSpec((tb, H, W), lambda i: (i, 0, 0))],
        out_specs=(
            pl.BlockSpec((tb, H - 2, W - 2), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, H - 2, W - 2), lambda i: (i, 0, 0)),
        ),
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(gray)
