"""Single numerics-mode dispatch table shared by every backend.

PR 6 taught us the "identity trap": a mode predicate duplicated across
backends (ref vs kernel vs fused) eventually disagrees in one of them,
and the divergent backend silently falls back to a different numerics
path. Concretely: `fused_hog.py` engaged the Newton-Raphson rsqrt only
under `mode == "cordic"` while `stages.py` made the same decision with
its own `_use_nr`, so any new mode had to update N scattered if-chains
or quietly normalize in fp32 somewhere.

This module is now the ONE place that maps a numerics-mode name to its
per-stage choices. Backends dispatch through:

  * ``spec_for(cfg)``       -- HOGConfig -> NumericsSpec (the mode row),
  * ``MAG_BIN`` impls stay in core/hog.py keyed by ``spec.name``; the
    Pallas twin table is ``kernels/hog_gradient.py:MAG_BIN_IMPLS``,
  * ``store_hist(hist)``    -- histogram accumulator -> stored dtype,
  * ``finish_blocks(v, eps, norm)`` -- the block-normalize tail
    (rsqrt flavor + optional int8 quantize-dequantize), used verbatim by
    the ref path and every Pallas block-norm kernel.

Unknown modes raise ValueError everywhere instead of falling through an
else-branch.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from repro.core import quant

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class NumericsSpec:
    """One numerics mode's per-stage choices.

    name        -- the mag/bin implementation key (core/hog.py _MAG_BIN
                   and kernels/hog_gradient.py MAG_BIN_IMPLS),
    kernel_mode -- what the gradient/hist Pallas kernels receive,
    norm        -- block-normalize tail flavor ("rsqrt" | "nr" | "fixed"),
    quantized   -- True iff the chain runs the fixed-point datapath
                   (rint'd gray in, int16 histograms, int8 descriptors,
                   int8 scoring matmul).
    """

    name: str
    kernel_mode: str
    norm: str
    quantized: bool


SPECS: Dict[str, NumericsSpec] = {
    "ref": NumericsSpec("ref", "sector", "rsqrt", False),
    "sector": NumericsSpec("sector", "sector", "rsqrt", False),
    "cordic": NumericsSpec("cordic", "cordic", "nr", False),
    "fixed": NumericsSpec("fixed", "fixed", "fixed", True),
}


def spec_for(cfg) -> NumericsSpec:
    """HOGConfig -> NumericsSpec. ``numerics="fixed"`` overrides ``mode``
    (the fixed datapath IS a mag/bin choice; cfg.mode only picks the
    float flavor)."""
    name = "fixed" if getattr(cfg, "numerics", "float") == "fixed" else cfg.mode
    try:
        return SPECS[name]
    except KeyError:
        raise ValueError(
            f"unknown numerics mode {name!r}; expected one of "
            f"{sorted(SPECS)}") from None


def nr_rsqrt(x: Array, iters: int = 2) -> Array:
    """Newton-Raphson reciprocal sqrt, faithful to the hardware unit.

    Seed = the exponent-halving bit manipulation (0x5f3759df), i.e. the
    integer-datapath seed a hardware rsqrt unit derives before its NR
    refinement stages; two NR iterations then reach ~1e-6 relative error,
    matching the paper's Block_NormalizationCore ([3]'s scheme).
    """
    xf = x.astype(jnp.float32)
    i = jax.lax.bitcast_convert_type(xf, jnp.int32)
    y = jax.lax.bitcast_convert_type(jnp.int32(0x5F3759DF) - (i >> 1),
                                     jnp.float32)
    for _ in range(iters):
        y = y * (1.5 - 0.5 * xf * y * y)
    return y


#: which rsqrt each norm flavor uses. "fixed" shares the hardware NR unit
#: (the FPGA's normalizer is the same core) and then quantizes.
NORM_RSQRT = {
    "rsqrt": jax.lax.rsqrt,
    "nr": nr_rsqrt,
    "fixed": nr_rsqrt,
}


def finish_blocks(v: Array, eps: float, norm: str, axis: int = -1) -> Array:
    """The block-normalize tail: (..., bd) raw block vectors -> (..., bd)
    L2-normalized f32 blocks (eq. 5), quantized onto the per-block int8
    grid when norm == "fixed". `axis` holds the bd block components; the
    Pallas kernels pass a leading axis (components-major planes).

    EVERY backend's normalize stage ends here -- ref (core/hog.py), the
    standalone block_norm kernel, dense_block_norm, and both fused
    kernels -- so a mode cannot normalize differently in one backend.

    In fixed mode the incoming vectors hold int16 histogram counts in
    half-gray units; eps is scaled by quant.MAG_SCALE so eq. 5 stays the
    same *relative* regularizer as the float chain (v/s normalized equals
    v normalized with eps*s).
    """
    try:
        rs = NORM_RSQRT[norm]
    except KeyError:
        raise ValueError(
            f"unknown norm flavor {norm!r}; expected one of "
            f"{sorted(NORM_RSQRT)}") from None
    v = v.astype(jnp.float32)
    e = eps * quant.MAG_SCALE if norm == "fixed" else eps
    # e * e in Python (f64) then one f32 round -- bit-identical to the
    # historical `+ cfg.eps ** 2` weak-scalar add
    sq = v * v
    ss = (quant.plane_reduce(jnp.add, sq) if axis == 0
          else jnp.sum(sq, axis=axis, keepdims=True)) + jnp.float32(e * e)
    out = v * rs(ss)
    if norm == "fixed":
        out = quant.quantize_dequantize(out, axis)
    return out


def store_hist(hist: Array) -> Array:
    """Histogram accumulator -> stored dtype: int16 for integer (fixed
    chain) accumulators, passthrough for float. The int16 bound is
    per-cell: 64 px * mag_q<=361 = 23104 < 2^15 regardless of slab or
    frame size (bounds per cell, not per slab)."""
    if jnp.issubdtype(hist.dtype, jnp.integer):
        return hist.astype(jnp.int16)
    return hist
