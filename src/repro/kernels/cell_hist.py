"""Pallas TPU kernel: per-cell orientation histograms (HOG stage 3b).

Input : mag (B, Ha, Wa) f32, bin (B, Ha, Wa) int32    (paper: 128 x 64)
Output: hist (B, ch, cw, 9) f32                        (paper: 16 x 8 x 9)

TPU adaptation of the paper's BRAM accumulate-per-bin pipeline: the
scatter "hist[bin] += mag" serializes on TPU, so the accumulation is
re-expressed as one masked magnitude plane per bin,

    hist[b, c] = sum_px mag[c, px] * [bin[c, px] == b]

pooled over each cell's pixels -- the "adder tree in space, not time"
translation (DESIGN.md §2, §16).

Layout (window kernels): the BATCH rides the 128-wide lane axis, window
columns the sublanes and window rows a leading axis. A window is only
66 pixels wide, so any layout with a window axis on the lanes pads it
about 2x and must split the lanes into (cells, 8) to pool a cell, which
Mosaic does not lower. With windows on the lanes, pooling a cell splits
the row axis (free) and the sublane axis at its 8-row tile (free), and
the bins stay a leading axis of whole planes: nothing is scattered into
a bin axis. The wrapper transposes in and out of this layout in XLA.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANE, cdiv, resolve_interpret


def window_hist(mag, b, *, cell: int, bins: int):
    """(Ha, Wa, TB) mag/bin -> (bins, ch, cw, TB) f32 cell histograms.

    Integer (fixed chain) magnitudes sum exactly in f32: a cell holds at
    most 64 * 361 < 2^24."""
    ha, wa, tb = mag.shape
    ch, cw = ha // cell, wa // cell
    mag = mag.astype(jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    planes = [jnp.where(b == k, mag, zero)
              .reshape(ch, cell, cw, cell, tb).sum(axis=(1, 3))
              for k in range(bins)]
    return jnp.stack(planes)


def batch_on_lanes(x, block_b: int):
    """(B, H, W) -> ((H, W, Bp) batch-on-lanes copy, tile width). The
    batch pads to a whole number of tiles; a tile is the whole batch up
    to `block_b` (keep it a multiple of 128 for the TPU)."""
    B = x.shape[0]
    tb = B if B <= block_b else block_b
    bp = cdiv(B, tb) * tb
    return jnp.pad(jnp.moveaxis(x, 0, -1),
                   ((0, 0), (0, 0), (0, bp - B))), tb


def _kernel(mag_ref, bin_ref, hist_ref, *, cell: int, bins: int):
    hist_ref[...] = window_hist(mag_ref[...], bin_ref[...], cell=cell,
                                bins=bins)


@partial(jax.jit, static_argnames=("cell", "bins", "block_b", "interpret"))
def cell_hist(mag: jax.Array, bin_idx: jax.Array, cell: int = 8,
              bins: int = 9, block_b: int = LANE,
              interpret: Optional[bool] = None) -> jax.Array:
    B, Ha, Wa = mag.shape
    ch, cw = Ha // cell, Wa // cell
    m, tb = batch_on_lanes(mag, block_b)
    bi, _ = batch_on_lanes(bin_idx, block_b)
    # one cell row of a lane tile per program: a whole 128-window tile
    # of f32 mag + int32 bins (8 MiB, double-buffered) would not fit the
    # TPU's scoped VMEM
    spec = pl.BlockSpec((cell, Wa, tb), lambda i, r: (r, 0, i))
    out = pl.pallas_call(
        partial(_kernel, cell=cell, bins=bins),
        grid=(m.shape[-1] // tb, ch),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((bins, 1, cw, tb), lambda i, r: (0, r, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bins, ch, cw, m.shape[-1]),
                                       jnp.float32),
        interpret=resolve_interpret(interpret),
    )(m, bi)
    hist = jnp.moveaxis(out[..., :B], (0, 3), (3, 0))
    # int32 magnitudes (fixed chain) store int16 histograms (per-cell
    # bound 64 * 361 < 2^15; the f32 sums above are exact integers)
    if jnp.issubdtype(mag.dtype, jnp.integer):
        return hist.astype(jnp.int16)
    return hist
