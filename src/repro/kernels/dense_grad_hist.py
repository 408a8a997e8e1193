"""Pallas TPU kernel: DENSE gradient -> mag/bin -> cell histograms.

Input : gray scene (B, H, W) f32, H = gh + 2 with gh a whole number of
        cells (the dense-layout trim, core/stages.py)
Output: hist (B, ch, cw, bins) f32 -- the whole scene's cell grid

The window kernels (hog_gradient.py + cell_hist.py) tile over a BATCH
of small windows. This kernel instead tiles the chain over ROW SLABS of
the scene's CELL GRID (`row_cells` cell rows = 8*row_cells pixel rows
per program), the dense analogue of how the paper's FPGA streams rows
through BUFFER_GRADIENT: each slab's gradients, bins and cell
histograms live entirely in VMEM and the grid pipelines slabs against
the HBM loads.

Layout (shared with fused_hog.dense_fused_hog, DESIGN.md §16): pooling a
cell's 8 columns out of a lane-major image would split the 128-wide
lane axis into (cells, 8), which Mosaic does not lower, and a trailing
bins axis pads 9 values to 128 lanes. So the wrapper hands the kernel
COLUMN-OFFSET PLANES: plane q holds pixel column 8*j + q of every cell
column j (q = 0..9, the two extra planes are the right-hand gradient
halo). Cell columns sit on the lanes (a 1080p frame fills 240 of 256),
rows on the sublanes. Offset pc's gradients are plain plane differences,
a cell's 8 columns pool by adding 8 planes, its 8 rows by splitting the
sublane axis at its 8-row tile, and each bin is one masked plane: no
scatter into a bin axis, no lane reshape. The kernel writes bins-major
(bins, rows, cw) blocks; XLA transposes the small result back.

Halo: a slab of R pixel rows reads R + 2 gray rows. The wrapper builds
the overlapping row slabs with one clamped row gather (rows past the
frame only feed cell rows >= ch, which are sliced off), which keeps the
BlockSpecs plain and non-overlapping.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common import cdiv, resolve_interpret
from repro.kernels.hog_gradient import mag_bin_impl


def column_slabs(gray, *, cell: int, stride: int, rows: int, slabs: int):
    """(B, H, W) gray -> (B, cell + 2, slabs, rows, cw) column-offset
    planes of overlapping row slabs: slab i holds gray rows
    i*stride .. i*stride + rows - 1 (clamped to the frame), plane q
    column cell*j + q of cell column j."""
    B, H, W = gray.shape
    cw = (W - 2) // cell
    planes = jnp.stack([gray[:, :, q:q + cell * (cw - 1) + 1:cell]
                        for q in range(cell + 2)], axis=1)
    idx = np.minimum(np.arange(slabs)[:, None] * stride
                     + np.arange(rows)[None, :], H - 1)
    return planes[:, :, idx]


def slab_hist(slab_ref, *, cell: int, bins: int, mode: str):
    """Column-offset planes of one gray slab, a (1, cell + 2, 1, R + 2,
    cw) block -> (bins, R // cell, cw) f32 cell histograms of its R
    interior rows.

    The loop runs over the cell's column offsets, so only one offset's
    gradients and CORDIC temporaries are live at a time (a UHD slab of
    the fixed chain otherwise overflows the scoped VMEM). Integer (fixed
    chain) magnitudes sum exactly in f32: a cell holds at most
    64 * 361 < 2^24."""
    rr, cw = slab_ref.shape[3] - 2, slab_ref.shape[4]
    impl = mag_bin_impl(mode)

    def plane(q, r0):
        return slab_ref[0, q, 0, pl.ds(r0, rr), :]

    def offset(pc, acc):                         # column offset in a cell
        fx = plane(pc + 2, 1) - plane(pc, 1)      # eq. (1)
        fy = plane(pc + 1, 2) - plane(pc + 1, 0)  # eq. (2)
        mag, b = impl(fx, fy)
        mag = mag.astype(jnp.float32)
        return tuple(a + jnp.where(b == k, mag, jnp.float32(0))
                     for k, a in enumerate(acc))

    acc = jax.lax.fori_loop(
        0, cell, offset,
        tuple(jnp.zeros((rr, cw), jnp.float32) for _ in range(bins)))
    return jnp.stack([a.reshape(rr // cell, cell, cw).sum(axis=1)
                      for a in acc])


def _kernel(slab_ref, hist_ref, *, cell: int, bins: int, mode: str):
    hist_ref[0] = slab_hist(slab_ref, cell=cell, bins=bins, mode=mode)


@partial(jax.jit, static_argnames=("cell", "bins", "mode", "row_cells",
                                   "interpret"))
def dense_grad_hist(gray: jax.Array, cell: int = 8, bins: int = 9,
                    mode: str = "sector", row_cells: int = 8,
                    interpret: Optional[bool] = None) -> jax.Array:
    """(B, H, W) f32 dense scene -> (B, ch, cw, bins) cell histograms."""
    B, H, W = gray.shape
    gh = (H - 2) // cell * cell
    ch, cw = gh // cell, (W - 2) // cell
    tr = min(row_cells, ch)
    s = cdiv(ch, tr)
    k = tr * cell + 2                         # gray rows each slab reads
    slabs = column_slabs(gray, cell=cell, stride=tr * cell, rows=k,
                         slabs=s)
    out = pl.pallas_call(
        partial(_kernel, cell=cell, bins=bins, mode=mode),
        grid=(B, s),
        in_specs=[pl.BlockSpec((1, cell + 2, 1, k, cw),
                               lambda b, i: (b, 0, i, 0, 0))],
        out_specs=pl.BlockSpec((1, bins, tr, cw), lambda b, i: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, bins, s * tr, cw), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(slabs)
    hist = jnp.moveaxis(out[:, :, :ch], 1, -1)
    # fixed chain: exact integer sums, stored int16 (per-cell bound)
    return hist.astype(jnp.int16) if mode == "fixed" else hist
