"""Pallas TPU kernel: FUSED HOG window pipeline (stages 3-6 in one kernel).

Input : gray (B, 130, 66) f32
Output: descriptors (B, 3780) f32

This is the beyond-paper §Perf artifact. The staged kernels round-trip
(B,128,64) magnitude/bin and (B,16,8,9) histograms through HBM between
pallas_calls; per window that is ~98 KB of intermediate traffic for a
15 KB descriptor. Fusing the whole chain keeps every intermediate in
VMEM, so the kernel itself reads the gray window and writes only the
descriptor (the wrapper's XLA transposes of the batch onto the lanes
and back add their own HBM traffic, not measured) -- mirroring how the paper's FPGA streams cell data through
BUFFER_HOG_PRENORM without ever leaving on-chip BRAM. That
correspondence (BRAM dataflow == VMEM fusion) is the paper's core
insight mapped to TPU (DESIGN.md §2).

The SVM dot product could fuse here too; it is kept separate because the
weight tile is shared across the whole batch (svm_matmul.py).

Both kernels keep histograms and block vectors as bins-major planes:
the window kernel with the batch on the lanes (cell_hist.py), the dense
kernel with cell columns on the lanes (dense_grad_hist.py). The wrappers
transpose the planes back to the (..., bh, bw, 36) block grid in XLA.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import numerics as N
from repro.kernels.cell_hist import batch_on_lanes, window_hist
from repro.kernels.common import LANE, cdiv, resolve_interpret
from repro.kernels.dense_block_norm import block_vectors
from repro.kernels.dense_grad_hist import column_slabs, slab_hist
from repro.kernels.hog_gradient import mag_bin_impl


def _norm_flavor(mode: str) -> str:
    # the normalize tail is a MODE-DERIVED property: SPECS is the same
    # table stages.py dispatches on, so the fused kernels can never
    # disagree with the staged ones about which rsqrt (or quantizer) a
    # mode uses
    return N.SPECS[mode].norm


def _kernel(gray_ref, desc_ref, hist_ref, *, cell: int, block: int,
            bins: int, eps: float, mode: str):
    _, ch, cw, _ = hist_ref.shape
    wa = cw * cell
    impl = mag_bin_impl(mode)

    def cell_row(r, carry):       # cell + 2 gray rows -> one histogram row
        g = gray_ref[pl.ds(r * cell, cell + 2)]           # (cell+2, W, TB)
        fx = g[1:-1, 2:wa + 2] - g[1:-1, :wa]
        fy = g[2:, 1:wa + 1] - g[:-2, 1:wa + 1]
        mag, b = impl(fx, fy)
        hist_ref[:, pl.ds(r, 1)] = window_hist(mag, b, cell=cell, bins=bins)
        return carry

    jax.lax.fori_loop(0, ch, cell_row, 0)
    bw = desc_ref.shape[2]

    def block_row(i, carry):      # one block row at a time
        h = hist_ref[:, pl.ds(i, block)]            # (bins, block, cw, TB)
        v = block_vectors([h[:, j:j + 1] for j in range(block)], bw)
        desc_ref[:, pl.ds(i, 1)] = N.finish_blocks(
            v, eps, _norm_flavor(mode), axis=0)
        return carry

    jax.lax.fori_loop(0, desc_ref.shape[1], block_row, 0)


@partial(jax.jit, static_argnames=("cell", "block", "bins", "eps", "mode",
                                   "block_b", "interpret"))
def fused_hog(gray: jax.Array, cell: int = 8, block: int = 2, bins: int = 9,
              eps: float = 1e-2, mode: str = "sector", block_b: int = LANE,
              interpret: Optional[bool] = None) -> jax.Array:
    B, H, W = gray.shape
    ch, cw = (H - 2) // cell, (W - 2) // cell
    bh, bw = ch - block + 1, cw - block + 1
    bd = block * block * bins
    g, tb = batch_on_lanes(gray, block_b)
    bp = g.shape[-1]
    out = pl.pallas_call(
        partial(_kernel, cell=cell, block=block, bins=bins, eps=eps,
                mode=mode),
        grid=(bp // tb,),
        in_specs=[pl.BlockSpec((H, W, tb), lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((bd, bh, bw, tb), lambda i: (0, 0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bd, bh, bw, bp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bins, ch, cw, tb), jnp.float32)],
        # a 128-window lane tile holds 4.8 MB of gray and 2.2 MB of
        # descriptors; double-buffered, with the histogram scratch and
        # the loop temporaries, that is just over the 16 MiB default
        # scoped VMEM (v5e has 128 MiB)
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=32 << 20),
        interpret=resolve_interpret(interpret),
    )(g)
    # (bd, bh, bw, B) -> (B, bh, bw, bd) -> the collated 3780 descriptor
    return jnp.moveaxis(out[..., :B], (0, 3), (3, 0)).reshape(B, -1)


# ------------------------------------------------------------ dense grid
# The window kernel above fuses the chain for a BATCH of 130x66 tiles.
# The dense variant fuses the same chain for a WHOLE SCENE, tiled over
# row slabs of the scene's block grid so arbitrarily tall frames stream
# through a fixed VMEM budget (the dense analogue of the paper's
# BUFFER_HOG_PRENORM row streaming). A slab of `row_blocks` block rows
# needs `row_blocks + block - 1` cell rows of histogram, i.e. a
# one-cell-row recompute overlap between neighboring slabs -- the
# wrapper hands each program its overlapping gray rows as column-offset
# planes (dense_grad_hist.column_slabs, one XLA gather, ~15% duplicated
# rows), which keeps the BlockSpecs plain and non-overlapping.

def _dense_kernel(slab_ref, out_ref, *, cell: int, block: int, bins: int,
                  eps: float, mode: str):
    hist = slab_hist(slab_ref, cell=cell, bins=bins, mode=mode)
    tr, bw = out_ref.shape[-2:]
    v = block_vectors([hist[:, i:i + tr] for i in range(block)], bw)
    out_ref[0] = N.finish_blocks(v, eps, _norm_flavor(mode), axis=0)


@partial(jax.jit, static_argnames=("cell", "block", "bins", "eps", "mode",
                                   "row_blocks", "interpret"))
def dense_fused_hog(gray: jax.Array, cell: int = 8, block: int = 2,
                    bins: int = 9, eps: float = 1e-2, mode: str = "sector",
                    row_blocks: int = 8,
                    interpret: Optional[bool] = None) -> jax.Array:
    """(B, H, W) f32 dense scene -> (B, bh, bw, block^2*bins) f32."""
    B, H, W = gray.shape
    gh = (H - 2) // cell * cell
    ch, cw = gh // cell, (W - 2) // cell
    bh, bw = ch - block + 1, cw - block + 1
    bd = block * block * bins
    tr = min(row_blocks, bh)
    s = cdiv(bh, tr)
    k = (tr + block - 1) * cell + 2          # gray rows each slab reads
    slabs = column_slabs(gray, cell=cell, stride=tr * cell, rows=k,
                         slabs=s)
    out = pl.pallas_call(
        partial(_dense_kernel, cell=cell, block=block, bins=bins, eps=eps,
                mode=mode),
        grid=(B, s),
        in_specs=[pl.BlockSpec((1, cell + 2, 1, k, cw),
                               lambda b, i: (b, 0, i, 0, 0))],
        out_specs=pl.BlockSpec((1, bd, tr, bw), lambda b, i: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, bd, s * tr, bw), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(slabs)
    return jnp.moveaxis(out[:, :, :bh], 1, -1)
