"""Pallas TPU kernel: DENSE block L2 normalization (eq. 5, whole scene).

Input : hist (B, ch, cw, bins) f32 -- the scene's cell-histogram grid
Output: blocks (B, bh, bw, block^2*bins) f32, L2-normalized

Dense companion of block_norm.py: instead of one megablock holding the
whole scene's cell grid, the kernel tiles over ROW SLABS of the BLOCK
grid (`row_blocks` block rows per program). A block row r reads cell
rows r..r+block-1, so the wrapper passes `block` vertically shifted
views of the histogram buffer instead of overlapping BlockSpecs; slab i
of view j holds cell rows i*TR+j .. i*TR+j+TR-1, exactly the j-th cell
row of every block in the slab.

Layout: bins-major planes (bins, rows, cw), like dense_grad_hist.py.
With the 9 bins (or 36 block components) on the lanes every slab would
pad about 14x, and a UHD slab would not fit the TPU's scoped VMEM;
with cell columns on the lanes a 3840-wide frame pads 479 to 512. The
wrapper transposes in and out in XLA.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import numerics as N
from repro.kernels.common import cdiv, resolve_interpret


def block_vectors(rows: Sequence[jax.Array], bw: int) -> jax.Array:
    """Raw block vectors, components-major: rows[i] is the (bins, R, cw,
    ...) histogram of cell row i of each block row -> (block^2*bins, R,
    bw, ...). Component (i*block + j)*bins + k is bin k of cell (i, j),
    the order of the ref collate (core/hog.py:block_normalize)."""
    return jnp.concatenate([h[:, :, j:j + bw] for h in rows
                            for j in range(len(rows))], axis=0)


def _kernel(*refs, eps: float, mode: str):
    views, out_ref = refs[:-1], refs[-1]
    v = block_vectors([r[0] for r in views], out_ref.shape[-1])
    # shared normalize tail: rsqrt flavor + int8 quantize for "fixed"
    out_ref[0] = N.finish_blocks(v, eps, mode, axis=0)


@partial(jax.jit, static_argnames=("block", "eps", "mode", "row_blocks",
                                   "interpret"))
def dense_block_norm(hist: jax.Array, block: int = 2, eps: float = 1e-2,
                     mode: str = "rsqrt", row_blocks: int = 16,
                     interpret: Optional[bool] = None) -> jax.Array:
    """(B, ch, cw, bins) f32 -> (B, bh, bw, block^2*bins) f32."""
    B, ch, cw, bins = hist.shape
    bh, bw = ch - block + 1, cw - block + 1
    bd = block * block * bins
    tr = min(row_blocks, bh)
    s = cdiv(bh, tr)
    # pad cell rows so every shifted view tiles into s full slabs; the
    # zero rows only feed block rows >= bh, sliced off below (the zero
    # vectors normalize to zero -- eps^2 keeps the rsqrt finite)
    chp = s * tr + block - 1
    planes = jnp.pad(jnp.moveaxis(hist, -1, 1).astype(jnp.float32),
                     ((0, 0), (0, 0), (0, chp - ch), (0, 0)))
    views = [planes[:, :, j:j + s * tr] for j in range(block)]
    out = pl.pallas_call(
        partial(_kernel, eps=eps, mode=mode),
        grid=(B, s),
        in_specs=[pl.BlockSpec((1, bins, tr, cw),
                               lambda b, i: (b, 0, i, 0))] * block,
        out_specs=pl.BlockSpec((1, bd, tr, bw), lambda b, i: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, bd, s * tr, bw), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(*views)
    return jnp.moveaxis(out[:, :, :bh], 1, -1)
