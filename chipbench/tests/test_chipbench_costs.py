"""Kernel costs against counts made by hand for one VGA level, and the
reference resize against the filter jax.image.resize documents."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chipbench import cells, reference  # noqa: E402

PREC = {"descriptors": "bfloat16", "weights": "bfloat16"}
ONE_LEVEL = {"scales": [1.0], "shape_bucket": 32}

# level 0 of a 640x480 frame, counted by hand: gradient field
# (480-2)//8*8 = 472 rows by (640-2)//8*8 = 632 columns, 59x79 cells,
# 58x78 blocks, (58-15+1) x (78-7+1) = 44 x 72 = 3,168 windows
GRAD_PX = 472 * 632
BLOCKS = 58 * 78
WINDOWS = 44 * 72
KERNELS = ["hog", "resize", "score"]


def test_hog_cost_of_one_vga_level():
    costs = cells.costs(KERNELS)
    ops, byts = costs["hog"].cost(480, 640, ONE_LEVEL, PREC)
    assert ops == 31 * GRAD_PX + 109 * BLOCKS
    assert byts == 4 * 474 * 634 + 2 * 36 * BLOCKS


def test_score_cost_of_one_vga_level():
    # the matmul of every block position with the 105 window offsets'
    # 36 weights each: as many operations as 2 x 3780 per window, over
    # the block grid rather than the window grid
    ops, byts = cells.costs(KERNELS)["score"].cost(480, 640, ONE_LEVEL, PREC)
    assert ops == 2 * 36 * 105 * BLOCKS
    assert 2 * 36 * 105 == 2 * 3780 and BLOCKS > WINDOWS
    # of bytes only the weights: the block grid and the partial sums may
    # stay in on-chip memory, so the least time is the MXU's
    assert byts == 2 * 36 * 105
    peak = cells.peaks("TPU v5 lite")
    assert ops / peak["bf16_flops"] > byts / peak["hbm_bytes_per_s"]


def test_resize_costs_nothing_at_scale_one_and_taps_below():
    resize = cells.costs(KERNELS)["resize"]
    assert resize.cost(480, 640, ONE_LEVEL, PREC) == (0, 0)
    ops, byts = resize.cost(480, 640, {"scales": [1.0, 0.5],
                                       "shape_bucket": 32}, PREC)
    # halving: the antialiased triangle spans 2 input samples either
    # side of each output, 4 taps, 3 at the first and last output of
    # each axis; rows pass over 240 x 640 outputs, columns pass over
    # 240 x 320 outputs, 2 operations a tap
    rows_taps, cols_taps = 4 * 240 - 2, 4 * 320 - 2
    assert ops == 2 * (rows_taps * 640 + cols_taps * 240)
    assert byts == 4 * (480 * 640 + 240 * 320)


def test_window_geometry_of_the_vga_pyramid():
    cfg = cells.config("vga_perf")["detector"]
    lv = reference.levels(480, 640, cfg["scales"], cfg["shape_bucket"])
    assert len(lv) == 6 and (lv[0].sph, lv[0].spw) == (44, 72)
    assert sum(v.sph * v.spw for v in lv) == 6741
    assert len(reference.window_boxes(480, 640, cfg["scales"], 32)) == 6741


@pytest.mark.parametrize("src,dst", [(480, 384), (640, 512), (1088, 146),
                                     (7, 3), (5, 9)])
def test_resize_taps_match_the_documented_filter(src, dst):
    import jax
    import jax.numpy as jnp
    want = np.asarray(jax.image.resize(jnp.eye(src, dtype=jnp.float32),
                                       (dst, src), "linear"))
    idx, wts = reference.resize_taps(src, dst)
    got = np.zeros((dst, src))
    np.add.at(got, (np.repeat(np.arange(dst), idx.shape[1]), idx.ravel()),
              wts.ravel())
    np.testing.assert_allclose(got, want, atol=1e-5)
