"""Multi-scale sliding-window human detector -- device-resident end-to-end.

The paper's hardware detects a single fixed 130x66 window; multi-window /
multi-resolution detection is listed as "future development" (§VI). This
module is that future development, built TPU-natively on the staged HOG
pipeline (core/stages.py):

  * Block normalization (eq. 5) is *window-independent*, so the scene's
    normalized block grid is computed ONCE (dense layout, any backend:
    ref | kernel | fused) and shared by every window. A window's SVM
    score is a dot product between its 15x7 block patch and the weight
    tensor -- the whole score map is one valid-mode convolution that XLA
    lowers to MXU matmuls.
  * Multi-scale is ONE compiled program per frame-shape bucket: frames
    are padded up to a bucket shape, the image pyramid + dense scoring
    for every scale is unrolled inside a single jit, thresholding and
    top-k run device-side, and NMS is a vectorized matrix-IoU greedy
    pass (fori_loop over the fixed top-k, O(K) vector work per step --
    no O(N^2) host Python loop, no per-frame retrace).
  * Only box DECODE stays on host: top-k indices select rows of a
    static per-bucket box table (pure geometry, precomputed in numpy).

`detect()` keeps the original host-facing contract (list of dicts) with
one deliberate change: the device program considers at most
`max_detections` top-scoring candidates per frame (fixed K keeps the
shapes static); saturating that cap emits a RuntimeWarning.
`FrameDetector` is the reusable device-program handle the serving layer
uses (serve/engine.py full-frame requests).

The BATCHED path (`detect_batch`) vmaps the same per-bucket pyramid
program over a stacked (B, H, W) frame batch: one jit per
(true-shape, shape-bucket, B) tuple, per-frame top-k and NMS still
device-side, one host sync for the whole batch. The batch axis runs as a scanned map of
`batch_chunk`-wide vmapped chunks (chunk 1 = frame-at-a-time scan, the
fast layout on the CPU host; chunk >= B = one wide vmap for real
accelerators). Frames in a batch may differ in true size as long as
they share a padded bucket (the per-frame (h, w) mask rides along the
batch axis). This is the hot path the video/tracking layer
(core/video.py) and the serving microbatcher (serve/engine.py) sit on.

The SHARDED path layers multi-device data parallelism on top of the
batched one: with `cfg.data_parallel != 1` the frame batch is laid over
the 'data' axis of a 1-D device mesh (launch/mesh.py:make_detection_mesh)
and the per-bucket program runs under shard_map -- each device executes
the same scan-vs-vmap schedule on its local B/n_devices sub-batch, with
pyramid, scoring, top-k and NMS all device-local (no cross-device
collectives, no host round-trips). Batches that do not divide the mesh
are padded with zero frames whose true-size mask is (0, 0), so every
window of a pad frame fails the inside-frame test and decodes to an
empty result; the pad rows are sliced off before the Detections is
built. Per-frame results are byte-identical to the single-device path
(tests/test_sharded.py pins this per backend/numerics mode).

The TILED path adds intra-frame parallelism on top of both: with
`cfg.frame_parallel != 1`, frames whose padded bucket clears
`frame_parallel_min_area` split ONE frame's pyramid work over the
'tile' axis of a ('data', 'tile') mesh -- by row-slab of each scale's
score grid (exact descriptor halo) or by whole scale-groups
(cfg.tile_mode). Each tile emits a local top-k; an exact union re-rank
(core/tiling.py:merge_topk) plus one nms_keep pass reproduce the
untiled result (byte for byte on CPU host devices, tests/test_tiled.py;
the same boxes on TPU chips, DESIGN.md §11), taking worst-case
single-frame latency from one chip to all of them. A single tiled frame
reaches the chips as row bands, one a chip (`_banded_put`).

UPLOADS. Every RGB frame crosses to the chip as interleaved rows,
(..., h, w*3), and every program turns it into gray by its static shape
(`_luma`): a minor dimension of 3 made a v5e's host-to-chip copy four
times slower. `frame_uploads()` counts the frames handed over by layout.

STAGES. Each stage of the frame program is wrapped in `jax.named_scope`
where it is defined, so every variant (single, batched, sharded, tiled)
carries the names in its HLO metadata (`op_name`) and in the device
trace: `gray` (_prep_frame), `resize` (each pyramid level), `hog`
(scene_blocks), `score/matmul` and `score/collate` (score_blocks),
`select` (threshold and top-k) and `nms` (nms_keep); the tiled programs
add `merge` (the gather and re-rank of the tiles' top-k lists). The
scopes change metadata only, not the operations or their names.
`op_scopes(batch)` reads them back from the compiled text of the
programs dispatched.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
import time
from functools import lru_cache, partial
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.core import numerics as N, quant
from repro.core.hog import HOGConfig, PAPER_HOG, grayscale
from repro.core.stages import dense_blocks
from repro.core.svm import SVMParams
from repro.obs import spans

Array = jax.Array


@lru_cache(maxsize=1)
def _donate() -> bool:
    """Whether the per-bucket programs request frame-buffer donation.

    jax ignores donation on the CPU backend (with a warning), so
    donate_argnums is only requested where it can take effect. On TPU
    the frame/gray buffers of the per-bucket programs are donated: a 4K
    f32 frame batch is the largest allocation on the hot path and
    reusing it as the program's scratch removes the double-buffering
    high-water mark. Evaluated lazily (first detect call, cached) --
    `jax.default_backend()` initializes the backend, which must not
    happen at import time, before the user picks a platform."""
    return jax.default_backend() != "cpu"


@dataclasses.dataclass(frozen=True)
class DetectorConfig:
    hog: HOGConfig = PAPER_HOG
    scales: Tuple[float, ...] = (1.0, 0.8, 0.64)
    score_threshold: float = 0.0          # sign(D(x)) per eq. (7)
    nms_iou: float = 0.3
    max_detections: int = 0               # device top-k size (K).
    #   0 = AUTO: K = min(n, max(256, ceil(n / 256))) grows with the
    #   window count n, so UHD-sized grids don't silently saturate
    #   while every pre-UHD bucket keeps the historical K=256 (n stays
    #   < 65536 there). n > 0 pins K exactly (the legacy behavior).
    backend: str = "ref"                  # stage backend for dense HOG
    shape_bucket: int = 32                # frames pad up to multiples of this
    batch_chunk: int = 0                  # detect_batch vmap width: frames
    #   per vmapped chunk inside the scanned batch program. 0 = AUTOTUNE:
    #   probe scan-vs-vmap per (bucket, B) at first use (min-of-k on
    #   synthetic frames) and cache the winner -- see autotune_report().
    #   1 = scan the batch frame-by-frame (best locality on CPU hosts);
    #   >= B = one fully vectorized vmap step (wide accelerators).
    #   Under data_parallel != 1 the chunk applies to each device's
    #   LOCAL sub-batch.
    data_parallel: int = 1                # devices on the batch axis:
    #   1 = single-device (the pre-sharding path, bit-for-bit),
    #   0 = every visible device, n > 1 = exactly n devices (ValueError
    #   when the host has fewer). detect_batch pads B up to a multiple
    #   of the mesh size with masked-out zero frames and runs the
    #   per-bucket program under shard_map over the 'data' mesh axis
    #   (launch/mesh.py:make_detection_mesh) -- see DESIGN.md §10.
    frame_parallel: int = 1               # devices tiling ONE frame's
    #   pyramid (intra-frame parallelism): 1 = off, 0 = every device
    #   left over after the batch axis (device_count // data_parallel),
    #   n > 1 = exactly n tiles. Frames whose padded bucket area
    #   (ph * pw) >= frame_parallel_min_area route to the tiled path:
    #   per-tile local top-k under shard_map over the 'tile' mesh axis
    #   (launch/mesh.py:make_tiled_mesh), then an exact union re-rank +
    #   one NMS pass -- the untiled program's boxes (core/tiling.py,
    #   DESIGN.md §11). Composes with data_parallel as
    #   a 2-D (data, tile) schedule for batches.
    tile_mode: str = "slab"               # intra-frame decomposition:
    #   "slab" = row-slabs of each scale's score grid (halo recompute,
    #   balanced rows), "scale" = whole pyramid scales greedily balanced
    #   over tiles by window count (no halo, coarser balance).
    frame_parallel_min_area: int = 0      # only frames with bucket area
    #   ph * pw >= this use the tiled path; 0 = every frame (when
    #   frame_parallel resolves > 1). The "uhd" preset sets 1280*720 so
    #   small frames keep the cheaper untiled program.
    pyramid_resize: str = "matmul"        # pyramid resize arithmetic:
    #   "matmul" = dense two-matmul form (the PR 1-5 default; O(src)
    #   per output pixel), "banded" = the SAME interpolation weights
    #   applied as <= ~4 fixed-order multiply-adds per output pixel
    #   (core/tiling.py:resize_banded; O(taps) -- the UHD-fast form,
    #   and per-element, hence exactly tiling-invariant). The two modes
    #   differ only in float accumulation order (final-ulp score
    #   deltas); each mode is self-consistent, and tiled == untiled
    #   bitwise WITHIN either mode.
    class_thresholds: Tuple[float, ...] = ()  # per-head score thresholds
    #   for MULTI-HEAD scoring (svm["w"] of shape (K, F), see
    #   score_blocks): entry k gates head k's windows. () = every head
    #   uses score_threshold. Length must equal K when the program is
    #   traced with stacked params; baked static (part of the program
    #   cache key) exactly like score_threshold.


def scene_blocks(gray: Array, cfg: HOGConfig,
                 backend: str = "ref") -> Array:
    """Whole-scene normalized block grid: (H, W) -> (BH, BW, 36).

    Thin view over the dense layout of the staged pipeline; `backend`
    selects ref (pure jnp) or the dense-grid Pallas kernel/fused
    implementations (kernels/dense_grad_hist.py et al.).
    """
    with jax.named_scope("hog"):
        return dense_blocks(gray, cfg, backend)


def score_blocks(blocks: Array, w: Array, b: Array,
                 cfg: HOGConfig = PAPER_HOG, use_kernel: bool = False) -> Array:
    """Score the dense block grid: (BH, BW, 36) -> (PH, PW).

    score[i, j] = <blocks[i:i+15, j:j+7, :], W> + b. Instead of a
    15x7x36 conv (which XLA:CPU runs ~6x slower than the equivalent
    matmul), the window sum factors through the per-offset partial
    products: ONE (BH*BW, 36) @ (36, 105) matmul computes every block
    position's contribution to each of the 105 window offsets on the
    MXU, then 105 shifted adds collate the score map. bf16 block
    descriptors (the perf preset) feed the matmul directly with f32
    accumulation. `use_kernel` routes the matmul through the Pallas
    kernel (kernels/svm_matmul.py:score_matmul) -- the MXU-explicit
    path used by the kernel/fused backends.

    MULTI-HEAD: `w` of shape (K, F) with `b` of shape (K,) scores K
    stacked SVM heads in the SAME matmul, widened to (36, 105*K) --
    near-free on the MXU, since the reduction dim (36) and the M rows
    are unchanged. Returns (K, PH, PW). Per-column arithmetic is
    untouched by the widening: each output column is an independent
    36-element dot product (int8 mode is exact integer accumulation;
    float modes keep per-column accumulation order), so head k's plane
    is byte-identical to scoring head k alone (tests/test_multihead.py
    pins this per numerics mode).
    """
    bh, bw = cfg.blocks_hw                              # 15, 7
    BH, BW, bd = blocks.shape
    ph, pw = BH - bh + 1, BW - bw + 1
    flat = blocks.reshape(BH * BW, bd)
    if w.ndim == 2:                                     # stacked (K, F) heads
        return _score_blocks_multi(flat, w, b, cfg, use_kernel,
                                   BH, BW, ph, pw)
    with jax.named_scope("score/matmul"):
        contrib = _score_contrib(flat, w, cfg, use_kernel, bh * bw)
    with jax.named_scope("score/collate"):
        contrib = contrib.reshape(BH, BW, bh * bw)
        out = jnp.zeros((ph, pw), jnp.float32)
        for di in range(bh):                            # static 15x7 unroll
            for dj in range(bw):
                out = out + contrib[di:di + ph, dj:dj + pw, di * bw + dj]
        return out + b


def _score_contrib(flat: Array, w: Array, cfg: HOGConfig, use_kernel: bool,
                   offsets: int) -> Array:
    """The scoring matmul: (BH*BW, 36) block rows against the (36,
    offsets) per-offset weights -> (BH*BW, offsets) partial sums, with
    offsets 105, or 105*K head-major columns for K stacked heads."""
    bd = flat.shape[-1]
    if N.spec_for(cfg).quantized:
        # fixed mode: the incoming grid is dequantized int8 (exactly
        # q * scale, numerics.finish_blocks), so requantizing recovers
        # the codes EXACTLY -- q/127 * max has relative error ~2^-22,
        # far inside rint's 0.5 margin -- and the one array that flowed
        # through every stage/tile/shard seam stays the public contract.
        # int8 x int8 -> int32 is exact, so scores are byte-identical
        # under any blocking; the rank-1 f32 rescale is elementwise with
        # a fixed multiply order (quant.rescale_scores).
        q, s_rows = quant.quantize_blocks(flat)
        wt = w.reshape(offsets, bd).T.astype(jnp.float32)
        wq, s_cols = quant.quantize_weight_columns(wt)
        if use_kernel:
            from repro.kernels.svm_matmul import score_matmul_int8
            ci = score_matmul_int8(q, wq)
        else:
            ci = jax.lax.dot_general(
                q, wq, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        return quant.rescale_scores(ci, s_rows, s_cols)
    wt = w.reshape(offsets, bd).T.astype(flat.dtype)        # (36, 105)
    if use_kernel:
        from repro.kernels.svm_matmul import score_matmul
        return score_matmul(flat, wt)
    return jax.lax.dot_general(
        flat, wt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _score_blocks_multi(flat: Array, w: Array, b: Array, cfg: HOGConfig,
                        use_kernel: bool, BH: int, BW: int,
                        ph: int, pw: int) -> Array:
    """K stacked heads through one widened matmul: (BH*BW, 36) @
    (36, 105*K) -> (K, PH, PW). Weight columns are laid out head-major
    ((k, offset) = k*105 + offset), so column k*105+o carries exactly
    the column head k's single-head matmul would have at offset o --
    the per-column int8 quantization scales, and with them the int8
    codes, match the per-head path code for code. The shifted-add
    collate runs the same static 15x7 unroll per head plane, in the
    same accumulation order as the single-head path."""
    bh, bw = cfg.blocks_hw
    K = w.shape[0]
    with jax.named_scope("score/matmul"):
        # (K, bh*bw, bd) -> (bd, K*bh*bw), head-major columns
        contrib = _score_contrib(flat, w, cfg, use_kernel, K * bh * bw)
    with jax.named_scope("score/collate"):
        contrib = contrib.reshape(BH, BW, K, bh * bw)
        out = jnp.zeros((K, ph, pw), jnp.float32)
        for di in range(bh):                            # static 15x7 unroll
            for dj in range(bw):
                out = out + jnp.moveaxis(
                    contrib[di:di + ph, dj:dj + pw, :, di * bw + dj], 2, 0)
        return out + b[:, None, None]


@partial(jax.jit, static_argnames=("cfg", "backend"))
def score_map(gray: Array, w: Array, b: Array,
              cfg: HOGConfig = PAPER_HOG, backend: str = "ref") -> Array:
    """Dense SVM score map at cell (8-px) stride. gray: (H, W) -> (PH, PW)."""
    blocks = scene_blocks(gray, cfg, backend)           # (BH, BW, 36)
    return score_blocks(blocks, w, b, cfg, use_kernel=(backend != "ref"))


# ------------------------------------------------------------------- NMS

def matrix_iou(a: Array, b: Array) -> Array:
    """Pairwise IoU. a: (N, 4), b: (M, 4) as (y0, x0, y1, x1) -> (N, M)."""
    y0 = jnp.maximum(a[:, None, 0], b[None, :, 0])
    x0 = jnp.maximum(a[:, None, 1], b[None, :, 1])
    y1 = jnp.minimum(a[:, None, 2], b[None, :, 2])
    x1 = jnp.minimum(a[:, None, 3], b[None, :, 3])
    inter = jnp.maximum(y1 - y0, 0.0) * jnp.maximum(x1 - x0, 0.0)
    area_a = (a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1])
    area_b = (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
    return inter / jnp.maximum(area_a[:, None] + area_b[None, :] - inter,
                               1e-9)


def nms_keep(boxes: Array, scores: Array, iou_thr: float) -> Array:
    """Vectorized greedy NMS, device-resident.

    boxes (K, 4) must be sorted by descending score (lax.top_k order);
    entries with score == -inf are invalid and never kept. The IoU
    matrix is computed once; the greedy dependency runs as a fori_loop
    over the FIXED K with O(K) vector work per step, so the whole pass
    stays on device with a static shape -- exact same keep set as the
    host greedy reference (tests/test_stages_detector.py).
    """
    k = boxes.shape[0]
    with jax.named_scope("nms"):
        iou = matrix_iou(boxes, boxes)
        valid = jnp.isfinite(scores)
        rank = jnp.arange(k)

        def body(i, keep):
            suppressed = jnp.any(keep & (iou[:, i] > iou_thr) & (rank < i))
            return keep.at[i].set(valid[i] & ~suppressed)

        return jax.lax.fori_loop(0, k, body, jnp.zeros((k,), bool))


def _nms(boxes: np.ndarray, scores: np.ndarray, iou_thr: float) -> List[int]:
    """Greedy NMS on host -- the O(N^2) Python reference the vectorized
    `nms_keep` is validated against. boxes: (N, 4) as (y0, x0, y1, x1)."""
    order = np.argsort(-scores)
    keep: List[int] = []
    while order.size:
        i = order[0]
        keep.append(int(i))
        if order.size == 1:
            break
        rest = order[1:]
        yy0 = np.maximum(boxes[i, 0], boxes[rest, 0])
        xx0 = np.maximum(boxes[i, 1], boxes[rest, 1])
        yy1 = np.minimum(boxes[i, 2], boxes[rest, 2])
        xx1 = np.minimum(boxes[i, 3], boxes[rest, 3])
        inter = np.maximum(0, yy1 - yy0) * np.maximum(0, xx1 - xx0)
        a_i = (boxes[i, 2] - boxes[i, 0]) * (boxes[i, 3] - boxes[i, 1])
        a_r = (boxes[rest, 2] - boxes[rest, 0]) * (boxes[rest, 3] - boxes[rest, 1])
        iou = inter / np.maximum(a_i + a_r - inter, 1e-9)
        order = rest[iou <= iou_thr]
    return keep


# -------------------------------------------- per-bucket compiled program

def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b if b > 1 else a


def _resolve_k(cfg: DetectorConfig, n: int) -> int:
    """Top-k size for a program with n window positions. Auto mode
    (max_detections == 0) scales K with the grid so big frames don't
    silently saturate: K = max(256, ceil(n / 256)) clamped to n --
    exactly 256 for every bucket below ~65k windows (the historical
    constant), ~953 at 4K's 244k windows. An explicit max_detections
    pins K (legacy / memory-bound deployments)."""
    if cfg.max_detections:
        return min(cfg.max_detections, n)
    return min(n, max(256, -(-n // 256)))


@lru_cache(maxsize=256)
def _resize_weights(src: int, dst: int) -> np.ndarray:
    """(dst, src) row-weight matrix reproducing jax.image.resize's
    "linear" kernel (incl. its anti-aliasing taps when downscaling),
    extracted exactly by resizing the identity. Lets the pyramid
    resize run as two small matmuls -- same arithmetic as the
    gather-based resize but in MXU/BLAS form, ~30% faster on the CPU
    host and one fused op per axis on TPU."""
    import jax.image
    # first use may be inside a jit trace (resize_banded builds its tap
    # tables lazily from program bodies); escape it so the identity
    # resize runs eagerly and converts to a concrete array
    with jax.ensure_compile_time_eval():
        eye = jnp.eye(src, dtype=jnp.float32)
        return np.asarray(jax.image.resize(eye, (dst, src), "linear"))


def _frame_hw(shape) -> Tuple[int, int]:
    """True (h, w) of a frame shape; raises on anything that is not an
    (H, W) gray or (H, W, 3) RGB frame."""
    if len(shape) == 3 and shape[-1] == 3:
        return int(shape[0]), int(shape[1])
    if len(shape) == 2:
        return int(shape[0]), int(shape[1])
    raise ValueError(
        f"expected an (H, W) gray or (H, W, 3) RGB frame, got shape "
        f"{tuple(shape)}")


class DecodeTables:
    """Static host-side decode geometry of one compiled program: the
    flattened box/scale tables and the top-k size. Built once per
    FrameProgram; identity hash/eq on purpose so it can ride as the
    aux data of the api-layer Detections pytree."""

    __slots__ = ("boxes", "scales", "k")

    def __init__(self, boxes: np.ndarray, scales: np.ndarray, k: int):
        self.boxes = boxes             # (N, 4) window boxes, frame coords
        self.scales = scales           # (N,) nominal pyramid scale per row
        self.k = k                     # top-k size


@dataclasses.dataclass(frozen=True)
class FrameProgram:
    """One compiled multi-scale program + its static decode tables."""

    fn: "jax.stages.Wrapped"       # (gray_pad, w, b, hw) -> (scores, idx, keep)
    boxes: np.ndarray              # (N, 4) window boxes in frame coords
    scales: np.ndarray             # (N,) nominal pyramid scale per row
    n_positions: int               # N: total window positions, all scales
    k: int                         # top-k size
    per_scale: Tuple[Tuple[float, int, int], ...] = ()
    #                (scale, score-map PH, score-map PW) per pyramid level
    raw: "Callable" = None         # unjitted fn -- what detect_batch vmaps
    tables: "DecodeTables" = None  # the boxes/scales/k above, as one holder


@lru_cache(maxsize=64)
def _frame_program(ph: int, pw: int, cfg: DetectorConfig) -> FrameProgram:
    """Build the compiled program for padded frame shape (ph, pw).

    Everything shape-dependent is static here: the per-scale pyramid
    shapes, the flattened box table (pure geometry -> numpy, baked as a
    jit constant for the device-side gather), and K.
    """
    hcfg = cfg.hog
    specs: List[Tuple[int, int, float]] = []
    for s in cfg.scales:
        sh, sw = int(ph * s), int(pw * s)
        if sh >= hcfg.window_h and sw >= hcfg.window_w:
            specs.append((sh, sw, s))

    cell = hcfg.cell
    wbh, wbw = hcfg.blocks_hw                       # 15, 7 window blocks
    box_rows, scale_rows = [], []
    per_scale = []
    for sh, sw, s in specs:
        gh, gw = (sh - 2) // cell * cell, (sw - 2) // cell * cell
        sbh, sbw = gh // cell - hcfg.block + 1, gw // cell - hcfg.block + 1
        sph, spw = sbh - wbh + 1, sbw - wbw + 1     # score-map shape
        per_scale.append((s, sph, spw))
        # exact per-axis resize factor of the padded frame
        sy, sx = sh / ph, sw / pw
        ys, xs = np.mgrid[0:sph, 0:spw].astype(np.float64)
        y0, x0 = ys * cell / sy, xs * cell / sx
        boxes = np.stack([y0, x0, y0 + hcfg.window_h / sy,
                          x0 + hcfg.window_w / sx], axis=-1)
        box_rows.append(boxes.reshape(-1, 4).astype(np.float32))
        scale_rows.append(np.full(sph * spw, s, np.float32))

    if not box_rows:
        empty4 = np.zeros((0, 4), np.float32)
        empty1 = np.zeros((0,), np.float32)
        return FrameProgram(None, empty4, empty1, 0, 0, (),
                            tables=DecodeTables(empty4, empty1, 0))

    boxes_tab = np.concatenate(box_rows)
    scale_tab = np.concatenate(scale_rows)
    n = len(boxes_tab)
    k = _resolve_k(cfg, n)
    boxes_dev = jnp.asarray(boxes_tab)

    if cfg.pyramid_resize not in ("matmul", "banded"):
        raise ValueError(
            f"DetectorConfig.pyramid_resize={cfg.pyramid_resize!r}: "
            f"expected 'matmul' or 'banded'")
    banded = cfg.pyramid_resize == "banded"
    # per-scale resize as two matmuls (exact jax.image.resize weights,
    # baked as jit constants); the full-res gray is shared, so the
    # grayscale conversion + pyramid schedule run once per frame and
    # every scale's resize->stages->score chain hangs off one buffer.
    # Under pyramid_resize="banded" the same weights apply in band form
    # instead (tiling.resize_banded builds its own tables).
    resize_w = {} if banded else \
        {(sh, sw): (jnp.asarray(_resize_weights(ph, sh)),
                    jnp.asarray(_resize_weights(pw, sw)))
         for sh, sw, _ in specs if (sh, sw) != (ph, pw)}

    def fn(gray: Array, w: Array, b: Array, hw: Array):
        from repro.core.tiling import resize_banded
        multi = w.ndim == 2            # stacked (K, F) heads, static
        parts = []
        for sh, sw, _ in specs:
            with jax.named_scope("resize"):
                if (sh, sw) == (ph, pw):
                    g = gray
                elif banded:
                    g = resize_banded(gray, sh, sw)
                else:
                    wy, wx = resize_w[(sh, sw)]
                    g = (wy @ gray) @ wx.T
            sm = score_map(g, w, b, hcfg, cfg.backend)
            parts.append(sm.reshape(sm.shape[0], -1) if multi
                         else sm.reshape(-1))
        if multi:
            kh = int(w.shape[0])
            if cfg.class_thresholds and len(cfg.class_thresholds) != kh:
                raise ValueError(
                    f"class_thresholds has {len(cfg.class_thresholds)} "
                    f"entries but the stacked params carry {kh} heads")
        with jax.named_scope("select"):
            scores = parts[0] if len(parts) == 1 \
                else jnp.concatenate(parts, axis=-1)
            # windows must lie inside the TRUE (unpadded) frame and
            # clear the score threshold; both masks applied device-side
            inside = (boxes_dev[:, 2] <= hw[0] + 1e-4) \
                & (boxes_dev[:, 3] <= hw[1] + 1e-4)
            if multi:
                thr = jnp.asarray(cfg.class_thresholds
                                  or (cfg.score_threshold,) * kh,
                                  jnp.float32)
                valid = inside[None, :] & (scores > thr[:, None])
            else:
                valid = inside & (scores > cfg.score_threshold)
            top, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
            cand = boxes_dev[idx]
            n_valid = jnp.sum(valid, axis=-1)
        if multi:
            keep = jax.vmap(nms_keep, in_axes=(0, 0, None))(
                cand, top, cfg.nms_iou)
        else:
            keep = nms_keep(cand, top, cfg.nms_iou)
        return top, idx, keep, n_valid

    return FrameProgram(jax.jit(fn), boxes_tab, scale_tab, n, k,
                        tuple(per_scale), fn,
                        tables=DecodeTables(boxes_tab, scale_tab, k))


def _luma(frame: Array, w: int) -> Array:
    """The float32 gray plane of a frame of rows `w` pixels wide, read
    by its static shape: a last dim of 3*w is interleaved RGB rows (the
    layout every RGB frame travels in, `_upload`), one of w is gray."""
    if frame.shape[-1] == 3 * w:
        return grayscale(frame.reshape(frame.shape[:-1] + (w, 3)))
    if frame.shape[-1] == w:
        return frame.astype(jnp.float32)
    raise ValueError(
        f"expected rows of {w} gray or {3 * w} interleaved RGB values, "
        f"got shape {tuple(frame.shape)}")


def _prep_frame(frame: Array, h: int, w: int, ph: int, pw: int) -> Array:
    """In-program frame prep shared by every frame program: grayscale
    of an (h, w*3) interleaved RGB frame, or an (h, w) gray one as it
    is (`_luma`), then edge-pad to the bucket. Runs INSIDE the jit so
    uint8 stays on the wire, XLA fuses the luma into the gradient
    stage, and the conversion happens once per frame -- every pyramid
    scale then resizes the one gray buffer."""
    with jax.named_scope("gray"):
        g = _luma(frame, w)
        if (ph, pw) != (h, w):
            g = jnp.pad(g, ((0, ph - h), (0, pw - w)), mode="edge")
        return g


@lru_cache(maxsize=64)
def _single_fn(h: int, w: int, ph: int, pw: int,
               cfg: DetectorConfig) -> "jax.stages.Wrapped":
    """The per-frame program with grayscale + pad fused in: raw frame
    (h, w*3) interleaved RGB or (h, w) gray -> (top, idx, keep,
    n_valid). One jit per (true-shape, bucket) pair; the frame buffer is
    donated on accelerators (the program owns it -- detect_raw hands
    over a fresh buffer)."""
    base = _frame_program(ph, pw, cfg)
    if base.raw is None:
        return None

    def fn(frame: Array, wv: Array, bv: Array, hw: Array):
        return base.raw(_prep_frame(frame, h, w, ph, pw), wv, bv, hw)

    return jax.jit(fn, donate_argnums=(0,) if _donate() else ())


@lru_cache(maxsize=64)
def _batch_fn(h: int, w: int, ph: int, pw: int, batch: int,
              cfg: DetectorConfig, donate: bool = False
              ) -> "jax.stages.Wrapped":
    """The per-bucket program vmapped over a stacked frame batch.

    One jit per (true-shape, shape-bucket, B) tuple: raw frames
    (B, h, w*3) interleaved RGB or (B, h, w) gray and the true (h, w)
    mask are batched, SVM params broadcast. Grayscale conversion and
    edge-pad to the bucket run INSIDE the program (uint8 stays on the
    wire; XLA fuses the luma into the gradient stage), so the host does
    zero per-frame prep dispatches. Keying on the true shape is the
    price of the fused prep: uniform batches of DIFFERENT true shapes
    in one bucket compile separate programs (bounded by the lru cache
    and, in practice, by the handful of camera geometries a deployment
    sees); mixed-shape batches take the pre-padded host path, which
    reuses the single (bucket, B) program. The batch axis is mapped in
    `cfg.batch_chunk`-wide vmapped chunks (lax.map): chunk 1 scans
    frame-by-frame (keeps each frame's pyramid cache-resident on CPU
    hosts), chunk >= B is one
    fully vectorized vmap step (wide accelerators); cfg.batch_chunk==0
    resolves the choice by measurement BEFORE this cache is consulted
    (_autotune_chunk). `donate` hands the frame-stack buffer to the
    program on accelerators; the autotune probe passes False so its
    reused probe buffers stay valid. Returns None when the bucket is
    too small for even one window (same as the single path).
    """
    base = _frame_program(ph, pw, cfg)
    if base.raw is None:
        return None

    def one(frame: Array, wv: Array, bv: Array, hw: Array):
        return base.raw(_prep_frame(frame, h, w, ph, pw), wv, bv, hw)

    donate_kw = dict(donate_argnums=(0,)) if donate else {}
    return jax.jit(_chunked_schedule(one, max(1, cfg.batch_chunk), batch),
                   **donate_kw)


def _chunked_schedule(one: Callable, chunk: int, batch: int) -> Callable:
    """The scan-vs-vmap batch schedule shared by the single-device
    program and each device of the sharded one: chunk >= batch is one
    wide vmap, otherwise a lax.map scan of chunk-wide vmapped steps
    (chunk 1 = plain frame-by-frame scan). ONE definition on purpose:
    the sharded path's byte-identity with the single-device path rests
    on both running exactly this schedule."""
    if chunk >= batch:
        return jax.vmap(one, in_axes=(0, None, None, 0))

    def fn(frames_b: Array, wv: Array, bv: Array, hw_b: Array):
        return jax.lax.map(lambda fh: one(fh[0], wv, bv, fh[1]),
                           (frames_b, hw_b),
                           batch_size=chunk if chunk > 1 else None)

    return fn


# ------------------------------------------------- sharded batch program

@lru_cache(maxsize=8)
def _detection_mesh(dp: int):
    """The 1-D 'data' mesh sharded programs run over, built once per
    device count (Mesh construction touches jax device state, so it is
    deferred to first sharded call and cached)."""
    from repro.launch.mesh import make_detection_mesh
    return make_detection_mesh(dp)


def _resolve_dp(cfg: DetectorConfig) -> int:
    """Resolve cfg.data_parallel to a concrete device count.

    1 stays 1 without initializing the backend (the single-device path
    must not pay a device query); 0 means every visible device; an
    explicit n > jax.device_count() is a config error, reported with
    the same clear message as the mesh builders."""
    dp = cfg.data_parallel
    if dp == 1:
        return 1
    n = jax.device_count()
    if dp == 0:
        return n
    if not 1 <= dp <= n:
        raise ValueError(
            f"DetectorConfig.data_parallel={dp}: the host has {n} "
            f"visible device(s) (jax.devices()); use 0 (= all) or a "
            f"value in [1, {n}]")
    return dp


@lru_cache(maxsize=64)
def _sharded_batch_fn(h: int, w: int, ph: int, pw: int, batch: int,
                      dp: int, cfg: DetectorConfig, donate: bool = False
                      ) -> "jax.stages.Wrapped":
    """The per-bucket program sharded over the 'data' mesh axis.

    `batch` is the PADDED global batch (a multiple of `dp`; the caller
    pads with zero frames masked out via hw = (0, 0)). Each device runs
    the same chunked scan-vs-vmap schedule `_batch_fn` would run, on
    its local batch/dp sub-batch -- shard_map with data-sharded frames
    and hw mask, replicated SVM params, and data-sharded outputs. No
    collective touches the hot path: frames are independent, so the
    program is embarrassingly parallel and per-frame results stay
    byte-identical to the single-device path. One jit per (true-shape,
    bucket, B, dp) tuple. Returns None when the bucket is too small for
    even one window (same as the single/batched paths).
    """
    base = _frame_program(ph, pw, cfg)
    if base.raw is None:
        return None
    assert batch % dp == 0, (batch, dp)
    local = batch // dp
    mesh = _detection_mesh(dp)

    def one(frame: Array, wv: Array, bv: Array, hw: Array):
        return base.raw(_prep_frame(frame, h, w, ph, pw), wv, bv, hw)

    local_fn = _chunked_schedule(one, max(1, cfg.batch_chunk), local)
    data = P("data")
    # check_vma=False: pallas_call (kernel/fused backends) has no
    # replication rule, and the program is embarrassingly parallel --
    # no collectives for the checker to validate anyway
    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=(data, P(), P(), data),
                   out_specs=(data, data, data, data),
                   check_vma=False)
    donate_kw = dict(donate_argnums=(0,)) if donate else {}
    return jax.jit(fn, **donate_kw)


# --------------------------------------------- intra-frame tiled program
# The frame-parallel path (DESIGN.md §11): one frame's pyramid work laid
# over the 'tile' axis of a (data, tile) mesh. Each tile runs a LOCAL
# program over the window positions it owns -- a row-slab of every
# scale's score grid (with an exact descriptor halo) or a whole
# scale-group -- and produces its local top-k; tiling.merge_topk then
# re-ranks the union exactly and ONE nms_keep pass over the merged list
# reproduces the untiled keep set, so results are byte-identical to the
# untiled program per backend/numerics mode on CPU host devices
# (tests/test_tiled.py); on TPU chips the boxes agree and the float
# scores may not (DESIGN.md §11).


def _resolve_fp(cfg: DetectorConfig, dp: Optional[int] = None) -> int:
    """Resolve cfg.frame_parallel to a concrete tile count. 1 stays 1
    without initializing the backend (the untiled path must not pay a
    device query); 0 means every device left over after the batch axis
    (device_count // data_parallel, at least 1); an explicit n must fit
    the host together with the data axis."""
    fp = cfg.frame_parallel
    if fp == 1:
        return 1
    if dp is None:
        dp = _resolve_dp(cfg)
    n = jax.device_count()
    if fp == 0:
        return max(1, n // dp)
    if fp < 1 or dp * fp > n:
        raise ValueError(
            f"DetectorConfig.frame_parallel={fp}: with data_parallel="
            f"{dp} the host's {n} visible device(s) allow at most "
            f"{max(1, n // dp)} tiles; use 0 (= all remaining) or a "
            f"value in [1, {max(1, n // dp)}]")
    return fp


@lru_cache(maxsize=8)
def _tile_mesh(dp: int, fp: int):
    """The 2-D ('data', 'tile') mesh tiled programs run over (deferred
    + cached like _detection_mesh)."""
    from repro.launch.mesh import make_tiled_mesh
    return make_tiled_mesh(dp, fp)


@lru_cache(maxsize=64)
def _tile_local_fn(ph: int, pw: int, fp: int,
                   cfg: DetectorConfig) -> Optional[Callable]:
    """One tile's local program: (gray_pad, w, b, hw) -> (top, idx,
    n_valid_local), where top/idx are the tile's LOCAL top-k over the
    global K (scores descending, -inf padded; idx = global flat window
    index, n for phantom rows) and the tile id comes from
    lax.axis_index('tile') -- one SPMD program for all tiles.

    tile_mode="slab": every scale is split into row-slabs of its score
    grid. A tile owning `slab` score rows computes hs = (slab + wbh +
    block - 2) * cell + 2 scaled-pixel rows starting at its cell-aligned
    offset d * slab * cell -- the (wbh + block - 2) cell-row descriptor
    halo plus the 2-px gradient border -- so every owned descriptor is
    built from exactly the pixels the untiled program uses. The resize
    tables (band or matmul row-weights) are zero-extended so the last
    tile's overhang computes exact zeros, and overhang score rows are
    masked to (-inf, idx=n) phantoms.

    tile_mode="scale": pyramid scales are greedily balanced over tiles
    by window count (tiling.scale_groups; groups may be empty) and each
    tile computes its scales FULL-frame with the exact expressions the
    untiled program uses, via one lax.switch on the tile id.

    Box-identity of the merged result rests on the tiling invariance of
    the per-tile arithmetic: banded resize is per-element; the matmul
    resize runs the full untiled product per tile and slices only
    RESULT rows (shape-dependent GEMM blocking makes anything less
    non-bitwise, see the inline note); the dense HOG
    stages are per-cell/per-block local; and local lists keep ascending
    global index among equal scores (see tiling.merge_topk).
    """
    from repro.core import tiling
    base = _frame_program(ph, pw, cfg)
    if base.raw is None:
        return None
    hcfg = cfg.hog
    cell = hcfg.cell
    n, k = base.n_positions, base.k
    boxes_dev = jnp.asarray(base.boxes)
    thr = cfg.score_threshold
    banded = cfg.pyramid_resize == "banded"
    if cfg.tile_mode not in ("slab", "scale"):
        raise ValueError(
            f"DetectorConfig.tile_mode={cfg.tile_mode!r}: expected "
            f"'slab' or 'scale'")

    # per_scale is the untiled program's own geometry; rebuild each
    # scale's pixel shape and flat-index base from it so both paths
    # index the one box table identically
    specs = []
    off = 0
    for s, sph, spw in base.per_scale:
        sh, sw = int(ph * s), int(pw * s)
        specs.append((sh, sw, s, sph, spw, off))
        off += sph * spw
    assert off == n, (off, n)

    @jax.named_scope("select")
    def _finish(parts_s, parts_i, nv):
        s_all = parts_s[0] if len(parts_s) == 1 else jnp.concatenate(parts_s)
        i_all = parts_i[0] if len(parts_i) == 1 else jnp.concatenate(parts_i)
        if s_all.shape[0] < k:
            padn = k - s_all.shape[0]
            s_all = jnp.concatenate(
                [s_all, jnp.full((padn,), -jnp.inf, s_all.dtype)])
            i_all = jnp.concatenate(
                [i_all, jnp.full((padn,), n, jnp.int32)])
        top, pos = jax.lax.top_k(s_all, k)
        return top, i_all[pos], nv

    if cfg.tile_mode == "slab":
        plans = []
        for sh, sw, s, sph, spw, base_i in specs:
            slab = tiling.slab_rows(sph, fp)
            hs = tiling.slab_pixel_rows(slab, hcfg)
            # resize tables must cover the LAST tile's slab window;
            # rows past the scaled image are zero-weight (exact zeros)
            L = max(sh, (fp - 1) * slab * cell + hs)
            p = dict(sph=sph, spw=spw, base=base_i, slab=slab, hs=hs)
            if (sh, sw) == (ph, pw):
                p["mode"] = "direct"
                p["L"] = L
            elif banded:
                lo_r, w_r = tiling.extend_band(
                    *tiling.band_weights(ph, sh), L)
                p.update(mode="banded", lo_r=jnp.asarray(lo_r),
                         w_r=jnp.asarray(w_r),
                         col=(tiling.band_weights(pw, sw)
                              if sw != pw else None))
            else:
                # full-shape weights: the tile runs the EXACT untiled
                # matmul and slices output rows after (see `local`)
                p.update(mode="matmul", sh=sh, L=L,
                         wy=jnp.asarray(_resize_weights(ph, sh)),
                         wx=(jnp.asarray(_resize_weights(pw, sw))
                             if sw != pw else None))
            plans.append(p)

        def local(gray: Array, wv: Array, bv: Array, hw: Array):
            d = jax.lax.axis_index("tile")
            parts_s, parts_i = [], []
            nv = jnp.zeros((), jnp.int32)
            for p in plans:
                slab, hs, spw = p["slab"], p["hs"], p["spw"]
                poff = d * (slab * cell)        # cell-aligned pixel base
                with jax.named_scope("resize"):
                    if p["mode"] == "direct":
                        g_ext = jnp.pad(gray, ((0, p["L"] - ph), (0, 0)))
                        gs = jax.lax.dynamic_slice(g_ext, (poff, 0),
                                                   (hs, pw))
                    elif p["mode"] == "banded":
                        lo_loc = jax.lax.dynamic_slice(p["lo_r"], (poff,),
                                                       (hs,))
                        w_loc = jax.lax.dynamic_slice(
                            p["w_r"], (poff, 0), (hs, p["w_r"].shape[1]))
                        g_pad = jnp.pad(gray,
                                        ((0, p["w_r"].shape[1]), (0, 0)))
                        gs = tiling.band_rows(g_pad, lo_loc, w_loc)
                        if p["col"] is not None:
                            lo_c, w_c = p["col"]
                            gs = tiling.band_cols(
                                jnp.pad(gs, ((0, 0), (0, w_c.shape[1]))),
                                jnp.asarray(lo_c), jnp.asarray(w_c))
                    else:
                        # matmul resize is NOT sliceable on its reduction
                        # OR output rows pre-hoc: XLA picks GEMM blocking
                        # (and with it the fp32 accumulation order) from
                        # the operand shapes, so a (hs, ph) slice of wy can
                        # produce different low bits than the same rows of
                        # the full product. Run the untiled expression
                        # verbatim and slice the RESULT -- data movement
                        # only, bitwise by construction. Tiling then buys
                        # no resize savings in this mode (the banded mode
                        # is the performance path); it stays for parity.
                        gs = p["wy"] @ gray
                        if p["wx"] is not None:
                            gs = gs @ p["wx"].T
                        gs = jnp.pad(gs, ((0, p["L"] - p["sh"]), (0, 0)))
                        gs = jax.lax.dynamic_slice(
                            gs, (poff, 0), (hs, gs.shape[1]))
                smap = score_map(gs, wv, bv, hcfg, cfg.backend)
                rows = d * slab + jnp.arange(slab, dtype=jnp.int32)
                idx = (p["base"] + rows[:, None] * spw
                       + jnp.arange(spw, dtype=jnp.int32)[None, :]
                       ).reshape(-1)
                owned = jnp.repeat(rows < p["sph"], spw)
                bx = boxes_dev[idx]             # gather clamps overhang
                inside = (bx[:, 2] <= hw[0] + 1e-4) \
                    & (bx[:, 3] <= hw[1] + 1e-4)
                valid = owned & inside & (smap.reshape(-1) > thr)
                parts_s.append(jnp.where(valid, smap.reshape(-1), -jnp.inf))
                parts_i.append(jnp.where(owned, idx, n))
                nv = nv + jnp.sum(valid)
            return _finish(parts_s, parts_i, nv)

        return local

    # tile_mode == "scale": whole scales per tile, one switch branch
    # per tile; every branch pads to the same candidate count
    groups = tiling.scale_groups(base.per_scale, fp)
    pmax = max([k] + [sum(sph * spw for _, sph, spw in
                          (base.per_scale[i] for i in g)) for g in groups])
    rw = {} if banded else \
        {(sh, sw): (jnp.asarray(_resize_weights(ph, sh)),
                    jnp.asarray(_resize_weights(pw, sw)))
         for sh, sw, _, _, _, _ in specs if (sh, sw) != (ph, pw)}

    def make_branch(group):
        gspecs = [specs[i] for i in group]

        def branch(gray: Array, wv: Array, bv: Array, hw: Array):
            parts_s = []
            parts_i = []
            nv = jnp.zeros((), jnp.int32)
            for sh, sw, s, sph, spw, base_i in gspecs:
                # exact same per-scale expressions as the untiled fn
                with jax.named_scope("resize"):
                    if (sh, sw) == (ph, pw):
                        g = gray
                    elif banded:
                        g = tiling.resize_banded(gray, sh, sw)
                    else:
                        wy, wx = rw[(sh, sw)]
                        g = (wy @ gray) @ wx.T
                flat = score_map(g, wv, bv, hcfg, cfg.backend).reshape(-1)
                bx = boxes_dev[base_i:base_i + sph * spw]
                inside = (bx[:, 2] <= hw[0] + 1e-4) \
                    & (bx[:, 3] <= hw[1] + 1e-4)
                valid = inside & (flat > thr)
                parts_s.append(jnp.where(valid, flat, -jnp.inf))
                parts_i.append(jnp.arange(base_i, base_i + sph * spw,
                                          dtype=jnp.int32))
                nv = nv + jnp.sum(valid)
            have = sum(sph * spw for _, _, _, sph, spw, _ in gspecs)
            if have < pmax:
                parts_s.append(jnp.full((pmax - have,), -jnp.inf))
                parts_i.append(jnp.full((pmax - have,), n, jnp.int32))
            return (parts_s[0] if len(parts_s) == 1
                    else jnp.concatenate(parts_s),
                    parts_i[0] if len(parts_i) == 1
                    else jnp.concatenate(parts_i),
                    nv)

        return branch

    branches = [make_branch(g) for g in groups]

    def local(gray: Array, wv: Array, bv: Array, hw: Array):
        d = jax.lax.axis_index("tile")
        s_all, i_all, nv = jax.lax.switch(d, branches, gray, wv, bv, hw)
        with jax.named_scope("select"):
            top, pos = jax.lax.top_k(s_all, k)
            return top, i_all[pos], nv

    return local


_TILED_UPLOADS = {"frames": 0, "bytes": {}}
_TILED_UPLOADS_LOCK = threading.Lock()


def _banded_put(image, fp: int) -> Array:
    """A frame to the `fp` chips of the 'tile' axis as row bands, each
    chip receiving only its own rows. An RGB frame travels as (rows,
    w*3): a minor dimension of 3 made a v5e's host-to-chip copy four
    times slower (PERF.md). A frame whose rows do not split evenly gets
    zero rows at the bottom up to a multiple of `fp`, which the program
    drops (`_tiled_single_fn`). The bytes placed on each device and the
    frames are counted (`tiled_uploads`). A fresh buffer every call, so
    the program may donate it."""
    host = np.asarray(image)
    extra = -host.shape[0] % fp
    if extra:
        host = np.pad(host, ((0, extra),) + ((0, 0),) * (host.ndim - 1))
    host = host.reshape(host.shape[0], -1)
    sharding = NamedSharding(_tile_mesh(1, fp), P("tile"))
    frame = jax.device_put(host, sharding)
    band = int(np.prod(sharding.shard_shape(host.shape))) * host.itemsize
    with _TILED_UPLOADS_LOCK:
        counts = _TILED_UPLOADS["bytes"]
        for d in sharding.addressable_devices:
            counts[d.id] = counts.get(d.id, 0) + band
        _TILED_UPLOADS["frames"] += 1
    return frame


def tiled_uploads() -> dict:
    """What the tiled path's uploads placed, process-wide: {"frames": n,
    "bytes": {device id: bytes}}."""
    with _TILED_UPLOADS_LOCK:
        return {"frames": _TILED_UPLOADS["frames"],
                "bytes": dict(_TILED_UPLOADS["bytes"])}


_FRAME_UPLOADS = {layout: {"frames": 0, "bytes": 0}
                  for layout in ("interleaved", "gray", "minor3")}
_FRAME_UPLOADS_LOCK = threading.Lock()


def _note_upload(frame: Array, w: int, frames: int) -> None:
    """Count `frames` frames of rows `w` pixels wide handed to a frame
    program as `frame`, under its layout (`frame_uploads`)."""
    layout = "interleaved" if frame.shape[-1] == 3 * w else \
        "gray" if frame.shape[-1] == w else "minor3"
    with _FRAME_UPLOADS_LOCK:
        counts = _FRAME_UPLOADS[layout]
        counts["frames"] += frames
        counts["bytes"] += frame.nbytes


def frame_uploads() -> dict:
    """Frames and bytes `detect_raw`, `detect_batch_raw` and the
    mixed-size path handed to the frame programs, process-wide, by
    layout: {"interleaved" | "gray" | "minor3": {"frames": n, "bytes":
    b}}. `interleaved` is RGB as (..., h, w*3) rows, `minor3` RGB as
    (..., h, w, 3), which a v5e copies four times slower (PERF.md) and
    no path uploads."""
    with _FRAME_UPLOADS_LOCK:
        return {k: dict(v) for k, v in _FRAME_UPLOADS.items()}


def _upload(image, rgb: bool, w: int, frames: int) -> Array:
    """A frame, or a stack of `frames`, for an untiled program: RGB
    (..., h, w, 3) as interleaved rows (..., h, w*3), gray as it is. A
    host array is reshaped as a view before its copy to the device; a
    caller's jax.Array is reshaped on the device, into a new buffer (a
    gray one is returned as it is, still the caller's)."""
    if not isinstance(image, jax.Array):
        image = np.asarray(image)
    frame = image.reshape(image.shape[:-2] + (w * 3,)) if rgb else image
    frame = jnp.asarray(frame)
    _note_upload(frame, w, frames)
    return frame


@lru_cache(maxsize=64)
def _tiled_single_fn(h: int, w: int, ph: int, pw: int, fp: int,
                     cfg: DetectorConfig) -> "jax.stages.Wrapped":
    """Single-frame tiled program: takes the frame as `fp` row bands
    (`_banded_put`: the true h rows padded to a multiple of fp, an RGB
    frame as (rows, w*3)). Under shard_map over the 'tile' axis each
    tile converts its own band to gray, and the bands are gathered into
    the whole gray plane that every tile's local program reads (the
    `gray` scope), padded to the bucket as the untiled program pads it;
    the local top-k lists come out stacked, then ONE exact merge (the
    `merge` scope) + NMS in the enclosing jit -- the merge runs once,
    not replicated per tile, which matters on hosts where forced
    devices share cores. The frame buffer is donated on accelerators,
    as in _single_fn."""
    from repro.core.tiling import merge_topk
    base = _frame_program(ph, pw, cfg)
    if base.raw is None:
        return None
    local = _tile_local_fn(ph, pw, fp, cfg)
    boxes_dev = jnp.asarray(base.boxes)
    mesh = _tile_mesh(1, fp)

    def tile_fn(band: Array, wv: Array, bv: Array, hw: Array):
        with jax.named_scope("gray"):
            gray = jax.lax.all_gather(_luma(band, w), "tile",
                                      tiled=True)[:h]
        t, i, v = local(_prep_frame(gray, h, w, ph, pw), wv, bv, hw)
        return t[None], i[None], v[None]

    sm = shard_map(tile_fn, mesh=mesh,
                   in_specs=(P("tile"), P(), P(), P()),
                   out_specs=(P("tile"), P("tile"), P("tile")),
                   check_vma=False)

    def fn(frame: Array, wv: Array, bv: Array, hw: Array):
        tl, il, nl = sm(frame, wv, bv, hw)
        with jax.named_scope("merge"):
            top, idx = merge_topk(tl, il, base.k)
        keep = nms_keep(boxes_dev[idx], top, cfg.nms_iou)
        return top, idx, keep, jnp.sum(nl)

    return jax.jit(fn, donate_argnums=(0,) if _donate() else ())


@lru_cache(maxsize=64)
def _tiled_batch_fn(h: int, w: int, ph: int, pw: int, batch: int,
                    dp: int, fp: int, cfg: DetectorConfig,
                    donate: bool = False) -> "jax.stages.Wrapped":
    """Batched 2-D (data x tile) schedule: the frame batch is sharded
    over 'data' exactly as _sharded_batch_fn (zero-frame padding, same
    chunked scan-vs-vmap schedule per device column), and within each
    frame the pyramid runs tiled over 'tile'. The merge happens inside
    the shard_map per frame -- all_gather of the (k,) local lists plus a
    psum of the valid counts over 'tile' are the only collectives; NMS
    then runs on the merged list (replicated within a frame's tile row,
    sharded over 'data'). Per-frame results byte-identical to the
    untiled / tiled-single paths. One jit per (true-shape, bucket, B,
    dp, fp) tuple."""
    from repro.core.tiling import merge_topk
    base = _frame_program(ph, pw, cfg)
    if base.raw is None:
        return None
    assert batch % dp == 0, (batch, dp)
    local_b = batch // dp
    local = _tile_local_fn(ph, pw, fp, cfg)
    boxes_dev = jnp.asarray(base.boxes)
    mesh = _tile_mesh(dp, fp)

    def one(frame: Array, wv: Array, bv: Array, hw: Array):
        gray = _prep_frame(frame, h, w, ph, pw)
        t, i, v = local(gray, wv, bv, hw)
        with jax.named_scope("merge"):
            tl = jax.lax.all_gather(t, "tile")          # (fp, k)
            il = jax.lax.all_gather(i, "tile")
            nv = jax.lax.psum(v, "tile")
            top, idx = merge_topk(tl, il, base.k)
        keep = nms_keep(boxes_dev[idx], top, cfg.nms_iou)
        return top, idx, keep, nv

    local_fn = _chunked_schedule(one, max(1, cfg.batch_chunk), local_b)
    data = P("data")
    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=(data, P(), P(), data),
                   out_specs=(data, data, data, data),
                   check_vma=False)
    donate_kw = dict(donate_argnums=(0,)) if donate else {}
    return jax.jit(fn, **donate_kw)


# ------------------------------------------------- batch-chunk autotune
# The scan-vs-vmap layout choice used to be a hardcoded CPU/accelerator
# guess (batch_chunk=1 vs =B). It is now measured: the first
# detect_batch call on a new (true-shape, bucket, B) tuple probes each
# candidate schedule on synthetic frames (min-of-k wall time, donation
# off so the probe buffers survive), caches the winner for the process
# lifetime, and exposes the decisions through autotune_report().

_AUTOTUNE: dict = {}
_AUTOTUNE_PROBE_ITERS = 3


def _autotune_chunk(h: int, w: int, ph: int, pw: int, batch: int,
                    cfg: DetectorConfig, frame_shape: Tuple[int, ...],
                    frame_dtype, dp: int = 1, fp: int = 1,
                    heads: int = 0) -> int:
    from repro.core import autotune_cache
    layout = f"{'rgb' if frame_shape[-1] == 3 * w else 'gray'}-{frame_dtype}"
    # `heads` rides at the END of the key so the k[7]/k[8] mesh indices
    # in _autotune_key_str stay valid for pre-existing entries; 0 = the
    # single-head (F,) parameter layout, K>0 = stacked (K, F) heads
    key = (h, w, ph, pw, batch, cfg, layout, dp, fp, heads)
    hit = _AUTOTUNE.get(key)
    if hit is not None:
        autotune_cache.note_memory_hit()
        return hit["chunk"]
    # under sharding the chunk schedules each device's LOCAL sub-batch
    local = batch // dp
    candidates = sorted({1, local} | ({4} if 1 < 4 < local else set()))
    if len(candidates) == 1:
        _AUTOTUNE[key] = {"chunk": candidates[0], "probe_ms": {}}
        return candidates[0]
    # a decision probed on an equivalent host may be on disk -- skip
    # the probe compiles entirely on warm starts (autotune_cache)
    dkey = autotune_cache.entry_key(_autotune_key_str(key), cfg)
    disk = autotune_cache.lookup(dkey)
    if disk is not None and disk["chunk"] in candidates:
        _AUTOTUNE[key] = {**disk, "source": "disk"}
        return disk["chunk"]
    # probe with the CALLER's frame layout (RGB uint8 vs gray f32, ...)
    # and the production donate flag, so the probe times -- and
    # pre-compiles -- the exact executable the real call will run,
    # grayscale conversion included. With donation active each probe
    # invocation hands over a fresh copy (the copy cost is symmetric
    # across candidates, so the scan-vs-vmap ranking is unaffected).
    frames = jnp.zeros(frame_shape, frame_dtype)
    donate = _donate()
    mk = (lambda: jnp.array(frames, copy=True)) if donate \
        else (lambda: frames)
    if heads:
        wv = jnp.zeros((heads, cfg.hog.n_features), jnp.float32)
        bv = jnp.zeros((heads,), jnp.float32)
    else:
        wv = jnp.zeros(cfg.hog.n_features, jnp.float32)
        bv = jnp.float32(0.0)
    hw_b = jnp.tile(jnp.asarray([h, w], jnp.float32), (batch, 1))
    probe_ms = {}
    t_probe = time.perf_counter()
    for c in candidates:
        c_cfg = dataclasses.replace(cfg, batch_chunk=c)
        if fp > 1:
            fn = _tiled_batch_fn(h, w, ph, pw, batch, dp, fp, c_cfg, donate)
        elif dp > 1:
            fn = _sharded_batch_fn(h, w, ph, pw, batch, dp, c_cfg, donate)
        else:
            fn = _batch_fn(h, w, ph, pw, batch, c_cfg, donate)
        jax.block_until_ready(fn(mk(), wv, bv, hw_b))     # compile
        best = float("inf")
        for _ in range(_AUTOTUNE_PROBE_ITERS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(mk(), wv, bv, hw_b))
            best = min(best, time.perf_counter() - t0)
        probe_ms[c] = best * 1e3
    chunk = min(probe_ms, key=probe_ms.get)
    _AUTOTUNE[key] = {"chunk": chunk, "probe_ms": probe_ms,
                      "source": "probe",
                      "probe_s": time.perf_counter() - t_probe}
    autotune_cache.store(dkey, chunk, probe_ms)
    return chunk


def _autotune_key_str(k: tuple) -> str:
    mesh = f"data:{k[7]}" + (f",tile:{k[8]}" if k[8] > 1 else "")
    heads = f" heads:{k[9]}" if len(k) > 9 and k[9] else ""
    return f"{k[0]}x{k[1]}->{k[2]}x{k[3]} B={k[4]} mesh={mesh}{heads} [{k[6]}]"


def autotune_report() -> dict:
    """Chosen detect_batch schedules, keyed by the probed geometry,
    mesh and frame layout: {"HxW->PHxPW B=n mesh=data:d [rgb-uint8]":
    {"chunk": c, "probe_ms": {candidate: ms}, "source": ...}}. Every
    key carries the mesh layout (data:1 = the unsharded path; a
    ",tile:f" suffix marks the 2-D frame-parallel schedule) so BENCH
    entries stay unambiguous about which device layout a schedule was
    probed on; "source" says whether the decision was probed live or
    restored from the disk cache (core/autotune_cache.py); a live
    probe also records its wall time, compiles included ("probe_s")."""
    return {_autotune_key_str(k): dict(v) for k, v in _AUTOTUNE.items()}


# ------------------------------------------- dispatched programs, loads
# Every frame program the detector has dispatched, keyed by its jitted
# function (one per program through the lru caches above), with its
# bucket, batch size and the abstract arguments it was called with, so
# that its compiled text can be read back (`op_scopes`); and what
# readying each program cost, as `DetectionSession.warmup` measures it
# (`ready_programs`, `program_loads`). Process-wide, like the program
# caches they describe; `DetectionSession.clear_cache` empties both.

_PROGRAMS: dict = {}
_LOADS: List[dict] = []
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _dispatch(fn, args: tuple, bucket: Tuple[int, int], batch: int):
    """Call a frame program (which only enqueues its work), noting it on
    its first call."""
    new = fn not in _PROGRAMS
    if new:
        # an uncommitted argument goes wherever the program runs, so it
        # is noted without a placement (with one, a program over several
        # devices would refuse it)
        avals = tuple(jax.ShapeDtypeStruct(
            np.shape(a), jnp.result_type(a),
            sharding=a.sharding if getattr(a, "committed", False)
            else None) for a in args)
    with spans.span("detect.dispatch"):
        out = fn(*args)
    if new:
        _PROGRAMS[fn] = {"bucket": bucket, "batch": batch, "args": avals}
    return out


@contextlib.contextmanager
def ready_programs():
    """Time readying the frame programs first dispatched inside the
    block: compiling them or loading them from JAX's persistent cache,
    plus the first run if the block waits for it. Each new program is
    recorded once in `program_loads()`: its bucket, batch size, the
    block's seconds, whether it compiled or loaded (JAX's monitoring
    events: a backend compile without a persistent-cache hit compiled),
    and the seconds of any batch-schedule probe the block ran."""
    seen, tuned = set(_PROGRAMS), set(_AUTOTUNE)
    n = {"compiles": 0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == _COMPILE_EVENT:
            n["compiles"] += 1

    def on_event(event, **_):
        if event == _CACHE_HIT_EVENT:
            n["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        seconds = time.perf_counter() - t0
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
    probe_s = sum(v.get("probe_s", 0.0) for k, v in _AUTOTUNE.items()
                  if k not in tuned)
    source = "compiled" if n["compiles"] > n["cache_hits"] else \
        "loaded" if n["cache_hits"] else "in memory"
    for fn, p in list(_PROGRAMS.items()):
        if fn not in seen:
            _LOADS.append({"bucket": p["bucket"], "batch": p["batch"],
                           "seconds": seconds, "source": source, **n,
                           "probe_s": probe_s})


def program_loads() -> List[dict]:
    """What readying each program cost (`ready_programs`), in order."""
    return [dict(r) for r in _LOADS]


_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s(.*)$")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"calls=%?([^\s,}]+)")
_HLO_OPERAND = re.compile(r"%([^\s,)}]+)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(")


def hlo_op_names(text: str) -> dict:
    """HLO instruction name -> `op_name` in a compiled module's text.
    An instruction the compiler left without metadata (a fusion it
    built, a layout copy, a tuple access) takes the first `op_name`
    inside the computation it calls, else its first operand's: called
    computations and operands come before their users in the text."""
    own, inner = {}, {}
    comp = None
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m is None:
            c = _HLO_COMPUTATION.match(line)
            if c is not None and line.rstrip().endswith("{"):
                comp = c.group(1)
            continue
        name, rest = m.groups()
        op = _HLO_OP_NAME.search(rest)
        if op is not None:
            found = op.group(1)
        else:
            called = _HLO_CALLS.search(rest)
            found = inner.get(called.group(1)) if called else None
            if found is None:
                found = next((own[a] for a in _HLO_OPERAND.findall(rest)
                              if a in own), None)
        if found is not None:
            own[name] = found
            inner.setdefault(comp, found)
    return own


def op_scopes(batch: int) -> dict:
    """HLO instruction name -> its `op_name` (the named-scope path, e.g.
    `jit(fn)/.../resize/dot_general`) in the compiled text of every
    frame program dispatched with `batch` frames (1: one frame).
    Instruction names are unique within a program, not across them."""
    out = {}
    for fn, p in list(_PROGRAMS.items()):
        if p["batch"] == batch:
            out.update(hlo_op_names(
                fn.lower(*p["args"]).compile().as_text()))
    return out


class FrameDetector:
    """Reusable handle: SVM params + config -> per-frame detections.

    Compiles once per frame-shape bucket (shape_bucket rounding), then
    every call on a same-bucket frame reuses the device program with no
    retrace; only the final box decode touches host numpy.
    """

    def __init__(self, svm: SVMParams, cfg: Optional[DetectorConfig] = None,
                 classes: Optional[Tuple[str, ...]] = None):
        # default built per instance (never a shared default-arg object)
        self.svm = svm
        self.cfg = DetectorConfig() if cfg is None else cfg
        # stacked (K, F) params score K heads in one widened matmul; the
        # optional class names ride into every Detections this handle
        # builds so decoded boxes carry class_id/label
        self.heads = int(np.shape(svm["w"])[0]) \
            if np.ndim(svm["w"]) == 2 else 0
        if classes is not None and self.heads \
                and len(classes) != self.heads:
            raise ValueError(
                f"{len(classes)} class names for {self.heads} heads")
        self.classes = tuple(classes) if classes is not None else (
            tuple(f"head{i}" for i in range(self.heads))
            if self.heads else None)

    def program_for(self, h: int, w: int) -> Tuple[FrameProgram, int, int]:
        b = max(1, self.cfg.shape_bucket)
        return _frame_program(_round_up(h, b), _round_up(w, b),
                              self.cfg), _round_up(h, b), _round_up(w, b)

    @property
    def data_devices(self) -> int:
        """Resolved device count of the batch ('data') axis: 1 on the
        single-device path, the mesh size under sharding. The serving
        microbatcher scales its coalescing target by this."""
        return _resolve_dp(self.cfg)

    @property
    def frame_devices(self) -> int:
        """Resolved device count of the intra-frame ('tile') axis: 1
        when frame parallelism is off. Whether a given frame actually
        runs tiled also depends on frame_parallel_min_area (see
        _tiled_for)."""
        return _resolve_fp(self.cfg)

    def _tiled_for(self, ph: int, pw: int, dp: int = 1) -> int:
        """Tile count a (ph, pw)-bucket frame runs under: the resolved
        'tile' axis when the bucket clears the area threshold, else 1
        (the untiled program). The threshold is on the PADDED bucket
        area -- that is the compute the program actually does, and it
        keeps routing deterministic per program."""
        fp = _resolve_fp(self.cfg, dp)
        if fp > 1 and ph * pw >= self.cfg.frame_parallel_min_area:
            if self.heads:
                raise ValueError(
                    "multi-head (stacked) params do not compose with "
                    "frame_parallel tiling yet; run the stacked heads "
                    "with frame_parallel=1 (the data axis still shards)")
            return fp
        return 1

    @staticmethod
    def _to_gray(image: Array) -> Array:
        _, w = _frame_hw(np.shape(image))
        return _luma(_upload(image, np.ndim(image) == 3, w, 1), w)

    def bucket_for(self, frame) -> Tuple[int, int]:
        """Padded-bucket shape a frame would be served under; raises
        ValueError on malformed shapes. The one validation + bucketing
        contract shared with the serving microbatcher."""
        h, w = _frame_hw(np.shape(frame))
        _, ph, pw = self.program_for(h, w)
        return ph, pw

    @staticmethod
    def _pad_to(gray: Array, ph: int, pw: int) -> Array:
        h, w = int(gray.shape[0]), int(gray.shape[1])
        if (ph, pw) == (h, w):
            return gray
        # edge-replicate so downscaling does not bleed zeros into
        # the last valid windows near the pad seam
        return jnp.pad(gray, ((0, ph - h), (0, pw - w)), mode="edge")

    def detect_raw(self, image: Array) -> "Detections":
        """One frame -> device-resident typed Detections (api layer).

        Nothing syncs to host here: the result wraps the compiled
        program's top-k/keep tensors plus the static decode tables, and
        decodes lazily on first host access (`.to_list()` et al.).
        Grayscale + pad run inside the program (one dispatch per frame,
        keyed on the true shape like the batch path), and the frame
        buffer is donated on accelerators. A tiled frame goes to its
        chips as row bands, one a chip (`_banded_put`); an untiled one
        whole to the default device. Either way an (h, w, 3) RGB frame
        travels as (h, w*3) interleaved rows (`_upload`) and a gray one
        as it is. Spans: `detect.upload`, the frame to the device(s);
        `detect.dispatch`, enqueueing the program.
        """
        from repro.api.results import Detections
        h, w = _frame_hw(np.shape(image))
        prog, ph, pw = self.program_for(h, w)
        if prog.fn is None:
            return Detections.empty(prog.tables, self.classes)
        fp = self._tiled_for(ph, pw)
        fn = (_tiled_single_fn(h, w, ph, pw, fp, self.cfg) if fp > 1
              else _single_fn(h, w, ph, pw, self.cfg))
        with spans.span("detect.upload"):
            if fp > 1:
                frame = _banded_put(image, fp)
                _note_upload(frame, w, 1)
            else:
                frame = _upload(image, np.ndim(image) == 3, w, 1)
                if _donate() and frame is image:
                    # the program donates its frame argument; a
                    # caller-owned device buffer must not be invalidated
                    # under them
                    frame = jnp.array(frame, copy=True)
            hw = jnp.asarray([h, w], jnp.float32)
        top, idx, keep, n_valid = _dispatch(
            fn, (frame, self.svm["w"], self.svm["b"], hw), (ph, pw), 1)
        return Detections(top, idx, keep, n_valid, prog.tables,
                          classes=self.classes)

    def __call__(self, image: Array) -> List[dict]:
        """Legacy per-frame contract (list of dicts). Thin shim over
        `detect_raw` -- prefer `repro.api.DetectionSession.detect`,
        which returns the typed result without the forced host sync."""
        return self.detect_raw(image).to_list()

    def detect_batch_raw(self, frames) -> "Detections":
        """Batched frame path: B frames -> one batched Detections.

        `frames` is a stacked (B, H, W[, 3]) array or a sequence of
        frames; an RGB stack travels as (B, H, W*3) interleaved rows
        (`_upload`). All frames must land in the SAME padded shape
        bucket (equal shapes always do; the serving microbatcher groups
        by bucket before calling) -- mixed buckets raise ValueError. The
        compiled program is the single-frame pyramid program vmapped
        over the batch, jitted once per (bucket, B) pair; per-frame
        top-k + NMS run device-side and the host never syncs until the
        result is decoded. With `cfg.data_parallel != 1` the batch is
        padded to a multiple of the data mesh size (masked zero frames,
        sliced off the result) and runs sharded, B/n_devices frames per
        device -- per-frame results byte-identical to data_parallel=1.
        Spans: `detect.stack` (the host stack of a frame list),
        `detect.upload`, `detect.dispatch`.
        """
        from repro.api.results import Detections
        if isinstance(frames, (list, tuple)) and not frames:
            return Detections.empty_batch(
                DecodeTables(np.zeros((0, 4), np.float32),
                             np.zeros((0,), np.float32), 0), 0,
                self.classes)
        uniform = not isinstance(frames, (list, tuple)) or \
            len({np.shape(f) for f in frames}) == 1
        if uniform:
            with spans.span("detect.stack"):
                batch = np.stack([np.asarray(f) for f in frames]) \
                    if isinstance(frames, (list, tuple)) else frames
            shape = tuple(np.shape(batch))
            if not isinstance(frames, (list, tuple)) \
                    and len(shape) == 3 and shape[-1] == 3:
                # a bare (H, W, 3) RGB frame would silently parse as H
                # gray frames of width 3 -- an ambiguity no caller wants
                raise ValueError(
                    f"shape {shape} looks like a single RGB frame; pass "
                    f"a list of frames or a stacked (B, H, W[, 3]) array")
            if not (len(shape) == 3
                    or (len(shape) == 4 and shape[-1] == 3)):
                raise ValueError(
                    f"expected (B, H, W[, 3]) stacked frames, got shape "
                    f"{shape}")
            n, h, w = int(shape[0]), int(shape[1]), int(shape[2])
            if n == 0:
                return Detections.empty_batch(
                    DecodeTables(np.zeros((0, 4), np.float32),
                                 np.zeros((0,), np.float32), 0), 0,
                    self.classes)
            hws = [(h, w)] * n
        else:
            # mixed true sizes: grayscale + pad per frame on host, then
            # hand the batched program a uniform pre-padded gray stack
            grays = [self._to_gray(f) for f in frames]
            n = len(grays)
            hws = [(int(g.shape[0]), int(g.shape[1])) for g in grays]
        buckets = {self.program_for(h, w)[1:] for h, w in hws}
        if len(buckets) != 1:
            raise ValueError(
                f"detect_batch needs one shape bucket per call, got "
                f"{sorted(buckets)}; group frames by bucket first")
        prog, ph, pw = self.program_for(*hws[0])
        if prog.fn is None:
            return Detections.empty_batch(prog.tables, n,
                                          self.classes)
        th, tw = (h, w) if uniform else (ph, pw)
        cfg = self.cfg
        dp = _resolve_dp(cfg)
        n_pad = _round_up(n, dp) if dp > 1 else n
        with spans.span("detect.upload"):
            if uniform:
                frames_b = _upload(batch, len(shape) == 4, w, n)
            else:
                frames_b = jnp.stack([self._pad_to(g, ph, pw)
                                      for g in grays])
            if n_pad != n:
                # pad the batch up to the mesh's data size with zero
                # frames whose true-size mask is (0, 0): every window
                # fails the inside-frame test, so pad rows decode to
                # empty results and are sliced off below before the
                # Detections is built
                pad = jnp.zeros((n_pad - n,) + tuple(frames_b.shape[1:]),
                                frames_b.dtype)
                frames_b = jnp.concatenate([frames_b, pad])
                hws = list(hws) + [(0, 0)] * (n_pad - n)
            elif _donate() and frames_b is frames:
                # the batched program donates its frame stack; only
                # copy when it is still the caller's own device buffer
                # (lists, numpy stacks, an RGB reshape and the pad
                # concatenate above all produced a fresh one already)
                frames_b = jnp.array(frames_b, copy=True)
            hw_b = jnp.asarray(hws, jnp.float32)
        fp = self._tiled_for(ph, pw, dp)
        if cfg.batch_chunk == 0:         # autotune scan-vs-vmap (first use)
            chunk = _autotune_chunk(th, tw, ph, pw, n_pad, cfg,
                                    tuple(frames_b.shape), frames_b.dtype,
                                    dp, fp, self.heads)
            cfg = dataclasses.replace(cfg, batch_chunk=chunk)
        if fp > 1:
            fn = _tiled_batch_fn(th, tw, ph, pw, n_pad, dp, fp, cfg,
                                 _donate())
        elif dp > 1:
            fn = _sharded_batch_fn(th, tw, ph, pw, n_pad, dp, cfg, _donate())
        else:
            fn = _batch_fn(th, tw, ph, pw, n_pad, cfg, _donate())
        top, idx, keep, n_valid = _dispatch(
            fn, (frames_b, self.svm["w"], self.svm["b"], hw_b), (ph, pw),
            n_pad)
        if n_pad != n:                   # drop the masked pad rows
            top, idx, keep, n_valid = (top[:n], idx[:n], keep[:n],
                                       n_valid[:n])
        return Detections(top, idx, keep, n_valid, prog.tables,
                          classes=self.classes)

    def detect_batch(self, frames) -> List[List[dict]]:
        """Legacy batched contract (B per-frame dict lists, one host
        sync). Thin shim over `detect_batch_raw`."""
        return self.detect_batch_raw(frames).to_list()


def detect(image_rgb: Array, svm: SVMParams,
           cfg: Optional[DetectorConfig] = None) -> List[dict]:
    """Multi-scale detection. Returns [{box:(y0,x0,y1,x1), score, scale}]
    sorted by descending score (top-k order).

    Deprecated shim: the unified entry point is
    `repro.api.DetectionSession.detect`, which reuses one session's
    compiled programs across calls and returns typed Detections
    (equivalence pinned by tests/test_api_session.py).
    """
    return FrameDetector(svm, cfg)(image_rgb)
