"""The plain reference: dense multi-scale HOG+SVM detection in numpy.

Independent of the program under test: it imports nothing of `repro`
and takes nothing the program made. From a configuration's sizes and
the SVM weights it computes, in float64:

  1. BT.601 gray of the RGB frame, edge-padded up to the shape bucket;
  2. each pyramid level, resized with the triangle ("linear") filter,
     antialiased when downscaling (the filter `jax.image.resize`
     documents), applied as a banded sum of taps;
  3. the paper's HOG chain on the level (the float64 chain of
     `tests/test_golden_reference.py`, copied and made dense): central
     differences, arctan2 hard binning into 9 unsigned bins, 8x8 cell
     histograms, 2x2-cell blocks L2-normalised with eps 1e-2;
  4. the SVM score of every 130x66 window at cell stride: the dot product
     of its 15x7 blocks with the 3,780 weights, plus the bias;
  5. windows inside the true frame whose score clears the threshold,
     the top K of them by score, and plain greedy NMS.

`blocks_dtype` computes step 3's descriptors and the weights in a lower
precision instead ("int8", "float8_e4m3fn", "bfloat16"): the control of
the comparison, see `compare.py`.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

LUMA = (0.2989, 0.5870, 0.1140)
CELL, BLOCK, BINS, EPS = 8, 2, 9, 1e-2
WIN_H, WIN_W = 130, 66
WBH, WBW = 15, 7                  # blocks per window


@dataclasses.dataclass(frozen=True)
class Level:
    scale: float
    sh: int                       # resized level, pixels
    sw: int
    sph: int                      # window positions (score map)
    spw: int

    @property
    def gh(self) -> int:          # gradient field trimmed to whole cells
        return (self.sh - 2) // CELL * CELL

    @property
    def gw(self) -> int:
        return (self.sw - 2) // CELL * CELL

    @property
    def bh(self) -> int:          # block grid
        return self.gh // CELL - BLOCK + 1

    @property
    def bw(self) -> int:
        return self.gw // CELL - BLOCK + 1


def bucket(h: int, w: int, step: int) -> Tuple[int, int]:
    return -(-h // step) * step, -(-w // step) * step


def levels(h: int, w: int, scales, step: int) -> List[Level]:
    """The pyramid of an (h, w) frame padded to the `step` bucket: one
    level per scale that still holds a whole window."""
    ph, pw = bucket(h, w, step)
    out = []
    for s in scales:
        sh, sw = int(ph * s), int(pw * s)
        if sh < WIN_H or sw < WIN_W:
            continue
        gh, gw = (sh - 2) // CELL * CELL, (sw - 2) // CELL * CELL
        bh, bw = gh // CELL - BLOCK + 1, gw // CELL - BLOCK + 1
        out.append(Level(float(s), sh, sw, bh - WBH + 1, bw - WBW + 1))
    return out


def window_boxes(h: int, w: int, scales, step: int) -> np.ndarray:
    """(N, 4) boxes (y0, x0, y1, x1) in frame pixels of every window, in
    level order and row-major within a level."""
    ph, pw = bucket(h, w, step)
    rows = []
    for lv in levels(h, w, scales, step):
        sy, sx = lv.sh / ph, lv.sw / pw
        ys, xs = np.mgrid[0:lv.sph, 0:lv.spw].astype(np.float64)
        y0, x0 = ys * CELL / sy, xs * CELL / sx
        rows.append(np.stack([y0, x0, y0 + WIN_H / sy, x0 + WIN_W / sx],
                             -1).reshape(-1, 4))
    return np.concatenate(rows)


# ----------------------------------------------------------------- resize

def resize_taps(src: int, dst: int) -> Tuple[np.ndarray, np.ndarray]:
    """Band form of the triangle-filter resize from `src` to `dst`
    samples: (dst, T) source indices and (dst, T) weights."""
    scale = dst / src
    kscale = max(1.0 / scale, 1.0)
    sample = (np.arange(dst) + 0.5) / scale - 0.5
    x = np.abs(sample[:, None] - np.arange(src)[None, :]) / kscale
    wts = np.maximum(0.0, 1.0 - x)
    tot = wts.sum(axis=1, keepdims=True)
    wts = np.where(np.abs(tot) > 1000.0 * np.finfo(np.float32).eps,
                   wts / np.where(tot != 0, tot, 1.0), 0.0)
    inside = (sample >= -0.5) & (sample <= src - 0.5)
    wts = np.where(inside[:, None], wts, 0.0)
    nz = wts > 0
    lo = np.argmax(nz, axis=1)
    taps = int(nz.sum(axis=1).max())
    idx = np.minimum(lo[:, None] + np.arange(taps)[None, :], src - 1)
    return idx, np.take_along_axis(wts, idx, axis=1) * (
        lo[:, None] + np.arange(taps)[None, :] < src)


def resize(g: np.ndarray, sh: int, sw: int) -> np.ndarray:
    out = g
    if sh != g.shape[0]:
        idx, wts = resize_taps(g.shape[0], sh)
        out = np.einsum("ot,otw->ow", wts, out[idx])
    if sw != g.shape[1]:
        idx, wts = resize_taps(g.shape[1], sw)
        out = np.einsum("ot,hot->ho", wts, out[:, idx])
    return out


# -------------------------------------------------------------------- HOG

def gray(frame: np.ndarray) -> np.ndarray:
    f = frame.astype(np.float64)
    return LUMA[0] * f[..., 0] + LUMA[1] * f[..., 1] + LUMA[2] * f[..., 2]


def hog_blocks(g: np.ndarray, blocks_dtype: Optional[str] = None
               ) -> np.ndarray:
    """Normalised block grid (BH, BW, 36) of a gray level."""
    gh, gw = (g.shape[0] - 2) // CELL * CELL, (g.shape[1] - 2) // CELL * CELL
    g = g[:gh + 2, :gw + 2]
    fx = g[1:-1, 2:] - g[1:-1, :-2]
    fy = g[2:, 1:-1] - g[:-2, 1:-1]
    mag = np.sqrt(fx * fx + fy * fy)
    theta = np.mod(np.degrees(np.arctan2(fy, fx)), 180.0)
    b = np.clip(np.floor(theta / (180.0 / BINS)), 0, BINS - 1).astype(np.int64)
    ch, cw = gh // CELL, gw // CELL
    cell_of = (np.arange(gh)[:, None] // CELL) * cw + np.arange(gw)[None, :] \
        // CELL
    hist = np.bincount((cell_of * BINS + b).ravel(), weights=mag.ravel(),
                       minlength=ch * cw * BINS).reshape(ch, cw, BINS)
    bh, bw = ch - BLOCK + 1, cw - BLOCK + 1
    # cell order within a block: (0,0) (0,1) (1,0) (1,1), 9 bins each
    v = np.concatenate([hist[i:i + bh, j:j + bw]
                        for i in range(BLOCK) for j in range(BLOCK)], -1)
    v = v / np.sqrt(np.sum(v * v, axis=-1, keepdims=True) + EPS ** 2)
    return lower(v, blocks_dtype, axis=-1)


def lower(x: np.ndarray, dtype: Optional[str], axis: int = -1) -> np.ndarray:
    """`x` rounded to a lower precision and back to float64: "int8" is
    symmetric per-vector int8 along `axis` (scale = max |x| / 127)."""
    if dtype is None:
        return x
    if dtype == "int8":
        s = np.max(np.abs(x), axis=axis, keepdims=True) / 127.0
        s = np.where(s > 0, s, 1.0)
        return np.clip(np.rint(x / s), -127, 127) * s
    import ml_dtypes
    return x.astype(getattr(ml_dtypes, dtype)).astype(np.float64)


def score_map(blocks: np.ndarray, w: np.ndarray, b: float) -> np.ndarray:
    """(BH, BW, 36) blocks -> (PH, PW) window scores."""
    bh, bw, _ = blocks.shape
    ph, pw = bh - WBH + 1, bw - WBW + 1
    wt = w.reshape(WBH, WBW, -1)
    out = np.full((ph, pw), float(b))
    for di in range(WBH):
        for dj in range(WBW):
            out += blocks[di:di + ph, dj:dj + pw] @ wt[di, dj]
    return out


def window_scores(frame: np.ndarray, w: np.ndarray, b: float, scales,
                  step: int, blocks_dtype: Optional[str] = None
                  ) -> np.ndarray:
    """(N,) float64 score of every window, in `window_boxes` order."""
    h, wd = frame.shape[:2]
    ph, pw = bucket(h, wd, step)
    g = np.pad(gray(frame), ((0, ph - h), (0, pw - wd)), mode="edge")
    wq = w if blocks_dtype is None else \
        lower(w.reshape(WBH * WBW, -1), blocks_dtype, axis=0).reshape(-1)
    parts = []
    for lv in levels(h, wd, scales, step):
        blk = hog_blocks(resize(g, lv.sh, lv.sw), blocks_dtype)
        parts.append(score_map(blk, wq, b).reshape(-1))
    return np.concatenate(parts)


# -------------------------------------------------------------------- NMS

def iou(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(N, 4) x (M, 4) -> (N, M) intersection over union."""
    y0 = np.maximum(a[:, None, 0], b[None, :, 0])
    x0 = np.maximum(a[:, None, 1], b[None, :, 1])
    y1 = np.minimum(a[:, None, 2], b[None, :, 2])
    x1 = np.minimum(a[:, None, 3], b[None, :, 3])
    inter = np.maximum(y1 - y0, 0) * np.maximum(x1 - x0, 0)
    area_a = (a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1])
    area_b = (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
    return inter / np.maximum(area_a[:, None] + area_b[None, :] - inter, 1e-9)


def greedy_nms(boxes: np.ndarray, iou_thr: float) -> List[int]:
    """Indices kept by greedy NMS over boxes already in score order."""
    keep: List[int] = []
    for i in range(len(boxes)):
        if not keep or not np.any(iou(boxes[i:i + 1], boxes[keep])[0]
                                  > iou_thr):
            keep.append(i)
    return keep


@dataclasses.dataclass
class FrameResult:
    scores: np.ndarray            # (N,) every window's score
    boxes: np.ndarray             # (N, 4) every window's box
    valid: np.ndarray             # (N,) inside the true frame and > thr
    top: np.ndarray               # indices of the top K valid, by score
    kept: np.ndarray              # indices kept by NMS, by score


def detect(frame: np.ndarray, w: np.ndarray, b: float, *, scales,
           step: int, threshold: float, iou_thr: float, k: int,
           blocks_dtype: Optional[str] = None) -> FrameResult:
    h, wd = frame.shape[:2]
    scores = window_scores(frame, w, b, scales, step, blocks_dtype)
    boxes = window_boxes(h, wd, scales, step)
    inside = (boxes[:, 2] <= h + 1e-4) & (boxes[:, 3] <= wd + 1e-4)
    valid = inside & (scores > threshold)
    cand = np.flatnonzero(valid)
    # descending score, ascending index among equal scores
    top = cand[np.lexsort((cand, -scores[cand]))][:k]
    kept = top[greedy_nms(boxes[top], iou_thr)]
    return FrameResult(scores, boxes, valid, top, kept)


def top_k_size(n: int, max_detections: int) -> int:
    """K of a frame with n windows: the configured cap, or with 0 the
    larger of 256 and n / 256, at most n."""
    if max_detections:
        return min(max_detections, n)
    return min(n, max(256, -(-n // 256)))
