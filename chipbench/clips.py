"""Seeded synthetic video clips: the frames every cell serves.

A copy of the clip generator of the program under test
(`repro.data.synth_pedestrian.make_clip` and the helpers it calls),
kept here so that the benchmark's traffic cannot move with the program.
Pedestrians with one rendered appearance each walk on constant-velocity
paths with small jitter over a static cluttered background; only the
per-frame sensor noise changes. Pure numpy; the same generator state
gives the same frames.
"""
from __future__ import annotations

import numpy as np

H, W = 130, 66                  # the paper's detection window
MIN_CONTRAST, MAX_CONTRAST = 2.0, 60.0
OCCLUSION_P = 0.65


def _smooth_noise(rng: np.random.Generator, h: int, w: int,
                  scale: int = 8) -> np.ndarray:
    small = rng.normal(size=(h // scale + 2, w // scale + 2))
    ys = np.linspace(0, small.shape[0] - 1.001, h)
    xs = np.linspace(0, small.shape[1] - 1.001, w)
    y0, x0 = ys.astype(int), xs.astype(int)
    fy, fx = ys - y0, xs - x0
    a = small[y0][:, x0]
    b = small[y0][:, x0 + 1]
    c = small[y0 + 1][:, x0]
    d = small[y0 + 1][:, x0 + 1]
    return (a * np.outer(1 - fy, 1 - fx) + b * np.outer(1 - fy, fx)
            + c * np.outer(fy, 1 - fx) + d * np.outer(fy, fx))


def _background(rng: np.random.Generator) -> np.ndarray:
    base = rng.uniform(60, 190)
    grad = np.linspace(0, rng.uniform(-30, 30), H)[:, None]
    tex = _smooth_noise(rng, H, W, scale=int(rng.integers(6, 16))) \
        * rng.uniform(5, 25)
    img = base + grad + tex
    if rng.random() < 0.4:
        y = int(rng.integers(20, H - 20))
        img[y:] += rng.uniform(-35, 35)
    return img


def _ellipse_mask(h: int, w: int, cy: float, cx: float,
                  ry: float, rx: float) -> np.ndarray:
    yy, xx = np.mgrid[0:h, 0:w]
    return (((yy - cy) / ry) ** 2 + ((xx - cx) / rx) ** 2) <= 1.0


def _person_mask(rng: np.random.Generator) -> np.ndarray:
    m = np.zeros((H, W), dtype=bool)
    scale = rng.uniform(0.82, 1.0)
    cx = W / 2 + rng.uniform(-6, 6)
    top = 14 + rng.uniform(-4, 6)

    head_r = 6.5 * scale * rng.uniform(0.85, 1.15)
    head_cy = top + head_r
    m |= _ellipse_mask(H, W, head_cy, cx + rng.uniform(-1.5, 1.5),
                       head_r, head_r * rng.uniform(0.8, 1.0))

    torso_top = head_cy + head_r * rng.uniform(0.7, 1.1)
    torso_h = 42 * scale * rng.uniform(0.9, 1.1)
    torso_w = 10.5 * scale * rng.uniform(0.85, 1.25)
    m |= _ellipse_mask(H, W, torso_top + torso_h / 2, cx,
                       torso_h / 2, torso_w)

    for side in (-1, 1):
        if rng.random() < 0.85:
            ax = cx + side * (torso_w + rng.uniform(0, 3.5))
            atop = torso_top + rng.uniform(0, 6)
            ah = torso_h * rng.uniform(0.7, 1.0)
            m |= _ellipse_mask(H, W, atop + ah / 2,
                               ax + side * rng.uniform(-1, 3),
                               ah / 2, 2.6 * scale)

    hip_y = torso_top + torso_h
    leg_h = min(H - 6 - hip_y, 50 * scale * rng.uniform(0.9, 1.05))
    spread = rng.uniform(1.5, 9.0)
    for side in (-1, 1):
        lx = cx + side * spread * rng.uniform(0.6, 1.2)
        m |= _ellipse_mask(H, W, hip_y + leg_h / 2, lx,
                           leg_h / 2, 3.4 * scale)
    return m


def _positive(rng: np.random.Generator) -> np.ndarray:
    img = _background(rng)
    mask = _person_mask(rng)
    bg_mean = float(img[mask].mean()) if mask.any() else 128.0
    contrast = rng.uniform(MIN_CONTRAST, MAX_CONTRAST)
    sign = -1.0 if rng.random() < 0.5 else 1.0
    person_luma = np.clip(bg_mean + sign * contrast, 10, 245)
    split_y = int(rng.uniform(60, 85))
    upper = mask & (np.arange(H)[:, None] < split_y)
    lower = mask & ~upper
    img[upper] = person_luma + rng.normal(0, 6)
    img[lower] = np.clip(person_luma + rng.uniform(-40, 40), 10, 245)
    if rng.random() < OCCLUSION_P:
        x0 = int(rng.integers(8, W - 14))
        wd = int(rng.integers(4, 10))
        img[:, x0:x0 + wd] = rng.uniform(30, 220)
    return img


def make_clip(rng: np.random.Generator, h: int, w: int, n_frames: int,
              n_people: int, speed: float = 4.0, jitter: float = 0.6,
              frame_noise: float = 8.0, n_distractors: int = 3
              ) -> np.ndarray:
    """(n_frames, h, w, 3) uint8 frames of `n_people` walking pedestrians
    over a static background with `n_distractors` clutter blobs."""
    T = n_frames
    if h < H or w < W:
        raise ValueError(f"clip frames must fit the {H}x{W} window, "
                         f"got ({h}, {w})")
    bg = _smooth_noise(rng, h, w, 12) * 20 + rng.uniform(70, 170)
    for _ in range(n_distractors):
        cy, cx = rng.uniform(0, h), rng.uniform(0, w)
        ry, rx = rng.uniform(8, 40), rng.uniform(5, 25)
        bg[_ellipse_mask(h, w, cy, cx, ry, rx)] += rng.uniform(-50, 50)
    bg = np.clip(bg, 0, 255)

    sprites, starts, vels = [], [], []
    for _ in range(n_people):
        sprites.append(_positive(rng))
        v = rng.uniform(-speed, speed, size=2)
        pos = np.empty(2)
        for ax, lim in ((0, h - H), (1, w - W)):
            travel = v[ax] * (T - 1)
            lo, hi = max(0.0, -travel), min(lim, lim - travel)
            if lo > hi:
                v[ax] = np.sign(v[ax]) * lim / max(T - 1, 1)
                travel = v[ax] * (T - 1)
                lo, hi = max(0.0, -travel), min(lim, lim - travel)
            pos[ax] = rng.uniform(lo, hi)
        starts.append(pos)
        vels.append(v)

    tint = rng.uniform(0.9, 1.1, size=3)
    frames = np.empty((T, h, w, 3), np.uint8)
    for t in range(T):
        scene = bg.copy()
        for i in range(n_people):
            y, x = starts[i] + vels[i] * t + rng.normal(0, jitter, 2)
            y0 = int(np.clip(round(y), 0, h - H))
            x0 = int(np.clip(round(x), 0, w - W))
            scene[y0:y0 + H, x0:x0 + W] = sprites[i]
        rgb = np.stack([scene * c for c in tint], axis=-1)
        rgb += rng.normal(0, frame_noise, size=rgb.shape)
        frames[t] = np.clip(rgb, 0, 255).astype(np.uint8)
    return frames
