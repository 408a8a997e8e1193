"""Hard-negative mining on synthetic scenes (Dalal-Triggs bootstrapping).

A head trained only on window-sized synthetic crops has a domain gap at
detection time: the pyramid's downscaled levels average the per-pixel
sensor noise away, so background there is SMOOTHER than any training
negative and the dense score field lights up far from every pedestrian
(empty 640x480 scenes score 8+ at sub-unit scales). The classic fix is
bootstrapping: sweep the current head over person-free scenes at a very
loose threshold, crop every firing window back to training-window
geometry, and retrain with those crops as negatives. Two rounds drop
the empty-scene detection count from ~20 to ~0-3 at threshold 3 while
keeping every pedestrian -- which is what makes the two-stage cascade's
retention/speedup contract (core/cascade.py) meaningful.
`DetectionSession.train(hard_negative_rounds=N)` and `train_coarse_head`
drive this loop.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.core.svm import SVMParams

MINE_THRESHOLD = -1.0      # loose sweep gate: mine anything remotely firing


def mine_hard_negatives(svm: SVMParams, det_cfg, n_scenes: int,
                        rng: np.random.Generator,
                        scene_hw: Tuple[int, int] = (480, 640),
                        threshold: float = MINE_THRESHOLD,
                        window_hw: Optional[Tuple[int, int]] = None
                        ) -> np.ndarray:
    """Sweep `svm` over `n_scenes` person-free synthetic scenes with the
    given DetectorConfig at a LOOSE threshold and return every firing
    window as a training-geometry crop: (N, wh, ww, 3) uint8, where
    (wh, ww) defaults to det_cfg's HOG window. N varies with how noisy
    the head still is -- it shrinks round over round.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.detector import FrameDetector
    from repro.data.synth_pedestrian import make_scene

    h, w = int(scene_hw[0]), int(scene_hw[1])
    wh, ww = window_hw or (det_cfg.hog.window_h, det_cfg.hog.window_w)
    det = FrameDetector(svm, dataclasses.replace(
        det_cfg, score_threshold=float(threshold), class_thresholds=()))
    crops = []
    for _ in range(int(n_scenes)):
        scene, _ = make_scene(rng, h, w, n_people=0)
        for d in det.detect_raw(scene).to_list():
            y0, x0, y1, x1 = [int(round(v)) for v in d["box"]]
            y0, x0 = max(0, y0), max(0, x0)
            y1, x1 = min(h, y1), min(w, x1)
            if y1 - y0 < wh // 3 or x1 - x0 < ww // 3:
                continue
            crops.append(np.asarray(jax.image.resize(
                jnp.asarray(scene[y0:y1, x0:x1], jnp.float32),
                (wh, ww, 3), "linear")))
    if not crops:
        return np.zeros((0, wh, ww, 3), np.uint8)
    return np.clip(np.stack(crops), 0, 255).astype(np.uint8)
