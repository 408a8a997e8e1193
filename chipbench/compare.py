"""How `correct` is decided: served answers against the plain reference.

For each sampled frame, the served answer (the boxes and scores left
after threshold, top-k and NMS, as the service decoded them) is held
against `reference.detect` on the same frame:

  score_gap    the widest gap between a served score and the reference's
               score of the same window (max over the sample);
  unexplained  served boxes that are no window of the pyramid, whose
               reference score is below the threshold by more than
               MARGIN, that overlap a higher-scored served box by more
               than the NMS IoU, or that clear the reference's cut by
               MARGIN and overlap no reference detection;
  missed       reference detections clearing the cut by MARGIN that no
               served box overlaps by more than the NMS IoU.

The cut is the threshold, or the reference's K-th best score on a frame
whose candidates overflow the top K. The two counts do not depend on
which of two near-equal overlapping boxes NMS keeps: a clear detection
is either served or suppressed by a served box that overlaps it. Every
frame of the window also has to be answered (`failed`).

Each number has its limit in LIMITS; the readings each was set from
are in PERF.md.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from chipbench import reference

#: upper end of each number for a correct run
LIMITS = {"score_gap": 0.1, "unexplained": 0, "missed": 0, "failed": 0}
#: how far a score must clear a cut for the counts to hold it
MARGIN = 2 * LIMITS["score_gap"]
#: served box coordinates are float32; a window matches within this
BOX_TOL = 0.01


def detector_settings(cfg: dict) -> dict:
    d = cfg["detector"]
    return {"scales": tuple(d["scales"]), "step": int(d["shape_bucket"]),
            "threshold": float(d["score_threshold"]),
            "iou_thr": float(d["nms_iou"]),
            "max_detections": int(d["max_detections"])}


def check_frame(dets: List[dict], frame: np.ndarray, w: np.ndarray,
                b: float, cfg: dict, ref: Optional[reference.FrameResult]
                = None) -> Dict[str, float]:
    """The numbers of one frame. `ref` is computed when not given."""
    s = detector_settings(cfg)
    if ref is None:
        k = reference.top_k_size(
            len(reference.window_boxes(*frame.shape[:2], s["scales"],
                                       s["step"])), s["max_detections"])
        ref = reference.detect(frame, w, b, scales=s["scales"],
                               step=s["step"], threshold=s["threshold"],
                               iou_thr=s["iou_thr"], k=k)
    thr, iou_thr = s["threshold"], s["iou_thr"]
    valid = np.flatnonzero(ref.valid)
    k = len(ref.top)
    cut = thr if len(valid) <= k else float(ref.scores[ref.top[-1]])
    boxes = np.asarray([d["box"] for d in dets], np.float64).reshape(-1, 4)
    scores = np.asarray([d["score"] for d in dets], np.float64)

    gap, unexplained = 0.0, 0
    ref_score = np.full(len(dets), -np.inf)
    for i, box in enumerate(boxes):
        err = np.abs(ref.boxes - box).max(axis=1)
        j = int(np.argmin(err))
        if err[j] > BOX_TOL:
            unexplained += 1
            continue
        ref_score[i] = ref.scores[j]
        gap = max(gap, abs(scores[i] - ref.scores[j]))
    unexplained += int(np.sum(np.isfinite(ref_score)
                              & (ref_score < thr - MARGIN)))
    if len(boxes) > 1:
        order = np.argsort(-scores, kind="stable")
        ov = reference.iou(boxes[order], boxes[order])
        unexplained += int(np.sum(np.any(np.triu(ov > iou_thr, 1), axis=0)))
    kept = ref.boxes[ref.kept]
    clear_served = np.flatnonzero(ref_score >= cut + MARGIN)
    if len(clear_served):
        cover = reference.iou(boxes[clear_served], kept) > iou_thr \
            if len(kept) else np.zeros((len(clear_served), 1), bool)
        unexplained += int(np.sum(~np.any(cover, axis=1)))
    clear_ref = ref.kept[ref.scores[ref.kept] >= cut + MARGIN]
    missed = 0
    if len(clear_ref):
        cover = reference.iou(ref.boxes[clear_ref], boxes) > iou_thr \
            if len(boxes) else np.zeros((len(clear_ref), 1), bool)
        missed = int(np.sum(~np.any(cover, axis=1)))
    return {"score_gap": gap, "unexplained": unexplained, "missed": missed,
            "boxes": len(dets), "ref_boxes": len(ref.kept),
            "candidates": int(len(valid)), "k": k}


def combine(per_frame: Iterable[Dict[str, float]], failed: int
            ) -> Dict[str, Dict[str, float]]:
    """The compared numbers over the sample, each beside its limit."""
    per_frame = list(per_frame)
    got = {"score_gap": max([f["score_gap"] for f in per_frame],
                            default=0.0),
           "unexplained": sum(f["unexplained"] for f in per_frame),
           "missed": sum(f["missed"] for f in per_frame),
           "failed": failed}
    return {k: {"value": got[k], "limit": LIMITS[k]} for k in LIMITS}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def served_from_reference(res: reference.FrameResult) -> List[dict]:
    """A reference result decoded as the service decodes the program's:
    the kept boxes with their scores, best first. Puts the reference in
    the program's place for the control."""
    return [{"box": tuple(res.boxes[j]), "score": float(res.scores[j])}
            for j in res.kept]


def reference_args(cfg: dict, frame_hw: Tuple[int, int]) -> dict:
    s = detector_settings(cfg)
    n = len(reference.window_boxes(*frame_hw, s["scales"], s["step"]))
    return dict(scales=s["scales"], step=s["step"], threshold=s["threshold"],
                iou_thr=s["iou_thr"],
                k=reference.top_k_size(n, s["max_detections"]))
