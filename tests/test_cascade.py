"""Two-stage cascade: region-planner invariants + scheduler behavior.

The cascade's correctness story (core/cascade.py, DESIGN.md §13) rests
on one planner invariant -- every candidate box's dilated rect is
covered by the returned region union (bounding rects only grow under
merging; edges only snap outward) -- from which threshold MONOTONICITY
follows: loosening the coarse reject threshold only adds candidate
boxes, so any survivor neighbourhood at a tight threshold is still
covered at a looser one. These tests pin the invariant directly
(random box sets, random planner knobs, subset-vs-superset coverage)
and the scheduler seams around it: the empty-frame shortcut, the dense
fallback below `min_frame_area`, region-local boxes mapping back to
frame coordinates, tracker-ROI promotion past the coarse gate, and
end-to-end retention vs the full dense pass on a trained head.
"""
import dataclasses

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.cascade import (CascadeConfig, CascadeDetector,
                                coarse_detector, plan_regions)
from repro.core.detector import DetectorConfig, FrameDetector
from repro.core.hog import HOGConfig

SEED = 11


def _rand_boxes(rng, n, h, w):
    y0 = rng.uniform(0, h * 0.8, n)
    x0 = rng.uniform(0, w * 0.8, n)
    return np.stack([y0, x0, y0 + rng.uniform(10, h * 0.3, n),
                     x0 + rng.uniform(10, w * 0.3, n)], -1).astype(np.float32)


def _covered(rect, regions, tol=1e-5):
    """rect fully inside the union of regions? (regions are axis-
    aligned; the planner only merges, so containment in ONE region is
    the realized invariant -- check that, the stronger condition)."""
    y0, x0, y1, x1 = rect
    return any(ry0 <= y0 + tol and rx0 <= x0 + tol
               and y1 <= ry1 + tol and x1 <= rx1 + tol
               for ry0, rx0, ry1, rx1 in regions)


def _dilated(boxes, frame_hw, cfg):
    h, w = frame_hw
    m = float(cfg.margin)
    return np.stack([
        np.clip(boxes[:, 0] - m, 0, h), np.clip(boxes[:, 1] - m, 0, w),
        np.clip(boxes[:, 2] + m, 0, h), np.clip(boxes[:, 3] + m, 0, w),
    ], axis=1)


# ------------------------------------------------------ planner invariants

def check_planner(seed):
    rng = np.random.default_rng(seed)
    h, w = int(rng.integers(200, 800)), int(rng.integers(200, 800))
    cfg = CascadeConfig(margin=int(rng.integers(0, 48)),
                        snap=int(rng.choice([16, 32, 64])),
                        max_regions=int(rng.integers(1, 6)))
    boxes = _rand_boxes(rng, int(rng.integers(1, 20)), h, w)
    regions = plan_regions(boxes, (h, w), cfg)
    assert 1 <= len(regions) <= cfg.max_regions
    for y0, x0, y1, x1 in regions:
        assert 0 <= y0 < y1 <= h and 0 <= x0 < x1 <= w
        # snapped: every edge on the grid unless clamped by the frame
        assert y0 % cfg.snap == 0 and x0 % cfg.snap == 0
        assert y1 % cfg.snap == 0 or y1 == h
        assert x1 % cfg.snap == 0 or x1 == w
    # coverage invariant: every dilated candidate box sits inside a region
    for rect in _dilated(boxes, (h, w), cfg):
        assert _covered(rect, regions), (rect, regions)


def test_planner_invariants_seeded():
    for s in range(40):
        check_planner(SEED * 1000 + s)


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=0, max_value=100_000))
def test_planner_invariants_hypothesis(seed):
    check_planner(seed)


def check_threshold_monotonicity(seed):
    """Loosening the reject threshold never loses a survivor: the
    candidate set at a TIGHT threshold is a subset of the set at a
    LOOSE one, and the loose plan still covers every tight candidate's
    dilated neighbourhood."""
    rng = np.random.default_rng(seed)
    h = w = 640
    cfg = CascadeConfig(margin=24, snap=32,
                        max_regions=int(rng.integers(1, 5)))
    boxes = _rand_boxes(rng, 16, h, w)
    scores = rng.uniform(-1.0, 1.0, len(boxes)).astype(np.float32)
    tight, loose = 0.4, -0.2
    tight_boxes = boxes[scores > tight]
    loose_boxes = boxes[scores > loose]
    assert set(map(tuple, tight_boxes)) <= set(map(tuple, loose_boxes))
    if len(tight_boxes) == 0:
        return
    loose_regions = plan_regions(loose_boxes, (h, w), cfg)
    for rect in _dilated(tight_boxes, (h, w), cfg):
        assert _covered(rect, loose_regions), \
            "loose-threshold plan lost a tight-threshold survivor"


def test_threshold_monotonicity_seeded():
    for s in range(40):
        check_threshold_monotonicity(SEED * 2000 + s)


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=0, max_value=100_000))
def test_threshold_monotonicity_hypothesis(seed):
    check_threshold_monotonicity(seed)


def test_planner_edge_cases():
    assert plan_regions(np.zeros((0, 4), np.float32), (480, 640)) == []
    # one box -> one snapped region containing it
    cfg = CascadeConfig(margin=16, snap=32, max_regions=4)
    r = plan_regions(np.asarray([[100, 100, 230, 166]], np.float32),
                     (480, 640), cfg)
    assert len(r) == 1 and _covered((84, 84, 246, 182), r)
    # max_regions=1 merges everything into one rect
    boxes = np.asarray([[0, 0, 50, 50], [400, 500, 470, 620]], np.float32)
    r1 = plan_regions(boxes, (480, 640),
                      dataclasses.replace(cfg, max_regions=1))
    assert len(r1) == 1
    for rect in _dilated(boxes, (480, 640), cfg):
        assert _covered(rect, r1)


# --------------------------------------------------------- scheduler seams

def _rand_head(rng, f):
    return {"w": rng.normal(0, 0.05, (f,)).astype(np.float32),
            "b": np.float32(0.0)}


def _fine_and_coarse(rng, fine_thr=-2.0, coarse_thr=0.0, **casc_kw):
    casc = CascadeConfig(coarse_threshold=coarse_thr, **casc_kw)
    fine_cfg = DetectorConfig(score_threshold=fine_thr)
    fine = FrameDetector(_rand_head(rng, fine_cfg.hog.n_features), fine_cfg)
    coarse = coarse_detector(
        _rand_head(rng, coarse_detector(
            {"w": np.zeros(756, np.float32), "b": 0.0}, fine_cfg,
            casc).cfg.hog.n_features),
        fine_cfg, casc)
    return CascadeDetector(fine, coarse, casc), fine


def test_empty_frame_shortcut():
    rng = np.random.default_rng(SEED)
    # coarse threshold far above any reachable score -> zero candidates
    casc, _ = _fine_and_coarse(rng, coarse_thr=1e9)
    out = casc.detect(rng.integers(0, 255, (320, 416, 3), np.uint8))
    assert out == []
    assert casc.stats["frames_empty"] == 1 and casc.stats["regions"] == 0


def test_dense_fallback_below_min_area():
    rng = np.random.default_rng(SEED + 1)
    casc, fine = _fine_and_coarse(rng, coarse_thr=1e9,
                                  min_frame_area=10**9)
    frame = rng.integers(0, 255, (320, 416, 3), np.uint8)
    assert casc.detect(frame) == fine.detect_raw(frame).to_list()
    assert casc.stats["frames_dense"] == 1


def test_roi_promotion_bypasses_coarse_gate():
    """With the coarse stage rejecting everything, a promoted ROI box
    still gets its neighbourhood scored by the fine stage -- and every
    returned box lands inside the planned region, in FRAME coords."""
    rng = np.random.default_rng(SEED + 2)
    casc, fine = _fine_and_coarse(rng, coarse_thr=1e9, margin=24, snap=32)
    frame = rng.integers(0, 255, (480, 640, 3), np.uint8)
    roi = (96.0, 96.0, 280.0, 240.0)
    out = casc.detect(frame, roi_boxes=[roi])
    assert out, "fine stage at threshold -2 must fire inside the ROI"
    assert casc.stats["regions"] == 1
    regions = plan_regions(np.asarray([roi], np.float32), (480, 640),
                           casc.cfg)
    (ry0, rx0, ry1, rx1), = regions
    for d in out:
        y0, x0, y1, x1 = d["box"]
        assert ry0 <= y0 and rx0 <= x0 and y1 <= ry1 and x1 <= rx1
    # the region-local detections must agree with a direct fine pass on
    # the same crop, offset back to frame coordinates
    crop_dets = fine.detect_raw(
        np.asarray(frame)[ry0:ry1, rx0:rx1]).to_list()
    crop_boxes = {tuple(round(v + o, 3) for v, o in
                        zip(d["box"], (ry0, rx0, ry0, rx0)))
                  for d in crop_dets}
    assert {tuple(round(v, 3) for v in d["box"])
            for d in out} <= crop_boxes


def test_region_area_accounting():
    rng = np.random.default_rng(SEED + 3)
    casc, _ = _fine_and_coarse(rng, coarse_thr=1e9, margin=16, snap=32)
    frame = rng.integers(0, 255, (480, 640, 3), np.uint8)
    casc.detect(frame, roi_boxes=[(0.0, 0.0, 160.0, 160.0)])
    assert 0.0 < casc.stats["region_area_frac"] < 0.5


# ----------------------------------------------------- end-to-end retention

@pytest.fixture(scope="module")
def trained():
    from repro.api import DetectionSession, presets
    # one bootstrap round keeps the quick head's score field clean
    # enough that region crops localize people stably (data/mining.py)
    sess = DetectionSession.train(presets("cascade"), n_pos=250, n_neg=180,
                                  hard_negative_rounds=1, mine_scenes=6)
    casc = sess.cascade(rng=np.random.default_rng(SEED))
    return sess, casc


def _iou(a, b):
    y0, x0 = max(a[0], b[0]), max(a[1], b[1])
    y1, x1 = min(a[2], b[2]), min(a[3], b[3])
    inter = max(0.0, y1 - y0) * max(0.0, x1 - x0)
    ua = (a[2] - a[0]) * (a[3] - a[1]) + (b[2] - b[0]) * (b[3] - b[1])
    return inter / (ua - inter + 1e-9)


def test_cascade_retention_on_synthetic_scene(trained):
    """Every dense-pass detection of an ACTUAL pedestrian must survive
    the cascade. The quickly-trained test head also fires on background
    clutter far from any person, and those may be dropped; the
    invariant is that no true detection is lost."""
    from repro.data.synth_pedestrian import make_scene
    sess, casc = trained
    rng = np.random.default_rng(SEED + 4)
    kept = total = 0
    for i in range(3):
        scene, truth = make_scene(rng, 480, 640, n_people=2,
                                  region=(0, 0, 320, 320))
        tboxes = [(y, x, y + th, x + tw) for y, x, th, tw in truth]
        full = [d for d in sess.detect(scene).to_list()
                if any(_iou(d["box"], t) >= 0.4 for t in tboxes)]
        cd = casc.detect(scene)
        total += len(full)
        # retained = matched directly (IoU >= 0.5, same class) OR a
        # cascade detection reports the same ground-truth pedestrian --
        # region-crop NMS may keep a slightly shifted box for the same
        # person (the crop's HOG grid is offset vs the full frame)
        for f in full:
            gt = max(range(len(tboxes)),
                     key=lambda j: _iou(f["box"], tboxes[j]))
            kept += any(
                f.get("class_id") == c.get("class_id")
                and (_iou(f["box"], c["box"]) >= 0.5
                     or _iou(c["box"], tboxes[gt]) >= 0.4)
                for c in cd)
    assert total > 0, "dense pass found nothing -- scene too hard"
    assert kept / total >= 0.99, f"cascade retained {kept}/{total}"


def test_cascade_stream_tracks_through_coarse_misses(trained):
    """Video contract: once a track exists, its predicted box is
    promoted past the coarse gate, so detections persist even when the
    coarse stage is blinded (threshold jacked to reject everything)."""
    from repro.data.synth_pedestrian import make_scene
    from repro.core.video import Tracker
    sess, casc = trained
    rng = np.random.default_rng(SEED + 5)
    scene, _ = make_scene(rng, 480, 640, n_people=1,
                          region=(0, 0, 288, 224))
    trk = Tracker()
    first = casc.detect(scene)
    if not first:
        pytest.skip("coarse stage found nothing on this seed")
    trk.update(first)
    blind = CascadeDetector(
        casc.fine, FrameDetector(
            casc.coarse.svm,
            dataclasses.replace(casc.coarse.cfg, score_threshold=1e9)),
        casc.cfg)
    out = blind.stream([scene, scene], tracker=trk)
    assert out[0], "promoted track ROI must keep detections alive"
    assert all("track_id" in d for d in out[0])


def test_fine_hysteresis_builds_looser_crop_detector():
    """fine_hysteresis > 0 gives the region-crop stage its own detector
    at (score_threshold - hysteresis); 0 reuses the fine detector
    object unchanged."""
    svm = {"w": np.zeros(3780, np.float32), "b": np.float32(0.0)}
    fine = FrameDetector(svm, DetectorConfig(score_threshold=4.0))
    casc0 = CascadeDetector(fine, fine, CascadeConfig())
    assert casc0._crop_fine is fine
    casc = CascadeDetector(fine, fine,
                           CascadeConfig(fine_hysteresis=1.5))
    assert casc._crop_fine is not fine
    assert casc._crop_fine.cfg.score_threshold == pytest.approx(2.5)
    # everything except the threshold band carries over
    assert casc._crop_fine.cfg.scales == fine.cfg.scales
    assert casc._crop_fine.svm is fine.svm


def test_mine_hard_negatives_geometry_and_dtype():
    """Mined crops come back stacked in the training-window geometry
    (uint8 RGB), for both the fine and the coarse head shapes."""
    from repro.core.cascade import coarse_hog
    from repro.core.hog import PAPER_HOG
    from repro.data.mining import mine_hard_negatives
    rng = np.random.default_rng(SEED + 6)
    # an untrained (zero) head fires nowhere at a positive threshold...
    svm = {"w": np.zeros(3780, np.float32), "b": np.float32(0.0)}
    out = mine_hard_negatives(svm, DetectorConfig(score_threshold=0.5),
                              1, rng, scene_hw=(256, 256), threshold=0.5)
    assert out.shape == (0, PAPER_HOG.window_h, PAPER_HOG.window_w, 3)
    # ...and fires everywhere at a negative one: crops must stack to
    # the requested window geometry
    ch = coarse_hog(PAPER_HOG)
    csvm = {"w": np.zeros(ch.n_features, np.float32), "b": np.float32(0.0)}
    out = mine_hard_negatives(
        csvm, DetectorConfig(hog=ch, scales=(0.5,)), 1, rng,
        scene_hw=(256, 256), threshold=-1.0,
        window_hw=(ch.window_h, ch.window_w))
    assert out.ndim == 4 and len(out) > 0
    assert out.shape[1:] == (ch.window_h, ch.window_w, 3)
    assert out.dtype == np.uint8
