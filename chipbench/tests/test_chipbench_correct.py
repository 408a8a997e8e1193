"""How `correct` is decided, rehearsed on the CPU at a tiny frame size.

The plain reference reproduces the float64 golden fixture of the
program's own tests; a whole harness run of the `perf` path (its Pallas
kernels interpreted) comes out correct; the reference computed in a
lower precision, put in the program's place, does not; and a run whose
served answers are broken underneath comes out not correct, once for
each fault a serving cell can have.
"""
import copy
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import cells, compare, reference, traffic  # noqa: E402
from chipbench import run as bench  # noqa: E402

SEED = 2 ** 32 + 99


def tiny_cell(name="hd1080_perf.single_cam"):
    """The cell with the multi-camera mix at a frame size the CPU holds:
    160x192 frames, two pyramid levels, two cameras at 10 fps, and a
    threshold low enough that every frame serves a handful of boxes."""
    c = copy.deepcopy(cells.cell(name))
    c.traffic = cells.traffic("cams")
    c.config["frame"] = {"h": 160, "w": 192}
    c.config["detector"].update(scales=[1.0, 0.85], score_threshold=-30.0)
    c.config["service"]["frame_batch"] = 2
    c.traffic.update(cameras=2, fps=10, clip={"frames": 2, "people": 1},
                     batch_sizes=[1, 2], check_frames=6)
    return c


def test_reference_reproduces_the_golden_descriptors():
    g = np.load(ROOT / "tests" / "golden" / "hog_golden.npz")
    for win, want in zip(g["windows"], g["descriptors"]):
        gray = reference.gray(win)[:130, :66]
        got = reference.hog_blocks(gray)
        np.testing.assert_allclose(got.reshape(-1), want, atol=1e-6)


def _run(cell, patch=None):
    peak = cells.peaks("TPU v5 lite")
    return bench.run(cell, SEED, 1.0, False, peak, time.monotonic(),
                     lambda m: None, patch=patch)


@pytest.fixture(scope="module")
def sound():
    return _run(tiny_cell())


def test_the_perf_path_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] == 20
    assert list(sound)[-1] == "checks"


@pytest.mark.parametrize("dtype", ["int8", "float8_e4m3fn"])
def test_a_lower_precision_control_is_not_correct(dtype):
    cell = tiny_cell()
    clip_frames = bench.frames(cell, SEED, lambda m: None)
    w, b = bench.load_svm(cell.config)
    w = np.asarray(w, np.float64)
    per_frame = []
    for clip in clip_frames:
        for frame in clip:
            args = compare.reference_args(cell.config, frame.shape[:2])
            low = reference.detect(frame, w, b, blocks_dtype=dtype, **args)
            per_frame.append(compare.check_frame(
                compare.served_from_reference(low), frame, w, b,
                cell.config))
    checks = compare.combine(per_frame, 0)
    assert not compare.passed(checks), checks


@pytest.fixture(scope="module")
def archive():
    """The archive cell's own sample: 48 of its 640x480 frames at its
    threshold, each with its float64 reference answer."""
    cell = cells.cell("vga_perf.archive")
    clip_frames = bench.frames(cell, SEED, lambda m: None)
    pairs = [(s, f) for s, c in enumerate(clip_frames) for f in range(len(c))]
    frames = [clip_frames[s][f] for s, f in (pairs[i] for i in
              traffic.check_sample(len(pairs), cell.traffic["check_frames"],
                                   SEED))]
    w, b = bench.load_svm(cell.config)
    w = np.asarray(w, np.float64)
    refs = [reference.detect(f, w, b, **compare.reference_args(
        cell.config, f.shape[:2])) for f in frames]
    return cell, frames, w, b, refs


@pytest.mark.parametrize("answer,correct", [
    ("bfloat16", True), ("float8_e4m3fn", False), ("half_left_out", False)])
def test_the_check_separates_at_the_archive_cells_own_size(archive, answer,
                                                           correct):
    """The program's own arithmetic (bf16 descriptors and weights) passes,
    the float8 control and a run that answers every other frame with no
    box fail, on the archive cell's frames and threshold."""
    cell, frames, w, b, refs = archive
    per_frame = []
    for n, (frame, ref) in enumerate(zip(frames, refs)):
        if answer == "half_left_out":
            dets = compare.served_from_reference(ref) if n % 2 == 0 else []
        else:
            dets = compare.served_from_reference(reference.detect(
                frame, w, b, blocks_dtype=answer,
                **compare.reference_args(cell.config, frame.shape[:2])))
        per_frame.append(compare.check_frame(dets, frame, w, b, cell.config,
                                             ref=ref))
    checks = compare.combine(per_frame, 0)
    assert sum(f["ref_boxes"] for f in per_frame) >= 24
    assert compare.passed(checks) is correct, checks


def _answers(fn):
    """A patch that rewrites each frame answer where the service
    resolves it."""
    def patch(svc):
        answer = svc._answer_frame

        def broken(req, payload):
            if "detections" in payload and "error" not in payload:
                payload = dict(payload, detections=fn(payload["detections"]))
            return answer(req, payload)

        svc._answer_frame = broken
    return patch


def _stale():
    last = [[]]

    def fn(dets):
        prev, last[0] = last[0], dets
        return prev
    return fn


def _half():
    n = [0]

    def fn(dets):
        n[0] += 1
        return dets if n[0] % 2 else []
    return fn


def _altered():
    def fn(dets):
        if not dets:
            return dets
        y0, x0, y1, x1 = dets[0]["box"]
        return [dict(dets[0], box=(y0, x0 + 8.0, y1, x1 + 8.0))] + dets[1:]
    return fn


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_a_broken_timed_path_is_not_correct(sound, fault):
    fn = {"state_unchanged": _stale, "half_left_out": _half,
          "answer_altered": _altered}[fault]()
    res = _run(tiny_cell(), patch=_answers(fn))
    assert not res["correct"], res["checks"]
