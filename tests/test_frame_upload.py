"""Every RGB frame reaches the untiled frame programs as interleaved rows.

`detect_raw`, `detect_batch_raw` and the mixed-size path hand an
(h, w, 3) RGB frame over as (h, w*3) rows, a stack as (B, h, w*3), and
the program turns it into gray by its static shape (detector._luma); a
gray frame travels as it is. These cases hold every entry point's
answers byte for byte to the gray-first reference, pin the argument each
program is noted with, keep a caller's device array alive under
donation, and count the layouts handed over (detector.frame_uploads).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import detector
from repro.core.detector import DetectorConfig, FrameDetector, frame_uploads
from repro.core.hog import grayscale
from repro.serve.engine import DetectionService

RNG = np.random.default_rng(17)
SVM = {"w": jnp.asarray(RNG.normal(size=3780).astype(np.float32) * .01),
       "b": jnp.float32(0.0)}
CFG = DetectorConfig(score_threshold=-5.0, scales=(1.0, 0.8), batch_chunk=2)
H, W = 150, 100


def _frames(n, rgb, h=H, w=W):
    shape = (h, w, 3) if rgb else (h, w)
    return [RNG.integers(0, 256, shape).astype(np.uint8) for _ in range(n)]


def gray_first(det, frames, fused=True):
    """The gray-first reference, leaves stacked over `frames`: each
    frame's `hog.grayscale` (a gray frame as float32), edge-padded to its
    bucket, through `_frame_program(ph, pw, cfg).raw`. `fused` traces the
    three as one program, as the frame programs run them; otherwise the
    gray plane is made first, outside, as the mixed-size path makes it."""
    outs = []
    for f in frames:
        h, w = f.shape[:2]
        prog, ph, pw = det.program_for(h, w)

        def prep(x):
            g = grayscale(x) if x.ndim == 3 else x.astype(jnp.float32)
            return jnp.pad(g, ((0, ph - h), (0, pw - w)), mode="edge")

        hw = jnp.asarray([h, w], jnp.float32)
        if fused:
            out = jax.jit(lambda x, wv, bv, hw_: prog.raw(
                prep(x), wv, bv, hw_))(jnp.asarray(f), SVM["w"], SVM["b"], hw)
        else:
            out = prog.fn(prep(jnp.asarray(f)), SVM["w"], SVM["b"], hw)
        outs.append([np.asarray(a) for a in out])
    return [np.stack(leaf) for leaf in zip(*outs)]


def assert_same(got, want):
    leaves = jax.tree_util.tree_leaves(got)
    assert len(leaves) == len(want) == 4
    for a, b in zip(leaves, want):
        a = np.asarray(a).reshape(b.shape)
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("rgb", [True, False], ids=["rgb", "gray"])
@pytest.mark.parametrize("entry", ["single", "stacked", "list", "jax_array",
                                   "mixed"])
def test_frames_answer_as_the_gray_first_reference(entry, rgb):
    det = FrameDetector(SVM, CFG)
    frames = _frames(3, rgb)
    if entry == "single":
        for f in frames:
            assert_same(det.detect_raw(f), gray_first(det, [f]))
        return
    if entry == "mixed":
        # two true sizes in one bucket: the host-side gray path
        frames = [frames[0], frames[1][:140, :98]]
        assert_same(det.detect_batch_raw(frames),
                    gray_first(det, frames, fused=False))
        return
    batch = {"stacked": lambda: np.stack(frames),
             "list": lambda: frames,
             "jax_array": lambda: jnp.asarray(np.stack(frames))}[entry]()
    got = det.detect_batch_raw(batch)
    assert got.to_list() and any(got.to_list()), "vacuous: no boxes"
    assert_same(got, gray_first(det, frames))


@pytest.mark.parametrize("batched", [False, True], ids=["single", "batch"])
def test_rgb_programs_are_noted_with_interleaved_rows(batched):
    """The first argument `_dispatch` notes for an RGB program (what
    `op_scopes` lowers again) has a trailing dim of 3*w."""
    cfg = dataclasses.replace(CFG, score_threshold=-5.125)
    det = FrameDetector(SVM, cfg)
    frames = _frames(2, True)
    if batched:
        det.detect_batch_raw(np.stack(frames))
        fn = detector._batch_fn(H, W, 160, 128, 2, cfg, detector._donate())
        want = (2, H, 3 * W)
    else:
        det.detect_raw(frames[0])
        fn = detector._single_fn(H, W, 160, 128, cfg)
        want = (H, 3 * W)
    assert detector._PROGRAMS[fn]["args"][0].shape == want


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("rgb", [True, False], ids=["rgb", "gray"])
@pytest.mark.parametrize("batched", [False, True], ids=["single", "batch"])
def test_caller_arrays_survive_donation(monkeypatch, batched, rgb):
    """With the programs donating their frame argument (as on a chip), a
    caller's own jax.Array is never the buffer handed over, and is still
    readable after the call: an RGB one was reshaped into a new buffer,
    a gray one is copied first."""
    monkeypatch.setattr(detector, "_donate", lambda: True)
    handed = []
    dispatch = detector._dispatch

    def noting(fn, args, *rest):
        handed.append(args[0])
        return dispatch(fn, args, *rest)

    monkeypatch.setattr(detector, "_dispatch", noting)
    # a config of its own: the program caches key on it, so these
    # programs are built donating
    cfg = dataclasses.replace(CFG, score_threshold=-5.25 - 0.5 * rgb)
    det = FrameDetector(SVM, cfg)
    frames = _frames(2, rgb)
    host = np.stack(frames) if batched else frames[0]
    mine = jnp.asarray(host)
    got = det.detect_batch_raw(mine) if batched else det.detect_raw(mine)
    jax.block_until_ready(jax.tree_util.tree_leaves(got))
    assert len(handed) == 1 and handed[0] is not mine
    assert not mine.is_deleted()
    assert np.array_equal(np.asarray(mine), host)
    assert_same(got, gray_first(det, frames if batched else frames[:1]))


def test_service_uploads_count_interleaved_frames():
    """A served batch and a lone frame both reach the programs as
    interleaved rows; no frame goes over as (..., w, 3)."""
    before = frame_uploads()
    svc = DetectionService(SVM, detector=CFG).start()
    try:
        frames = _frames(3, True)
        batch = svc.detect_frames(frames)
        lone = svc.submit_frame(frames[0]).get(timeout=120)
    finally:
        svc.stop()
    assert all("error" not in r for r in batch + [lone])
    after = frame_uploads()
    assert after["interleaved"]["frames"] - \
        before["interleaved"]["frames"] >= 4
    assert after["interleaved"]["bytes"] - \
        before["interleaved"]["bytes"] >= 4 * H * W * 3
    assert after["minor3"] == before["minor3"] == {"frames": 0, "bytes": 0}
    assert after["gray"] == before["gray"]
