"""DetectionSession facade: legacy-shim equivalence (golden fixtures +
scenes, byte-identical boxes), typed Detections contract, saturation
surfacing, warmup/cache stats, checkpoint round-trip, serve() wiring.
"""
import pathlib
import warnings

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.api import DetectionSession, Detections, PipelineConfig
from repro.api.config import ServiceConfig
from repro.core.detector import DetectorConfig, FrameDetector, detect
from repro.core.video import TrackerConfig, VideoDetector
from repro.data.synth_pedestrian import ClipConfig, make_clip, make_scene

GOLDEN = pathlib.Path(__file__).parent / "golden" / "hog_golden.npz"

RNG = np.random.default_rng(42)
SVM = {"w": jnp.asarray(RNG.normal(size=3780).astype(np.float32) * .01),
       "b": jnp.float32(0.0)}
CFG = DetectorConfig(score_threshold=-10.0, scales=(1.0, 0.8))


def _scene(seed, h=200, w=160):
    rng = np.random.default_rng(seed)
    return make_scene(rng, h, w, n_people=1)[0]


def _session(cfg=CFG, svm=SVM):
    return DetectionSession(svm, PipelineConfig(detector=cfg))


def _assert_identical(legacy, api):
    """Byte-identical: exact float equality, exact ordering."""
    assert legacy == api


# ------------------------------------------------- shim equivalence

def test_detect_shim_equivalent_on_golden_windows():
    """The golden-fixture windows + golden SVM params through the
    legacy detect() and through the session: byte-identical boxes."""
    z = np.load(GOLDEN)
    svm = {"w": jnp.asarray(z["svm_w"]), "b": jnp.asarray(z["svm_b"])}
    cfg = DetectorConfig(score_threshold=-1e9, scales=(1.0,))
    ses = DetectionSession(svm, PipelineConfig(detector=cfg))
    for i in range(z["windows"].shape[0]):
        win = z["windows"][i]                       # (130, 66, 3) uint8
        legacy = detect(win, svm, cfg)
        api = ses.detect(win).to_list()
        assert legacy, f"golden window {i} produced no detection"
        _assert_identical(legacy, api)


def test_detect_shim_equivalent_on_scene():
    scene = _scene(0)
    legacy = detect(scene, SVM, CFG)
    fd = FrameDetector(SVM, CFG)
    ses = _session()
    assert legacy
    _assert_identical(legacy, fd(scene))
    _assert_identical(legacy, ses.detect(scene).to_list())


def test_detect_batch_shim_equivalent():
    frames = [_scene(1), _scene(2), _scene(3)]
    fd = FrameDetector(SVM, CFG)
    ses = _session()
    legacy = fd.detect_batch(frames)
    api = ses.detect_batch(frames)
    assert any(legacy)
    _assert_identical(legacy, api.to_list())
    # per-frame slicing agrees with the whole-batch decode
    for i in range(3):
        _assert_identical(legacy[i], api.frame(i).to_list())


def test_stream_shim_equivalent_to_video_detector():
    rng = np.random.default_rng(5)
    clip, _ = make_clip(rng, ClipConfig(n_frames=5, n_people=1,
                                        h=160, w=128, frame_noise=4.0))
    cfg = DetectorConfig(score_threshold=-10.0, scales=(1.0,))
    tcfg = TrackerConfig()
    legacy = VideoDetector(SVM, cfg, tcfg).process_clip(list(clip),
                                                        batch_size=3)
    ses = DetectionSession(SVM, PipelineConfig(detector=cfg, tracker=tcfg))
    api = [d.to_list() for d in ses.stream(list(clip), batch_size=3)]
    assert len(api) == 5 and all(api)
    _assert_identical(legacy, api)
    assert all({"box", "score", "scale", "track_id", "hits",
                "misses"} <= set(d) for dets in api for d in dets)


# -------------------------------------------------- typed Detections

def test_detections_lazy_accessors_and_len():
    d = _session().detect(_scene(0))
    lst = d.to_list()
    assert len(d) == len(lst)
    np.testing.assert_array_equal(
        d.boxes, np.asarray([x["box"] for x in lst], np.float32))
    np.testing.assert_array_equal(
        d.scores, np.asarray([x["score"] for x in lst], np.float32))
    assert list(iter(d)) == lst
    scores = [x["score"] for x in lst]
    assert scores == sorted(scores, reverse=True)


def test_detections_stack_and_frame_roundtrip():
    ses = _session()
    singles = [ses.detect(_scene(i)) for i in (1, 2)]
    batched = Detections.stack(singles)
    assert batched.batched and batched.batch_size == 2
    for i, s in enumerate(singles):
        _assert_identical(s.to_list(), batched.frame(i).to_list())
    assert [f.to_list() for f in batched] == batched.to_list()


def test_detections_from_list_passthrough():
    dets = [{"box": (0.0, 0.0, 10.0, 5.0), "score": 2.0, "scale": 1.0,
             "track_id": 7, "hits": 3, "misses": 0}]
    d = Detections.from_list(dets)
    assert d.to_list() == dets                  # extra keys preserved
    assert len(d) == 1 and not d.saturated
    np.testing.assert_array_equal(d.boxes, [[0.0, 0.0, 10.0, 5.0]])


def test_detections_empty_frame():
    d = _session(DetectorConfig(scales=(1.0,))).detect(
        np.zeros((64, 64, 3), np.uint8))        # smaller than one window
    assert d.to_list() == [] and len(d) == 0
    assert d.saturated is False


# --------------------------------------------------------- saturation

def test_saturated_flag_single_and_batch():
    cfg = DetectorConfig(score_threshold=-1e9, scales=(1.0,),
                         max_detections=4)
    ses = _session(cfg)
    scene = _scene(0)
    d = ses.detect(scene)
    assert d.saturated is True
    with pytest.warns(RuntimeWarning, match="max_detections=4"):
        d.to_list()

    b = ses.detect_batch([scene, scene])
    sat = b.saturated
    assert sat.shape == (2,) and sat.all()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        assert len(b.to_list()) == 2


# ------------------------------------------- fetched batches: host slicing

def _stacked_svm(n):
    """n single-head SVMs stacked into one multi-class head set."""
    rng = np.random.default_rng(9)
    return {"w": jnp.asarray(rng.normal(size=(n, 3780)).astype(np.float32)
                             * .01),
            "b": jnp.zeros((n,), jnp.float32)}


def _warned(fn):
    """fn()'s result and the RuntimeWarning messages it raised."""
    with warnings.catch_warnings(record=True) as got:
        warnings.simplefilter("always", RuntimeWarning)
        out = fn()
    return out, [str(w.message) for w in got
                 if w.category is RuntimeWarning]


HOST_SLICE_CASES = {
    "single": (SVM, CFG),
    "single-saturated": (SVM, DetectorConfig(score_threshold=-1e9,
                                             scales=(1.0,),
                                             max_detections=4)),
    "multiclass": (_stacked_svm(2), CFG),
    "multiclass-saturated": (_stacked_svm(2), DetectorConfig(
        score_threshold=-1e9, scales=(1.0,), max_detections=4)),
}


@pytest.mark.parametrize("case", sorted(HOST_SLICE_CASES))
def test_fetched_batch_frames_slice_on_the_host(case):
    """A B=3 batch fetched once answers each frame from its host copy:
    numpy arrays equal to the device slice with the class axis kept, the
    answer and `saturated` of the per-frame `detect_raw`, the same
    RuntimeWarnings, and no device program (the batch's device buffers
    are deleted before any frame is sliced)."""
    svm, cfg = HOST_SLICE_CASES[case]
    fd = FrameDetector(svm, cfg)
    frames = [_scene(i) for i in (1, 2, 3)]
    lazy = fd.detect_batch_raw(frames)
    batch = fd.detect_batch_raw(frames)
    assert batch.fetch() is True and batch.fetch() is False
    lists, warned = _warned(batch.to_list)
    for leaf in jax.tree_util.tree_leaves(batch):
        leaf.delete()
    per_frame_warned = []
    for i, f in enumerate(frames):
        single = fd.detect_raw(f)
        want, w = _warned(single.to_list)
        per_frame_warned += w
        got = batch.frame(i)
        leaves = jax.tree_util.tree_leaves(got)
        assert all(type(a) is np.ndarray for a in leaves)
        for a, d in zip(leaves, jax.tree_util.tree_leaves(lazy.frame(i))):
            assert a.shape == d.shape and a.dtype == d.dtype
            np.testing.assert_array_equal(a, np.asarray(d))
        assert got.fetch() is False
        _assert_identical(want, got.to_list())
        _assert_identical(want, lists[i])
        np.testing.assert_array_equal(got.saturated, single.saturated)
        assert type(got.saturated) is type(single.saturated)
    assert warned == per_frame_warned
    assert bool(warned) == case.endswith("saturated")


@pytest.mark.parametrize("case", ["single", "multiclass"])
def test_unfetched_batch_frame_fetches_the_batch_once(case):
    """frame(i) of a batch never fetched makes the batch's one host copy
    and slices it: every frame gets numpy arrays and the per-frame
    `detect_raw` answer, and no frame copies anything again."""
    svm, cfg = HOST_SLICE_CASES[case]
    fd = FrameDetector(svm, cfg)
    frames = [_scene(i) for i in (1, 2, 3)]
    batch = fd.detect_batch_raw(frames)
    for i, f in enumerate(frames):
        got = batch.frame(i)
        assert all(type(a) is np.ndarray
                   for a in jax.tree_util.tree_leaves(got))
        assert got.fetch() is False
        _assert_identical(fd.detect_raw(f).to_list(), got.to_list())
    assert batch.fetch() is False


def test_host_built_results_need_no_fetch():
    """Results made on the host (empty, from_list, stack) hold their
    host copy already: fetch() copies nothing."""
    ses = _session()
    stacked = Detections.stack([ses.detect(_scene(i)) for i in (1, 2)])
    empty = _session(DetectorConfig(scales=(1.0,))).detect(
        np.zeros((64, 64, 3), np.uint8))
    listed = Detections.from_list([{"box": (0.0, 0.0, 10.0, 5.0),
                                    "score": 2.0, "scale": 1.0}])
    for d in (stacked, stacked.frame(1), empty, listed):
        assert d.fetch() is False


def test_unsaturated_flag_false_no_warning():
    d = _session().detect(_scene(0))
    assert d.saturated is False
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        d.to_list()                              # must not warn


# ------------------------------------------------- warmup / cache stats

def test_warmup_compiles_ahead_and_counts():
    ses = _session(DetectorConfig(score_threshold=-10.0, scales=(1.0,)))
    stats = ses.warmup([(150, 120), (2, 150, 120)])
    assert (150, 120) in stats["warmed"]
    assert (2, 150, 120) in stats["warmed"]
    before = ses.cache_stats()
    d = ses.detect(np.zeros((150, 120, 3), np.uint8))
    d.block_until_ready()
    after = ses.cache_stats()
    # the warmed shape must not recompile: no new program cache misses
    assert after["frame_programs"]["misses"] == \
        before["frame_programs"]["misses"]
    assert after["calls"]["frames"] == before["calls"]["frames"] + 1


def test_warmup_rejects_bad_shape():
    with pytest.raises(ValueError, match="warmup shape"):
        _session().warmup([(1, 2, 3, 4)])


# --------------------------------------------- checkpoint + serve wiring

def test_save_load_roundtrip(tmp_path):
    ses = _session()
    ses.save(str(tmp_path / "ckpt"), step=3)
    back = DetectionSession.load(str(tmp_path / "ckpt"),
                                 PipelineConfig(detector=CFG))
    np.testing.assert_array_equal(np.asarray(back.svm["w"]),
                                  np.asarray(SVM["w"]))
    np.testing.assert_array_equal(np.asarray(back.svm["b"]),
                                  np.asarray(SVM["b"]))
    scene = _scene(0)
    _assert_identical(ses.detect(scene).to_list(),
                      back.detect(scene).to_list())


def test_load_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        DetectionSession.load(str(tmp_path / "nothing"))


def test_serve_shares_session_detector():
    ses = DetectionSession(SVM, PipelineConfig(
        detector=CFG, service=ServiceConfig(window_batch=8,
                                            frame_batch=2)))
    svc = ses.serve()
    try:
        assert svc._detector is ses.detector      # shared programs
        assert svc.batch == 8 and svc.frame_batch == 2
        svc.start()
        res = svc.detect_frames([_scene(0)])
        assert len(res) == 1
        assert "saturated" in res[0] and "ms" in res[0]
        _assert_identical(res[0]["detections"],
                          ses.detect(_scene(0)).to_list())
    finally:
        svc.stop()


def test_serve_detector_override_builds_own():
    ses = _session()
    svc = ses.serve(detector=DetectorConfig(scales=(1.0,)))
    assert svc._detector is not ses.detector
    assert svc._detector.cfg.scales == (1.0,)
