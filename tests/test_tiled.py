"""Intra-frame (tiled) detection: seam identity + planner unit tests.

Two families, mirroring tests/test_sharded.py:

  * PLANNER / ARITHMETIC UNITS (always run, any device count): the
    banded-resize row identity that makes slab tiling exact, the
    row-sliced matmul identity the matmul resize mode relies on, the
    exact top-k merge, slab/scale-group planning, and auto-K.
  * TILED EQUIVALENCE (self-skip below 2 devices): single-frame and
    batched (data x tile) tiled programs must produce byte-identical
    `Detections.to_list()` output vs the untiled path, for both tile
    modes, divisible and non-divisible tile counts (padded-tile
    masking), and boxes that straddle slab seams. The CI `uhd-smoke`
    lane forces 8 host devices via REPRO_TEST_DEVICES=8.
"""
import dataclasses
import pathlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import detector
from repro.core.detector import (DetectorConfig, FrameDetector,
                                 _autotune_key_str, _banded_put, _donate,
                                 _frame_program, _resolve_fp, _resolve_k,
                                 _sharded_batch_fn, _tile_mesh,
                                 _tiled_batch_fn, _tiled_single_fn,
                                 hlo_op_names, op_scopes, tiled_uploads)
from repro.core.hog import PAPER_HOG, grayscale
from repro.core.tiling import (band_rows, band_weights, extend_band,
                               merge_topk, resize_banded, scale_groups,
                               slab_pixel_rows, slab_rows)
from repro.launch.mesh import make_tiled_mesh
from repro.serve.engine import DetectionService

multi = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs forced host devices (REPRO_TEST_DEVICES=8, CI lane "
           "'uhd-smoke')")

RNG = np.random.default_rng(23)
SVM = {"w": jnp.asarray(RNG.normal(size=3780).astype(np.float32) * .01),
       "b": jnp.float32(0.0)}


def _frame(h=160, w=128):
    return RNG.integers(0, 256, (h, w, 3)).astype(np.uint8)


# --------------------------------------------- planner / arithmetic units

def test_banded_resize_rows_are_tiling_invariant():
    """The contract slab tiling rests on: computing a row-slice of the
    banded resize from sliced tables is BITWISE equal to slicing the
    full output. (Per-output-element kernel, fixed tap order.)"""
    g = jnp.asarray(RNG.random((160, 128)).astype(np.float32))
    sh = 128                                    # downscale rows 160 -> 128
    lo, w = band_weights(160, sh)
    full = band_rows(g, jnp.asarray(lo), jnp.asarray(w))
    for a, b in [(0, 40), (37, 91), (100, sh)]:
        part = band_rows(g, jnp.asarray(lo[a:b]), jnp.asarray(w[a:b]))
        assert np.array_equal(np.asarray(part), np.asarray(full)[a:b])


def test_banded_resize_matches_reference_resize():
    """resize_banded is the same separable linear resize as
    jax.image.resize(method='linear') up to float summation order."""
    g = jnp.asarray(RNG.random((160, 128)).astype(np.float32))
    got = np.asarray(resize_banded(g, 128, 102)[:128, :102])
    want = np.asarray(jax.image.resize(g, (128, 102), "linear"))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_extended_band_tables_pad_with_zero_weight():
    """Zero-extending the tap tables (so every tile slices equal-shape
    windows) must not change any real output row."""
    lo, w = band_weights(160, 100)
    lo2, w2 = extend_band(lo, w, 128)
    assert lo2.shape[0] == 128 and w2.shape[0] == 128
    assert np.array_equal(lo2[:100], lo) and np.array_equal(w2[:100], w)
    assert np.all(w2[100:] == 0)


def test_merge_topk_matches_global_topk():
    """Per-tile local top-k lists merged with merge_topk must equal
    lax.top_k over the concatenated scores, including tie-breaking by
    lowest index and -inf phantom padding."""
    n, k, fp = 300, 32, 4
    s = RNG.random(n).astype(np.float32)
    s[50:60] = s[7]                             # ties across tiles
    idx = np.arange(n)
    locs, loci = [], []
    for d in range(fp):
        sl = slice(d * 75, (d + 1) * 75)
        st, it = jax.lax.top_k(jnp.asarray(s[sl]), k)
        locs.append(st)
        loci.append(jnp.asarray(idx[sl])[it])
    ms, mi = merge_topk(jnp.stack(locs), jnp.stack(loci), k)
    ws, wi = jax.lax.top_k(jnp.asarray(s), k)
    assert np.array_equal(np.asarray(ms), np.asarray(ws))
    assert np.array_equal(np.asarray(mi), np.asarray(wi))


def test_slab_and_scale_group_planning():
    assert slab_rows(5, 2) == 3 and slab_rows(5, 8) == 1
    assert slab_pixel_rows(3, PAPER_HOG) == 3 * 8 + 122
    per_scale = ((1.0, 5, 9), (0.8, 3, 6), (0.5, 1, 2))
    groups = scale_groups(per_scale, 2)
    assert len(groups) == 2
    assert sorted(i for g in groups for i in g) == [0, 1, 2]
    # greedy balance: the largest scale sits alone in one bin
    loads = [sum(per_scale[i][1] * per_scale[i][2] for i in g)
             for g in groups]
    assert max(loads) == 45
    # more tiles than scales -> empty groups allowed, nothing dropped
    groups8 = scale_groups(per_scale, 8)
    assert len(groups8) == 8
    assert sorted(i for g in groups8 for i in g) == [0, 1, 2]


def test_resolve_k_auto_scales_with_grid():
    auto = DetectorConfig(max_detections=0)
    assert _resolve_k(auto, 100) == 100          # clamped to n
    assert _resolve_k(auto, 60_000) == 256       # historical constant
    assert _resolve_k(auto, 244_026) == 954      # ceil(n/256) at ~4K
    pinned = DetectorConfig(max_detections=512)
    assert _resolve_k(pinned, 244_026) == 512    # explicit override wins


def test_frame_program_k_follows_auto_rule():
    cfg = DetectorConfig(scales=(1.0,))
    small = _frame_program(160, 128, cfg)
    assert small.k == min(small.n_positions, 256)
    big = _frame_program(2176, 3840, cfg)
    assert big.n_positions > 65_536
    assert big.k == -(-big.n_positions // 256)
    pin = _frame_program(2176, 3840,
                         dataclasses.replace(cfg, max_detections=512))
    assert pin.k == 512


def test_resolve_fp_and_mesh_guards():
    n = jax.device_count()
    with pytest.raises(ValueError) as ei:
        _resolve_fp(DetectorConfig(frame_parallel=n + 1))
    assert str(n) in str(ei.value)
    with pytest.raises(ValueError):
        make_tiled_mesh(1, n + 1)
    with pytest.raises(ValueError):
        make_tiled_mesh(n + 1, 1)
    mesh = make_tiled_mesh(1, 0)                 # 0 = all remaining
    assert mesh.axis_names == ("data", "tile") and mesh.size == n


def test_serve_reports_saturated_frames():
    """A pinned tiny K with an accept-everything threshold must surface
    through the service's frames_saturated counter (satellite: expose
    Detections.saturated in serve stats)."""
    cfg = DetectorConfig(score_threshold=-10.0, scales=(1.0,),
                         max_detections=4)
    svc = DetectionService(SVM, detector=cfg).start()
    try:
        res = svc.submit_frame(_frame()).get(timeout=60)
        assert "error" not in res
        assert res["saturated"] is True
        assert svc.stats["frames_saturated"] >= 1
        assert svc.stats["tile_devices"] == 1
    finally:
        svc.stop()


# ----------------------------------------------- tiled-vs-untiled identity

def _tiled_case(resize, mode, fp, h=160, w=128, scales=(1.0, 0.8)):
    """to_list() of the tiled single-frame path vs untiled, same
    pyramid_resize (identity is per resize mode; banded vs matmul
    differ in float summation order by design)."""
    base = DetectorConfig(score_threshold=-5.0, scales=scales,
                          pyramid_resize=resize)
    frame = _frame(h, w)
    plain = FrameDetector(SVM, base)
    tiled = FrameDetector(SVM, dataclasses.replace(
        base, frame_parallel=fp, tile_mode=mode))
    want = plain.detect_raw(frame).to_list()
    got = tiled.detect_raw(frame).to_list()
    assert want, "threshold must admit boxes or the test is vacuous"
    assert got == want
    return want


@multi
@pytest.mark.parametrize("resize,mode,fp", [
    ("banded", "slab", 2),
    ("banded", "slab", 3),        # non-divisible slab split
    ("matmul", "slab", 2),        # row-sliced matmul resize path
    ("banded", "scale", 2),
])
def test_tiled_matches_untiled(resize, mode, fp):
    if fp > jax.device_count():
        pytest.skip(f"needs {fp} devices")
    _tiled_case(resize, mode, fp)


@multi
@pytest.mark.parametrize("mode,fp", [("slab", 2), ("scale", 2)])
def test_tiled_matches_untiled_fixed_numerics(mode, fp):
    """numerics="fixed" across the tile mesh: slab halos recompute the
    same integer gradients/histograms bit for bit and the int8 scoring
    matmul is associative in int32, so tiled-vs-untiled must be
    byte-identical in BOTH tile modes -- the quantized chain has no
    float-summation-order escape hatch."""
    if fp > jax.device_count():
        pytest.skip(f"needs {fp} devices")
    from repro.configs import hog_svm
    base = DetectorConfig(hog=hog_svm.QUANT, score_threshold=-5.0,
                          scales=(1.0, 0.8), pyramid_resize="banded")
    frame = _frame()
    plain = FrameDetector(SVM, base)
    tiled = FrameDetector(SVM, dataclasses.replace(
        base, frame_parallel=fp, tile_mode=mode))
    want = plain.detect_raw(frame).to_list()
    got = tiled.detect_raw(frame).to_list()
    assert want, "threshold must admit boxes or the test is vacuous"
    assert got == want


@multi
def test_tiled_slab_overhang_tiles_are_masked():
    """fp larger than the smallest score grid: at 160x128/scale 1.0 the
    grid has 5 score rows, so with fp=8 several tiles own only
    overhang rows -- their candidates must be masked out, not merged."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    _tiled_case("banded", "slab", 8)


@multi
def test_tiled_scale_groups_with_empty_tiles():
    """fp=8 over 2 pyramid scales: six tiles get EMPTY scale groups and
    must contribute only phantom (-inf) rows to the merge."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    _tiled_case("banded", "scale", 8)


@multi
def test_tiled_keeps_seam_straddling_boxes():
    """Boxes whose windows span a slab seam live in the halo of the
    owning tile; they must survive tiling. With h=160 and fp=2 the
    seam sits at scaled row 3*8=24 -- every kept 128-tall window from
    score rows 0-2 crosses it."""
    dets = _tiled_case("banded", "slab", 2)
    sph = 5                                     # (160 - 128) // 8 + 1
    seam_y = slab_rows(sph, 2) * PAPER_HOG.cell
    straddle = [d for d in dets                 # box = (y0, x0, y1, x1)
                if d["box"][0] < seam_y < d["box"][2]]
    assert straddle, "no kept box straddles the slab seam"


@multi
def test_tiled_batch_matches_single_device():
    """2-D (data x tile) schedule, non-divisible B: dp=2 x fp=2 over a
    3-frame batch must match the single-device untiled batch byte for
    byte (pad-and-mask on the data axis, merge inside shard_map)."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    base = DetectorConfig(score_threshold=-5.0, scales=(1.0, 0.8),
                          pyramid_resize="banded", batch_chunk=1)
    frames = np.stack([_frame() for _ in range(3)])
    plain = FrameDetector(SVM, base)
    tiled = FrameDetector(SVM, dataclasses.replace(
        base, data_parallel=2, frame_parallel=2))
    want = [d.to_list() for d in plain.detect_batch_raw(frames)]
    got = [d.to_list() for d in tiled.detect_batch_raw(frames)]
    assert got == want
    # autotune keys carry the 2-D mesh layout (chunk pinned here, so
    # check the key formatter the report/disk cache share)
    key = _autotune_key_str((160, 128, 160, 128, 4, base, "rgb-uint8", 2, 2))
    assert key.endswith("mesh=data:2,tile:2 [rgb-uint8]")


def _gray_first(det, frames):
    """Each frame's `hog.grayscale` (a gray frame as float32),
    edge-padded to its bucket, through `_frame_program(ph, pw, cfg).raw`,
    traced as one program; leaves stacked over the frames."""
    h, w = frames[0].shape[:2]
    prog, ph, pw = det.program_for(h, w)

    @jax.jit
    def ref(x, wv, bv, hw):
        g = grayscale(x) if x.ndim == 3 else x.astype(jnp.float32)
        return prog.raw(jnp.pad(g, ((0, ph - h), (0, pw - w)), mode="edge"),
                        wv, bv, hw)

    hw = jnp.asarray([h, w], jnp.float32)
    outs = [[np.asarray(a) for a in ref(jnp.asarray(f), SVM["w"], SVM["b"],
                                        hw)] for f in frames]
    return [np.stack(leaf) for leaf in zip(*outs)]


@multi
@pytest.mark.parametrize("rgb", [True, False], ids=["rgb", "gray"])
@pytest.mark.parametrize("dp,fp", [(2, 1), (2, 2)],
                         ids=["sharded", "tiled_batch"])
def test_sharded_and_tiled_batches_answer_as_the_gray_first_reference(
        dp, fp, rgb):
    """A stacked batch of 3 over dp=2 (one masked pad frame), untiled
    (`_sharded_batch_fn`) or tiled over fp=2 (`_tiled_batch_fn`): an RGB
    stack reaches the program as (B, h, w*3) rows, and the answers are
    byte for byte those of the gray-first reference."""
    if dp * fp > jax.device_count():
        pytest.skip(f"needs {dp * fp} devices")
    # a threshold of its own: a program is noted with the arguments of
    # its first call, and RGB and gray frames of one size share it
    cfg = DetectorConfig(score_threshold=-5.0625 if rgb else -5.125,
                         scales=(1.0, 0.8), pyramid_resize="banded",
                         batch_chunk=1, data_parallel=dp, frame_parallel=fp)
    frames = [_frame() if rgb else _frame()[..., 0] for _ in range(3)]
    got = FrameDetector(SVM, cfg).detect_batch_raw(np.stack(frames))
    want = _gray_first(FrameDetector(SVM, cfg), frames)
    assert want[0].max() > -np.inf, "no window clears the threshold"
    leaves = jax.tree_util.tree_leaves(got)
    assert len(leaves) == len(want) == 4
    for a, b in zip(leaves, want):
        assert np.array_equal(np.asarray(a), b)
    fn = _tiled_batch_fn(160, 128, 160, 128, 4, dp, fp, cfg, _donate()) \
        if fp > 1 else _sharded_batch_fn(160, 128, 160, 128, 4, dp, cfg,
                                         _donate())
    assert detector._PROGRAMS[fn]["args"][0].shape == \
        (4, 160, 128 * 3 if rgb else 128)


@multi
def test_tiled_single_program_lowers_as_recorded():
    """The single-frame tiled program, taking a 160x128 RGB frame as
    (rows, w*3) bands over four tiles, lowers to the text recorded before
    its gray step became the helper every frame program shares
    (`_luma`): the in-program deinterleave changed no operation."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg = DetectorConfig(scales=(1.0, 0.8), frame_parallel=4,
                         pyramid_resize="banded", score_threshold=-5.0)
    band = NamedSharding(_tile_mesh(1, 4), P("tile"))
    text = _tiled_single_fn(160, 128, 160, 128, 4, cfg).lower(
        jax.ShapeDtypeStruct((160, 128 * 3), jnp.uint8, sharding=band),
        jax.ShapeDtypeStruct((3780,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.float32)).as_text()
    golden = pathlib.Path(__file__).parent / "golden" / \
        "tiled_single_160x128.mlir"
    assert text == golden.read_text()


@multi
def test_area_threshold_routes_small_frames_untiled():
    """frame_parallel_min_area above the bucket area: results identical
    AND no tiled program is ever built (the routing happens before the
    program cache)."""
    base = DetectorConfig(score_threshold=-5.0, scales=(1.0,),
                          pyramid_resize="banded")
    frame = _frame()
    want = FrameDetector(SVM, base).detect_raw(frame).to_list()
    misses = _tiled_single_fn.cache_info().misses
    routed = FrameDetector(SVM, dataclasses.replace(
        base, frame_parallel=0, frame_parallel_min_area=10 ** 9))
    assert routed.frame_devices == jax.device_count()
    assert routed.detect_raw(frame).to_list() == want
    assert _tiled_single_fn.cache_info().misses == misses


# ------------------------------------- the banded upload of the tiled path

def _perf_tiled(fp, **kw):
    """The perf preset as the four-chip 4K cell runs it
    (chipbench/configs/uhd_tiled4.json), at a small frame size: fused
    Pallas HOG in bf16, MXU scoring, banded resize, 3 levels."""
    from repro.api import presets
    return dataclasses.replace(
        presets("perf").detector, scales=(1.0, 0.8, 0.64),
        max_detections=0, pyramid_resize="banded", frame_parallel=fp,
        tile_mode="slab", **kw)


@multi
@pytest.mark.parametrize("h,w,rows", [
    (2160, 3840, 540),            # the 4K frame: a quarter on each chip
    (322, 256, 81),               # rows that do not split: zero rows added
])
def test_banded_upload_puts_a_row_band_on_each_tile(h, w, rows):
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    frame = np.zeros((h, w, 3), np.uint8)
    frame[h - 1] = 7
    before = tiled_uploads()
    put = _banded_put(frame, 4)
    shards = sorted(put.addressable_shards, key=lambda s: s.index[0].start)
    assert [s.device.id for s in shards] == [0, 1, 2, 3]
    assert all(s.data.shape[0] == rows for s in shards)
    assert put.shape == (4 * rows, w * 3)
    flat = np.asarray(put).reshape(4 * rows, w, 3)
    assert np.array_equal(flat[:h], frame) and not flat[h:].any()
    after = tiled_uploads()
    assert after["frames"] == before["frames"] + 1
    for d in range(4):
        assert after["bytes"][d] - before["bytes"].get(d, 0) == \
            rows * w * 3


@multi
@pytest.mark.parametrize("h,w", [(320, 256), (322, 160)])
def test_banded_upload_answers_as_the_whole_frame_and_untiled(h, w):
    """The perf preset tiled over 4 with the frame uploaded in row bands
    answers byte for byte as the same program given the whole frame on
    one device, and as the untiled program; 322 rows do not split over
    4 tiles."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    frame = _frame(h, w)
    svm = {"w": SVM["w"] * 100.0, "b": SVM["b"]}
    cfg = _perf_tiled(4, score_threshold=-5.0)
    tiled = FrameDetector(svm, cfg)
    banded = tiled.detect_raw(frame)
    _, ph, pw = tiled.program_for(h, w)
    whole = _tiled_single_fn(h, w, ph, pw, 4, cfg)(
        jnp.asarray(np.pad(frame, ((0, -h % 4), (0, 0), (0, 0)))
                    .reshape(-1, w * 3)),
        svm["w"], svm["b"], jnp.asarray([h, w], jnp.float32))
    leaves = jax.tree_util.tree_leaves(banded)
    assert len(leaves) == len(whole) == 4
    for a, b in zip(leaves, whole):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    want = FrameDetector(svm, dataclasses.replace(
        cfg, frame_parallel=1)).detect_raw(frame).to_list()
    assert want, "threshold must admit boxes or the test is vacuous"
    assert banded.to_list() == want


@multi
def test_banded_tiled_answers_agree_with_the_plain_reference():
    """People in a seeded clip, the benchmark's trained SVM: the tiled
    perf path with the banded upload keeps to the benchmark's own
    comparison (chipbench/compare.py) against the float64 reference."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    import json
    import pathlib
    import sys
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from chipbench import compare
    from chipbench.clips import make_clip
    svm = json.loads((root / "chipbench/svm/svm.json").read_text())
    frame = make_clip(np.random.default_rng(0), 320, 256, 1, 3)[0]
    cfg = _perf_tiled(4)
    dets = FrameDetector({"w": jnp.asarray(svm["w"], jnp.float32),
                          "b": jnp.float32(svm["b"])},
                         cfg).detect_raw(frame).to_list()
    got = compare.check_frame(
        dets, frame, np.asarray(svm["w"], np.float64), float(svm["b"]),
        {"detector": {"scales": list(cfg.scales), "shape_bucket": 32,
                      "score_threshold": cfg.score_threshold,
                      "nms_iou": cfg.nms_iou, "max_detections": 0}})
    assert got["boxes"] and got["ref_boxes"], got
    assert got["score_gap"] <= compare.LIMITS["score_gap"], got
    assert got["unexplained"] == 0 and got["missed"] == 0, got


@multi
def test_the_tiled_program_scopes_its_merge():
    """`op_scopes(1)` of a dispatched tiled program holds operations of
    the `merge` scope, the one the benchmark's merge reader matches."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg = _perf_tiled(4)
    FrameDetector(SVM, cfg).detect_raw(_frame(320, 256))
    fn = _tiled_single_fn(320, 256, 320, 256, 4, cfg)
    from repro.core import detector
    names = hlo_op_names(
        fn.lower(*detector._PROGRAMS[fn]["args"]).compile().as_text())
    merged = {op for op, path in names.items()
              if "merge" in path.split("/")}
    assert merged and merged <= set(op_scopes(1))
