"""The reduction from a profiler trace to busy time, idle gaps and
kernel time, on hand-made events and on one batch recorded on the chip."""
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from chipbench import cells, trace_reduce  # noqa: E402
from chipbench.observed import Observed  # noqa: E402


def _reduce(device, host, window=(0, 1000)):
    return trace_reduce.reduce([["/device:TPU:0", n, s, d]
                                for n, s, d in device],
                               [[n, s, d] for n, s, d in host], window)


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]


def test_busy_idle_and_labels_on_hand_made_events():
    s = _reduce(device=[("fusion.1", 100, 50), ("fusion.2", 120, 60),
                        ("dense_fused_hog.11", 400, 100), ("late", 2000, 10)],
                host=[("chipbench.submit", 200, 150),
                      ("chipbench.wait", 500, 500)])
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(180e-9)      # [100,180) + [400,500)
    assert s.kernel_s(r"^dense_fused_hog") == pytest.approx(100e-9)
    # gaps: [0,100) none, [180,400) submit, [500,1000) wait
    assert s.gaps[0] == ("chipbench.wait", pytest.approx(500e-9))
    assert s.gaps[1] == ("chipbench.submit", pytest.approx(220e-9))
    assert s.gaps[2] == ("no benchmark span", pytest.approx(100e-9))
    b = s.breakdown()
    assert b["device_ops"][0][0] == "dense_fused_hog.11"
    assert len(b["idle_gaps"]) == 3


@pytest.mark.parametrize("event_name,op", [
    ("%dense_fused_hog.3 = bf16[4,36,57,76]{3,2,1,0:T(8,128)(2,1)} "
     "custom-call(f32[4,384,512]{2,1,0:T(8,128)} %fusion.7), "
     "custom_call_target=\"tpu_custom_call\"", "dense_fused_hog.3"),
    ("%add_add_fusion.14 = f32[4,3168]{1,0:T(4,128)S(1)} fusion(), "
     "kind=kLoop", "add_add_fusion.14"),
    ("score_matmul.5", "score_matmul.5")])
def test_a_device_event_is_named_by_its_hlo_instruction(event_name, op):
    """The TPU's trace names an operation by its whole HLO text."""
    assert trace_reduce.op_name(event_name) == op


def test_a_loop_event_counts_only_the_time_its_body_leaves_uncovered():
    """A `while` spans its body's operations on the same line: the body's
    operations keep their own time, the loop its self time, and the
    busy time is counted once."""
    s = _reduce(device=[("while.1", 100, 200), ("fusion.2", 120, 60),
                        ("dense_fused_hog.3", 200, 60), ("copy.4", 400, 10)],
                host=[])
    assert s.op_s["while.1"] == pytest.approx(80e-9)
    assert s.op_s["fusion.2"] == pytest.approx(60e-9)
    assert s.kernel_s(r"^dense_fused_hog") == pytest.approx(60e-9)
    assert s.busy_s == pytest.approx(210e-9)
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        _reduce([("x", 0, 1)], [], window=(5, 5))


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    cells.benchmark()["per_layer"]])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    obs = Observed(seconds=1.0, end_to_end={}, engine={}, stage_timing=[],
                   trace=None, trace_frames=0, costs={}, peak={})
    assert cells.metric_reader(metric).read(obs) is None


@pytest.mark.parametrize("metric", ["hog_roofline", "score_roofline"])
def test_a_kernel_pattern_that_matches_no_op_fails_loudly(metric):
    s = _reduce(device=[("fusion.1", 100, 50)], host=[])
    obs = Observed(seconds=1.0, end_to_end={}, engine={}, stage_timing=[],
                   trace=s, trace_frames=3,
                   costs={"hog": (10, 10), "score": (10, 10)},
                   peak={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9})
    with pytest.raises(LookupError, match="matches"):
        cells.metric_reader(metric).read(obs)


@pytest.mark.parametrize("metric,name,hit", [
    ("hog_roofline", "dense_fused_hog.11", True),
    ("hog_roofline", "dense_fused_hog", True),
    ("hog_roofline", "fusion.12", False),
    ("score_roofline", "score_matmul.37", True),
    ("score_roofline", "score_matmul_int8.2", False),
    ("score_roofline", "dense_fused_hog.3", False)])
def test_kernel_patterns_match_the_compiled_names(metric, name, hit):
    """The custom calls of the compiled program are named after the
    Pallas kernels' jitted wrappers, one per pyramid level."""
    import re
    assert bool(re.search(cells.metric_reader(metric).PATTERN, name)) is hit


@pytest.fixture(scope="module")
def chip_batch():
    """One batch of 8 frames of the archive cell, recorded on the chip
    (data/vga_archive_trace.json), reduced over its window."""
    import json
    rec = json.loads((HERE / "data" / "vga_archive_trace.json").read_text())
    device = [[rec["plane"], n, s, d] for n, s, d in rec["ops"]]
    return rec, trace_reduce.reduce(device, [], tuple(rec["window"]))


def test_a_recorded_chip_batch_reduces_to_its_kernels(chip_batch):
    """Each of the 6 pyramid levels runs the HOG and the scoring kernel
    once for each of the batch's 2 chunks of 4 frames; self times add up
    to the busy time; every operation is named by its HLO name alone."""
    import re
    rec, s = chip_batch
    for metric in ("hog_roofline", "score_roofline"):
        rx = re.compile(cells.metric_reader(metric).PATTERN)
        assert sum(bool(rx.search(n)) for n, _, _ in rec["ops"]) == 12
        assert s.kernel_s(rx.pattern) > 0
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s, rel=1e-9)
    assert 0 < s.busy_s < s.window_s
    assert not any(" " in n or n.startswith("%") for n in s.op_s)
    assert all(label == "no benchmark span" for label, _ in s.gaps)


@pytest.mark.parametrize("metric", ["device_ms_per_frame.vga",
                                    "hog_roofline.vga", "score_roofline.vga"])
def test_a_recorded_chip_batch_reads_within_bounds(chip_batch, metric):
    """The per-layer readers on the recorded batch: device time per
    frame near the chip runs' 0.80 ms, each kernel's share of its
    roofline above 0 and at most 100 %."""
    rec, s = chip_batch
    cfg = cells.config("vga_perf")
    obs = Observed(seconds=s.window_s, end_to_end={}, engine={},
                   stage_timing=[], trace=s, trace_frames=rec["frames"],
                   costs={k: m.cost(480, 640, cfg["detector"],
                                    cfg["precision"])
                          for k, m in cells.costs(cfg["costs"]).items()},
                   peak=cells.peaks("TPU v5 lite"))
    v = cells.metric_reader(metric).read(obs)
    if metric.startswith("device_ms"):
        assert 0.5 < v < 1.2
    else:
        assert 0 < v <= 100
