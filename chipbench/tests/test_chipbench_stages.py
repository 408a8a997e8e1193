"""The program's spans and scopes beside the trace (chipbench/stages.py):
stage names from `op_name` paths, device time by stage, idle gaps
labelled by program spans, and the readers of the metrics that rest on
them, on hand-made events."""
import pathlib
import sys
import types

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from chipbench import cells, stages, trace_reduce  # noqa: E402
from chipbench.observed import Observed  # noqa: E402
from repro.obs.spans import Span  # noqa: E402

NEW_READERS = ("batch_idle_share.vga", "service_p95_ms.single_cam",
               "program_load_s.vga", "resize_ms_per_frame.vga",
               "collate_ms_per_frame.vga", "nms_ms_per_frame.vga")


@pytest.mark.parametrize("op_name,want", [
    ("jit(fn)/resize/dot_general", "resize"),
    ("jit(fn)/jit(score_map)/score/collate/add", "score/collate"),
    ("jit(one)/vmap(jit(score_map))/score/matmul/jit(score_matmul)",
     "score/matmul"),
    ("jit(one)/vmap(jit(score_map))/hog/jit(dense_fused_hog)/while/body",
     "hog"),
    ("jit(one)/vmap(nms)/while/body/closed_call/gather", "nms"),
    ("jit(fn)/while/body/closed_call/vmap(gray)/mul", "gray"),
    ("jit(fn)/select/jit(_where)/select_n", "select"),
    ("jit(fn)/jit(score_map)/score/reshape", None),
    ("reduce_sum", None),
    ("", None)])
def test_a_stage_is_the_innermost_named_scope_of_an_op_name(op_name, want):
    assert stages.stage(op_name) == want


def _ev(op, s, d, module="jit_fn", scope=""):
    return ["/device:TPU:0", op, s, d, module, scope]


def test_device_time_sums_by_stage_with_unscoped_and_other_modules():
    """Self time by stage: a loop's own time is what its body leaves;
    an op of the frame program without a stage is `unscoped`; an op of
    another program goes by its module."""
    dev = [_ev("while.1", 100, 200, scope="jit(fn)/nms/while"),
           _ev("fusion.2", 120, 60, scope="jit(fn)/nms/while/body/and"),
           _ev("fusion.3", 300, 50, scope="jit(fn)/vmap(resize)/dot"),
           _ev("copy.4", 350, 10, scope="jit(fn)/reshape"),
           _ev("slice.1", 400, 5, module="jit__getitem"),
           _ev("late.9", 2000, 10, scope="jit(fn)/resize/x")]
    got = stages.event_stage_s(dev, (0, 1000))
    assert got == {"nms": pytest.approx(200e-9),
                   "resize": pytest.approx(50e-9),
                   stages.UNSCOPED: pytest.approx(10e-9),
                   "jit__getitem": pytest.approx(5e-9)}
    by_op = stages.event_stage_s(dev, (0, 1000), by_op=True)
    assert by_op["nms/while.1"] == pytest.approx(140e-9)
    assert by_op["jit__getitem/slice.1"] == pytest.approx(5e-9)
    s = trace_reduce.reduce([ev[:4] for ev in dev], [], (0, 1000))
    scopes = {ev[1]: ev[5] for ev in dev}
    assert stages.stage_s(s.op_s, scopes) == pytest.approx(
        {"nms": 200e-9, "resize": 50e-9, stages.UNSCOPED: 15e-9})


def test_spans_map_onto_the_trace_clock_through_the_tick():
    """A span read on perf_counter lands where the tick, read at
    `t_tick` on the host, started on the trace (`tick_ns`)."""
    got = stages.on_trace_clock(
        [Span("serve.run", 10.5, 10.75, 4, 2, None, 2)], 10.0, 1000.0)
    assert got == [["serve.run", pytest.approx(1000.0 + 0.5e9),
                    pytest.approx(1000.0 + 0.75e9), 4, 2, 2]]


def _span(name, s, e, sid, parent=None, batch=None):
    return [name, s, e, sid, parent, batch]


PROGRAM = [
    _span("serve.batch", 100, 600, 1, None, 1),
    _span("serve.gather", 100, 150, 2, 1, 1),
    _span("serve.run", 150, 300, 3, 1, 1),
    _span("detect.upload", 160, 250, 4, 3, 1),
    _span("serve.decode", 300, 590, 5, 1, 1),
    _span("detect.fetch", 310, 500, 6, 5, 1),
    _span("host.gc", 520, 560, 7, 5, 1),
    _span("serve.request", 50, 600, 8, None, 1),
]
BENCH = [("chipbench.wait", 0, 1000)]


@pytest.mark.parametrize("gap,want", [
    ((170, 240), "detect.upload"),      # inside a leaf
    ((110, 240), "detect.upload"),      # gather 40, upload 80
    ((320, 480), "detect.fetch"),
    ((505, 580), "host.gc"),             # a stall wins where it overlaps
    ((620, 900), stages.IDLE),           # the worker held no batch
    ((580, 700), stages.IDLE),           # 20 in the batch, 100 outside
])
def test_a_gap_goes_to_the_span_that_covers_it_with_its_own_time(gap, want):
    s, e = gap
    over = [p for p in PROGRAM if p[2] > s and p[1] < e
            and p[0] not in stages.REQUEST_SPANS]
    assert stages.label_gap(s, e, over, BENCH) == want


def test_gaps_fall_back_to_the_benchmark_spans_without_program_spans():
    dev = [_ev("a", 100, 50), _ev("b", 400, 100)]
    gaps = stages.labelled_gaps(dev, [], BENCH, (0, 1000))
    assert [g[2] for g in gaps] == ["chipbench.wait"] * 3
    assert stages.label_s(gaps) == {"chipbench.wait": pytest.approx(850e-9)}


def test_every_gap_of_a_window_is_labelled_by_a_program_span():
    """The sweep labels each gap of the window (a stall wins wherever it
    overlaps one), and the labels' idle time adds up to the window's."""
    dev = [_ev("up", 255, 40), _ev("run", 520, 5)]
    gaps = stages.labelled_gaps(dev, PROGRAM, BENCH, (0, 1000))
    assert [(s, e, n) for s, e, n in gaps] == [
        (0, 255, stages.IDLE), (295, 520, "detect.fetch"),
        (525, 1000, "host.gc")]
    assert sum(stages.label_s(gaps).values()) == pytest.approx(955e-9)
    assert stages.idle_in_batches_s(dev, PROGRAM, (0, 1000)) == \
        pytest.approx(455e-9)


def test_host_self_time_leaves_children_to_the_children():
    got = stages.host_self_s(PROGRAM, (0, 1000))
    assert got["serve.batch"] == pytest.approx(10e-9)
    assert got["serve.run"] == pytest.approx(60e-9)
    assert got["serve.decode"] == pytest.approx(60e-9)
    assert got["detect.fetch"] == pytest.approx(190e-9)
    assert got["serve.request"] == pytest.approx(550e-9)


# ------------------------------------------------------------- readers
def _obs(trace=None, frames=4, batches=2):
    return Observed(seconds=1.0, end_to_end={}, engine={
        "frames": frames, "frame_batches": batches, "frame_target": 2},
        stage_timing=[], trace=trace, trace_frames=frames, costs={},
        peak={})


@pytest.fixture
def recorded(monkeypatch):
    """Hand-made spans in place of the process's recorder: a batch
    before the window, then two batches of two requests each."""
    S = Span
    spans = [S("serve.batch", 0.0, 0.9, 1, None, None, 1),
             S("serve.request", 0.0, 1.0, 2, None, 1, 1),
             S("serve.batch", 2.0, 2.5, 3, None, None, 3),
             S("serve.request", 1.9, 2.4, 4, None, 2, 3),
             S("serve.request", 1.95, 2.45, 5, None, 3, 3),
             S("serve.batch", 2.5, 2.75, 6, None, None, 6),
             S("serve.request", 2.0, 2.7, 7, None, 4, 6),
             S("serve.request", 2.1, 2.74, 8, None, 5, 6)]
    monkeypatch.setattr(stages, "recorded_spans", lambda: spans)
    return spans


def test_batch_idle_share_is_batch_time_less_busy_time(recorded):
    s = trace_reduce.reduce([["/device:TPU:0", "f", 0, 0.25e9]], [],
                            (0, 1e9))
    assert cells.metric_reader("batch_idle_share.vga").read(
        _obs(trace=s)) == pytest.approx(100 * (0.75 - 0.25) / 1.0)


def test_service_p95_reads_the_window_requests_only(recorded):
    v = cells.metric_reader("service_p95_ms.single_cam").read(_obs())
    assert v == pytest.approx(700.0)
    assert cells.metric_reader("service_p95_ms.single_cam").read(
        _obs(frames=9)) is None


def test_program_load_s_sums_the_load_counter(monkeypatch):
    from repro.core import detector
    monkeypatch.setattr(detector, "program_loads", lambda: [
        {"seconds": 1.5}, {"seconds": 2.25}])
    assert cells.metric_reader("program_load_s.vga").read(_obs()) == 3.75


@pytest.mark.parametrize("metric,stage", [
    ("resize_ms_per_frame.vga", "resize"),
    ("collate_ms_per_frame.hd", "score/collate"),
    ("nms_ms_per_frame.vga", "nms")])
def test_stage_readers_take_scopes_from_the_program_that_served(
        monkeypatch, metric, stage):
    from repro.core import detector
    asked = []

    def op_scopes(batch):
        asked.append(batch)
        return {"fusion.1": "jit(one)/vmap(resize)/dot",
                "fusion.2": "jit(one)/vmap(jit(score_map))/score/collate/x",
                "while.3": "jit(one)/vmap(nms)/while"}

    monkeypatch.setattr(detector, "op_scopes", op_scopes)
    dev = [["/device:TPU:0", "fusion.1", 0, 4e6],
           ["/device:TPU:0", "fusion.2", 5e6, 2e6],
           ["/device:TPU:0", "while.3", 8e6, 1e6]]
    s = trace_reduce.reduce(dev, [], (0, 1e9))
    want = {"resize": 4.0, "score/collate": 2.0, "nms": 1.0}[stage] / 4
    assert cells.metric_reader(metric).read(
        _obs(trace=s, frames=4, batches=1)) == pytest.approx(want)
    assert asked == [4]


def test_stage_readers_read_nothing_from_a_program_without_scopes(
        monkeypatch):
    from repro.core import detector
    monkeypatch.setattr(detector, "op_scopes",
                        lambda batch: {"fusion.1": "jit(fn)/dot"})
    s = trace_reduce.reduce([["/device:TPU:0", "fusion.1", 0, 4e6]], [],
                            (0, 1e9))
    assert cells.metric_reader("resize_ms_per_frame.vga").read(
        _obs(trace=s)) is None


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_new_reader_reads_nothing_from_an_older_program(monkeypatch,
                                                          metric):
    """A program with no span recorder and no load or scope counters
    (the parent of the change that added them): each reader returns
    None, and raises nothing."""
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    monkeypatch.setitem(sys.modules, "repro.core.detector",
                        types.ModuleType("repro.core.detector"))
    s = trace_reduce.reduce([["/device:TPU:0", "fusion.1", 0, 4e6]], [],
                            (0, 1e9))
    assert cells.metric_reader(metric).read(_obs(trace=s)) is None


# ------------------------------------------- a batch recorded on the chip
@pytest.fixture(scope="module")
def chip_batch():
    """One B=8 batch of the archive cell recorded on the chip with the
    program's spans (data/vga_archive_stages.json)."""
    import json
    rec = json.loads((HERE / "data" / "vga_archive_stages.json").read_text())
    device = [[rec["plane"], op, s, d, rec["modules"][m], rec["op_names"][c]]
              for op, s, d, m, c in rec["ops"]]
    return rec, device


def test_a_recorded_chip_batch_reduces_to_stages(chip_batch):
    """Stages and the answer-slicing modules cover at least 95 % of the
    batch's device time; every stage of the frame program shows, and the
    fusions the trace names `slice_reduce_fusion` are the score
    collate."""
    rec, device = chip_batch
    window = tuple(rec["window"])
    s = trace_reduce.reduce([ev[:4] for ev in device], [], window)
    by = stages.event_stage_s(device, window)
    assert sum(by.values()) == pytest.approx(s.busy_s, rel=1e-9)
    assert by.get(stages.UNSCOPED, 0.0) <= 0.05 * s.busy_s
    for st in stages.STAGES:
        assert by[st] > 0, st
    assert by["score/collate"] == max(by.values())
    names = {ev[1]: ev[5] for ev in device}
    assert all(stages.stage(names[n]) == "score/collate"
               for n in names if n.startswith("slice_reduce_fusion"))


def test_a_recorded_chip_batch_labels_all_its_idle_time(chip_batch):
    """Every idle gap of the batch goes to a program span, none to the
    benchmark's own spans, and the labels add up to the idle time."""
    rec, device = chip_batch
    window = tuple(rec["window"])
    s = trace_reduce.reduce([ev[:4] for ev in device], [], window)
    gaps = stages.labelled_gaps(device, rec["spans"],
                                [tuple(b) for b in rec["bench"]], window)
    by = stages.label_s(gaps)
    assert sum(by.values()) == pytest.approx(s.window_s - s.busy_s,
                                             rel=1e-9)
    program = {p[0] for p in rec["spans"]} | {stages.IDLE}
    assert set(by) <= program
    assert max(by, key=by.get) == "detect.slice"
