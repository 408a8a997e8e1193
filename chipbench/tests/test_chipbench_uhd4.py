"""The readers that the four-chip 4K cell adds: the tiled program's
`merge` scope, the tiled upload's counter and the frame's share of the
peak of every chip in the trace. Each reads a number where the program
has what it reads, and None, never 0 and never an error, where it does
not (an older program)."""
import pathlib
import sys
import types

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from chipbench import cells, trace_reduce  # noqa: E402
from chipbench.observed import Observed  # noqa: E402

SCOPED = ("merge_ms_per_frame.uhd4", "nms_ms_per_frame.uhd4",
          "collate_ms_per_frame.uhd4", "resize_ms_per_frame.uhd4",
          "gray_ms_per_frame.uhd4")
READERS = SCOPED + ("upload_mb_per_chip.uhd4", "frame_mfu.uhd4")
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _obs(trace=None, frames=4, e2e=None):
    return Observed(seconds=1.0, end_to_end=e2e or {"frame_p50_ms": 20.0},
                    engine={"frames": frames, "frame_batches": frames,
                            "frame_target": 1},
                    stage_timing=[], trace=trace, trace_frames=frames,
                    costs={"hog": (10 ** 9, 10 ** 8)}, peak=PEAK)


def _four_chips():
    """Four ops on each of four chips: a gray gather, a resize, a merge
    sort and an NMS loop."""
    dev = []
    for c in range(4):
        dev += [[f"/device:TPU:{c}", "all-gather.2", 10e6, 1e6],
                [f"/device:TPU:{c}", "fusion.5", 12e6, 3e6],
                [f"/device:TPU:{c}", "sort.7", 0, 2e6],
                [f"/device:TPU:{c}", "while.3", 3e6, 5e6]]
    return trace_reduce.reduce(dev, [], (0, 1e9))


@pytest.mark.parametrize("metric,ms", [
    ("merge_ms_per_frame.uhd4", 2.0), ("nms_ms_per_frame.uhd4", 5.0),
    ("gray_ms_per_frame.uhd4", 1.0), ("resize_ms_per_frame.uhd4", 3.0)])
def test_scope_readers_sum_their_scope_over_the_chips(monkeypatch, metric,
                                                      ms):
    from repro.core import detector
    monkeypatch.setattr(detector, "op_scopes", lambda batch: {
        "all-gather.2": "jit(fn)/shard_map/gray/all_gather",
        "fusion.5": "jit(fn)/shard_map/resize/dot_general",
        "sort.7": "jit(fn)/merge/sort",
        "while.3": "jit(fn)/nms/while"})
    got = cells.metric_reader(metric).read(
        _obs(trace=_four_chips(), frames=4))
    assert got == pytest.approx(4 * ms / 4)


@pytest.mark.parametrize("metric", SCOPED)
def test_scope_readers_read_nothing_where_the_program_cannot_be_lowered(
        monkeypatch, metric):
    """An older detector noted a one-device placement for arguments it
    left uncommitted, so lowering its four-chip program again from
    that note raises: None, nothing raised."""
    from repro.core import detector

    def op_scopes(batch):
        raise ValueError("Received incompatible devices for jitted "
                         "computation")

    monkeypatch.setattr(detector, "op_scopes", op_scopes)
    assert cells.metric_reader(metric).read(
        _obs(trace=_four_chips())) is None


@pytest.mark.parametrize("metric", SCOPED)
def test_scope_readers_raise_any_other_lowering_error(monkeypatch, metric):
    """Only the older program's refusal reads as None: any other error
    of lowering the served program again is the program's own fault and
    fails the run."""
    from repro.core import detector

    def op_scopes(batch):
        raise ValueError("Mosaic failed to lower the kernel")

    monkeypatch.setattr(detector, "op_scopes", op_scopes)
    with pytest.raises(ValueError, match="Mosaic"):
        cells.metric_reader(metric).read(_obs(trace=_four_chips()))


@pytest.mark.parametrize("op_name,want", [
    ("jit(fn)/merge/sort", True),
    ("jit(fn)/vmap(merge)/sort", True),
    ("jit(fn)/while/body/merge;jit(fn)/nms/x", True),
    ("jit(fn)/merged/sort", False),
    ("jit(fn)/nms/while", False),
    ("", False)])
def test_the_merge_scope_is_a_whole_component_of_the_path(op_name, want):
    assert cells.metric_reader("merge_ms_per_frame.uhd4").scoped(
        op_name) is want


def test_upload_reader_reads_the_busiest_chip_per_frame(monkeypatch):
    from repro.core import detector
    monkeypatch.setattr(detector, "tiled_uploads", lambda: {
        "frames": 3, "bytes": {0: 3 * 6_220_800, 1: 3 * 6_220_800,
                               2: 3 * 6_220_800, 3: 3 * 6_912_000}})
    got = cells.metric_reader("upload_mb_per_chip.uhd4").read(_obs())
    assert got == pytest.approx(6.912)


def test_frame_mfu_uhd4_shares_the_peak_of_every_chip_in_the_trace():
    trace = _four_chips()
    one = cells.metric_reader("frame_mfu.hd").read(_obs(trace=trace))
    four = cells.metric_reader("frame_mfu.uhd4").read(_obs(trace=trace))
    assert trace.chips == 4 and four == pytest.approx(one / 4)
    rate = _obs(trace=trace, e2e={"frames_per_s": 30.0})
    assert cells.metric_reader("frame_mfu.uhd4").read(rate) == \
        pytest.approx(100.0 * 1e9 * 30.0 / (4 * 197e12))


@pytest.mark.parametrize("metric", READERS)
def test_a_uhd4_reader_reads_nothing_from_an_older_program(monkeypatch,
                                                            metric):
    """A program with no `merge` scope and no upload counter (the parent
    of the change that added them), traced or not: None, nothing
    raised. frame_mfu needs a trace alone."""
    monkeypatch.setitem(sys.modules, "repro.core.detector",
                        types.ModuleType("repro.core.detector"))
    reader = cells.metric_reader(metric)
    assert reader.read(_obs(trace=None)) is None
    if metric != "frame_mfu.uhd4":
        assert reader.read(_obs(trace=_four_chips())) is None


def test_readers_read_nothing_where_the_program_has_none_of_it(
        monkeypatch):
    """A program with scopes but no `merge` (the untiled one), and a
    counter that no tiled frame moved."""
    from repro.core import detector
    monkeypatch.setattr(detector, "op_scopes", lambda batch: {
        "while.3": "jit(fn)/nms/while"})
    monkeypatch.setattr(detector, "tiled_uploads",
                        lambda: {"frames": 0, "bytes": {}})
    obs = _obs(trace=_four_chips())
    assert cells.metric_reader("merge_ms_per_frame.uhd4").read(obs) is None
    assert cells.metric_reader("upload_mb_per_chip.uhd4").read(obs) is None
