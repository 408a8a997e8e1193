"""resize_ms_per_frame on a cell whose frames run over several chips:
the reading of chipbench/metrics/resize_ms_per_frame.py (device self
time of the operations scoped `resize`, per frame answered in the
trace, in ms, summed over the chips), or None where an older detector
cannot hand back the scopes of its multi-chip program
(merge_ms_per_frame.stage_ms)."""
from chipbench import cells


def read(obs):
    return cells.metric_reader("merge_ms_per_frame").stage_ms(obs, "resize")
