"""gray_ms_per_frame on a cell whose frames run over several chips:
device self time of the operations scoped `gray` (jax.named_scope in
core/detector.py), per frame answered in the trace, in ms, summed over
the chips. In the tiled program that scope holds each chip's gray
conversion of its own row band and the all_gather of the bands into the
whole gray plane, so this is the device's part of the banded upload;
the host-to-chip copy itself is no device operation and shows in
`frame_p50_ms`. None where the program carries no scopes, or where an
older detector cannot hand back those of its multi-chip program
(merge_ms_per_frame.stage_ms)."""
from chipbench import cells


def read(obs):
    return cells.metric_reader("merge_ms_per_frame").stage_ms(obs, "gray")
