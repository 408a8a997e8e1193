"""resize_ms_per_frame: device self time of the frame program's
operations scoped `resize` (jax.named_scope in core/detector.py), over
the frames answered in the trace, in ms. An operation's scope is its
`op_name` in the compiled text of the program that served the window
(repro.core.detector.op_scopes); its time is its self time in the trace.
None where the program carries no scopes."""
from chipbench import stages


def read(obs):
    return stages.stage_ms_per_frame(obs, "resize")
