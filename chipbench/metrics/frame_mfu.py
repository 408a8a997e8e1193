"""frame_mfu: the frame's operations (the configuration's kernels in
chipbench/costs/) against the chip's bf16 peak, in %. In a cell that
reports frames_per_s: operations per frame times frames answered per
second, over the peak. In a cell that reports frame_p50_ms: one frame's
operations over what the peak does in the median frame latency."""


def read(obs):
    rate = obs.end_to_end.get("frames_per_s")
    if rate:
        return 100.0 * obs.ops_per_frame * rate / obs.peak["bf16_flops"]
    p50 = obs.end_to_end.get("frame_p50_ms")
    if p50:
        return 100.0 * obs.ops_per_frame / (p50 / 1e3 * obs.peak["bf16_flops"])
    return None
