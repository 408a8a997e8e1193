"""frame_mfu on a cell that runs its frames over several chips: the
formula of chipbench/metrics/frame_mfu.py against the bf16 peak of every
chip the trace holds (`obs.trace.chips`), in %. None without a trace."""


def read(obs):
    if obs.trace is None:
        return None
    peak = obs.peak["bf16_flops"] * obs.trace.chips
    rate = obs.end_to_end.get("frames_per_s")
    if rate:
        return 100.0 * obs.ops_per_frame * rate / peak
    p50 = obs.end_to_end.get("frame_p50_ms")
    if p50:
        return 100.0 * obs.ops_per_frame / (p50 / 1e3 * peak)
    return None
