"""tail_p95_ms: the 95th percentile (nearest rank) of frame latency in
an open-loop cell, from when each frame was due until its answer
arrived, in ms. It is set by the host's stalls more than by the
program: a stall of a few seconds delays every frame due in it and the
backlog after it, so it is reported beside frame_p50_ms, not bounded."""


def read(obs):
    return obs.end_to_end.get("frame_p95_ms")
