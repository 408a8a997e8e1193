"""queue_ms: mean wait of a frame in the service, from submit to the
dispatch of its batch (engine `stage_timing` events), in ms."""


def read(obs):
    n = sum(e["n"] for e in obs.stage_timing)
    if not n:
        return None
    return sum(e["queue_ms_mean"] * e["n"] for e in obs.stage_timing) / n
