"""service_p95_ms: the 95th percentile (nearest rank) of the program's
`serve.request` spans (repro.obs.spans: from `submit_frame` to the
request's answer) over the frames answered in the traced window, in
ms: the service's own part of `tail_p95_ms`, without the client's
lateness. None where the program records no spans."""
import math

from chipbench import stages


def read(obs):
    ms = stages.request_ms(obs)
    if not ms:
        return None
    ms.sort()
    return ms[max(0, math.ceil(0.95 * len(ms)) - 1)]
